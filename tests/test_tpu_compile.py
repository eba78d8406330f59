"""Compile the HPCG main path for a described TPU v5e, with no chip.

The TPU compiler is installed with jax and compiles for a topology that is
described, not attached: it refuses what the chip would refuse (unaligned
kernel slices, VMEM overuse, programs over the device's memory), which the
interpret-mode tests cannot see. Shapes are HPCG's reference local grid,
104^3 rows per chip, given as ``ShapeDtypeStruct``s; nothing runs.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and under pytest-xdist every worker
imports this file. Code that asks ``jax.default_backend()`` still sees the
CPU here, so the tests switch the kernels' interpret mode off themselves.
"""
import os
import re
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.core.distributed import DistSparseMatrix, _part_spec
from repro.core.formats import COO, DIA
from repro.core.solvers import cg, operator
from repro.kernels import dia_spmv as dia_kernel
from repro.kernels import ops as kops
from repro.launch.mesh import make_mesh
from repro.obs import metrics

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from bench import scopes  # noqa: E402

G = 104                  # HPCG reference local grid edge
M = G ** 3               # rows per chip: 1,124,864
NDIAG = 27               # 27-point stencil
HBM_BYTES = 16 * 10 ** 9  # v5e: 16 GB per chip


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture
def native(monkeypatch):
    """Kernels compile through Mosaic (not the CPU interpreter), and the
    persistent compile cache is off: these programs cannot be read back
    without a chip."""
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.setattr(kops, "interpret_mode", lambda: False)
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)


def _device_bytes(compiled) -> int:
    ma = compiled.memory_analysis()
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes + ma.generated_code_size_in_bytes)


def _custom_calls(compiled) -> int:
    return compiled.as_text().count('custom_call_target="tpu_custom_call"')


def _unscoped_loop_ops(compiled):
    """The fusions, custom calls and dots of the solve's loop that name no
    program layer (``bench.scopes``): a device profile could not place
    them. The TPU compiler's own ``ConcatBitcast`` calls, which join the
    slices of a vector it moves between memories, carry no metadata and
    are left out."""
    return [line for line in scopes.loop_ops(compiled.as_text())
            if scopes.scope_of(line) is None
            and 'custom_call_target="ConcatBitcast"' not in line]


def _dia_paths(scope):
    """DIA kernel calls traced in ``scope``, by the home of ``x``."""
    return {p: scope.delta(f"kernel.dia.x_{p}")
            for p in ("resident", "streamed")}


def _dia(sds):
    return DIA(sds(NDIAG, dtype=jnp.int32), sds(NDIAG, M), (M, M), NDIAG * M)


def _table_dia(nd):
    """A row-sharded DIA part over ``nd`` chips, as ``build_dist_matrix``
    stores it: the lane-tiled table of every chip's rows, offsets end to
    end."""
    return DIA(jax.ShapeDtypeStruct((nd * NDIAG,), jnp.int32),
               jax.ShapeDtypeStruct((NDIAG, nd * M // 128, 128), jnp.float32),
               (M, M), NDIAG * M)


def _placed(part, mesh):
    """``part``'s arrays with the shardings the distributed layer gives."""
    return jax.tree.map(
        lambda a, spec: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=NamedSharding(mesh, spec)),
        part, _part_spec(part, "rows"))


_RESULT = re.compile(r"^(?:ROOT )?%?[\w.\-]+ = [a-z]\w*\[([\d,]*)\]\S* ([\w\-]+)\(")
_FREE = ("parameter", "get-tuple-element", "bitcast", "tuple")


def _table_passes(text, elems=NDIAG * M):
    """Instructions, the DIA kernel's calls and free ones (parameters,
    bitcasts, tuple plumbing) left out, whose result holds ``elems``
    values in any shape: a copy, squeeze or relayout of the whole
    diagonal table."""
    out = []
    for line in text.split("\n"):
        m = _RESULT.match(line.strip())
        if m is None or m.group(2) in _FREE:
            continue
        if 'custom_call_target="tpu_custom_call"' in line:
            continue
        dims = [int(d) for d in m.group(1).split(",") if d]
        if dims and int(np.prod(dims)) == elems:
            out.append(line.strip())
    return out


def _loop_reduces(text, elems):
    """``reduce`` instructions of the solve's loop bodies whose result
    holds ``elems`` values: a squeeze of a stacked shard array."""
    comps, entry = scopes._computations(text)
    bodies = set(re.findall(r"\bbody=%?([\w.\-]+)", text))
    out = []
    for name in bodies:
        for line in comps.get(name, []):
            m = _RESULT.match(line)
            if m is None or m.group(2) != "reduce":
                continue
            dims = [int(d) for d in m.group(1).split(",") if d]
            if dims and int(np.prod(dims)) == elems:
                out.append(line)
    return out


def _dia_tables(scope):
    """DIA kernel calls traced in ``scope``, by how the table arrived."""
    return {p: scope.delta(f"kernel.dia.table_{p}")
            for p in ("tiled", "relaid")}


def test_dia_kernel_compiles_at_104cubed(topo, native):
    one = SingleDeviceSharding(topo.devices[0])

    def sds(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    A = _dia(sds)
    assert kops.default_config(A)["tm"] % dia_kernel.row_unit(jnp.float32) == 0
    with metrics.scope() as s:
        compiled = jax.jit(kops.dia_spmv).lower(A, sds(M)).compile()
    # the padded x (4.8 MB) fits VMEM: no per-diagonal window DMAs
    assert _dia_paths(s) == {"resident": 1, "streamed": 0}
    assert _custom_calls(compiled) == 1
    assert _device_bytes(compiled) < HBM_BYTES


def test_cg_pallas_compiles_at_104cubed(topo, native):
    """The one-chip CG solve over a 104^3 DIA matrix, kernel in the loop."""
    one = SingleDeviceSharding(topo.devices[0])

    def sds(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    solve = jax.jit(lambda a, b: cg(operator(a, backend="pallas"), b,
                                    tol=1e-7, maxiter=500))
    with metrics.scope() as s:
        compiled = solve.lower(_dia(sds), sds(M)).compile()
    assert _dia_paths(s)["streamed"] == 0 < _dia_paths(s)["resident"]
    assert _custom_calls(compiled) >= 1
    assert _device_bytes(compiled) < HBM_BYTES
    assert _unscoped_loop_ops(compiled) == []


def test_dist_cg_pallas_compiles_on_1_chip(topo, native):
    """The one-chip CG as the benchmark builds it: a 1-shard
    ``DistSparseMatrix`` whose local part is a row-sharded DIA table and
    whose remote part is the empty placeholder. The table goes into the
    kernel as stored: no op outside the kernel copies, squeezes or relays
    it out."""
    mesh = make_mesh((1,), ("rows",), devices=topo.devices[:1])
    remote = COO(*(jax.ShapeDtypeStruct((1,), t)
                   for t in (jnp.int32, jnp.int32, jnp.float32)), (M, 1), 1)
    A = DistSparseMatrix(_placed(_table_dia(1), mesh), _placed(remote, mesh),
                         nshards=1, mp=M, shape=(M, M), axis="rows",
                         halo_mode="neighbor", hw=0, remote_empty=True)
    b = jax.ShapeDtypeStruct((M,), jnp.float32,
                             sharding=NamedSharding(mesh, P("rows")))
    solve = jax.jit(lambda a, bb: cg(operator(a, mesh, backend="pallas"), bb,
                                     tol=1e-7, maxiter=500))
    with metrics.scope() as s:
        compiled = solve.lower(A, b).compile()
    text = compiled.as_text()
    assert _dia_paths(s)["streamed"] == 0 < _dia_paths(s)["resident"]
    assert _dia_tables(s)["relaid"] == 0 < _dia_tables(s)["tiled"]
    assert _table_passes(text) == []
    assert _loop_reduces(text, 1) == []
    assert _device_bytes(compiled) < HBM_BYTES
    assert _unscoped_loop_ops(compiled) == []


def test_dist_cg_pallas_compiles_on_4_chips(topo, native):
    """Weak-scaled distributed CG, one 104^3 z-slab per chip: the DIA
    kernel runs inside the shard body and the one-plane halo moves by
    collective-permute. The interior and boundary tables go into the
    kernel as stored, and the remote COO arrays need no squeeze."""
    nd, hw = 4, G * G
    mesh = make_mesh((nd,), ("rows",), devices=topo.devices[:nd])
    rows = NamedSharding(mesh, P("rows"))

    def sds(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=rows)

    cap = 2 * hw * 9  # each halo row couples to 9 rows of its plane
    remote = COO(sds(nd * cap, dtype=jnp.int32), sds(nd * cap, dtype=jnp.int32),
                 sds(nd * cap), (M, 2 * hw), cap)
    A = DistSparseMatrix(_placed(_table_dia(nd), mesh), remote,
                         boundary=_placed(_table_dia(nd), mesh),
                         nshards=nd, mp=M, shape=(nd * M, nd * M),
                         axis="rows", halo_mode="neighbor", hw=hw)
    solve = jax.jit(lambda a, b: cg(operator(a, mesh, backend="pallas"), b,
                                    tol=1e-7, maxiter=500))
    with metrics.scope() as s:
        compiled = solve.lower(A, sds(nd * M)).compile()
    # interior and boundary parts: x_blk of one chip fits VMEM
    assert _dia_paths(s)["streamed"] == 0 < _dia_paths(s)["resident"]
    assert _dia_tables(s)["relaid"] == 0 < _dia_tables(s)["tiled"]
    text = compiled.as_text()
    assert _custom_calls(compiled) >= 1
    assert "collective-permute" in text
    assert _table_passes(text) == []
    assert _loop_reduces(text, cap) == []
    assert _device_bytes(compiled) < HBM_BYTES
    assert _unscoped_loop_ops(compiled) == []
