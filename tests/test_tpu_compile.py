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
import sys

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.core.distributed import DistSparseMatrix
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


def _dia(sds, lead=()):
    return DIA(sds(*lead, NDIAG, dtype=jnp.int32), sds(*lead, NDIAG, M),
               (M, M), NDIAG * M)


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


def test_dist_cg_pallas_compiles_on_4_chips(topo, native):
    """Weak-scaled distributed CG, one 104^3 z-slab per chip: the DIA
    kernel runs inside the shard body and the one-plane halo moves by
    collective-permute."""
    nd, hw = 4, G * G
    mesh = make_mesh((nd,), ("rows",), devices=topo.devices[:nd])
    rows = NamedSharding(mesh, P("rows"))

    def sds(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=rows)

    cap = 2 * hw * 9  # each halo row couples to 9 rows of its plane
    remote = COO(sds(nd, cap, dtype=jnp.int32), sds(nd, cap, dtype=jnp.int32),
                 sds(nd, cap), (M, 2 * hw), cap)
    A = DistSparseMatrix(_dia(sds, (nd,)), remote, boundary=_dia(sds, (nd,)),
                         nshards=nd, mp=M, shape=(nd * M, nd * M),
                         axis="rows", halo_mode="neighbor", hw=hw)
    solve = jax.jit(lambda a, b: cg(operator(a, mesh, backend="pallas"), b,
                                    tol=1e-7, maxiter=500))
    with metrics.scope() as s:
        compiled = solve.lower(A, sds(nd * M)).compile()
    # interior and boundary parts: x_blk of one chip fits VMEM
    assert _dia_paths(s)["streamed"] == 0 < _dia_paths(s)["resident"]
    text = compiled.as_text()
    assert _custom_calls(compiled) >= 1
    assert "collective-permute" in text
    assert _device_bytes(compiled) < HBM_BYTES
    assert _unscoped_loop_ops(compiled) == []
