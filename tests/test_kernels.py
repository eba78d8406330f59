"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs pure-jnp oracle."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import (Format, banded_coo, coo_from_dense_np, convert,
                        random_coo, to_dense_np)
from repro.kernels import ops as kops
from repro.kernels.ref import (bsr_spmm_ref, csr_spmv_ref, dia_spmv_ref,
                               ell_spmv_ref)
from repro.obs import metrics

RNG = np.random.default_rng(0)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# DIA SpMV
# ---------------------------------------------------------------------------

# x's home in the DIA kernel -> an X_VMEM_BUDGET that forces it
DIA_X_PATHS = {"resident": 1 << 40, "streamed": 0}


@pytest.mark.parametrize("shape,offsets", [
    ((64, 64), [0]),
    ((128, 128), [-1, 0, 1]),
    ((300, 300), [-17, -3, 0, 3, 17]),
    ((1000, 1000), [-96, -32, -1, 0, 1, 32, 96]),
    ((128, 200), [0, 64, 150]),          # rectangular, remote-part shape
    ((200, 128), [-150, -10, 0]),        # tall rectangular
    ((513, 513), [-5, 0, 5]),            # non-tile-aligned rows
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("path", DIA_X_PATHS)
def test_dia_kernel_sweep(shape, offsets, dtype, path, monkeypatch):
    """Both homes of x, forced by the VMEM budget: the whole padded x in
    VMEM, or per-tile windows streamed from HBM."""
    monkeypatch.setattr(kops, "X_VMEM_BUDGET", DIA_X_PATHS[path])
    A = convert(banded_coo(shape, offsets, dtype=dtype), Format.DIA)
    x = jnp.asarray(RNG.standard_normal(shape[1]), dtype=dtype)
    with metrics.scope() as s:
        y_k = kops.dia_spmv(A, x)
    assert s.delta(f"kernel.dia.x_{path}") == 1
    assert sum(s.delta(f"kernel.dia.x_{p}") for p in DIA_X_PATHS) == 1
    y_r = dia_spmv_ref(A.offsets, A.data, x, shape[1])
    np.testing.assert_allclose(np.asarray(y_k, np.float32),
                               np.asarray(y_r, np.float32), **_tol(dtype))


@pytest.mark.parametrize("shape,offsets,tm", [
    ((1000, 1000), [-96, -32, -1, 0, 1, 32, 96], 1024),
    ((3000, 2500), [-2100, -700, 0, 5, 1300], 1024),
    ((517, 517), [-19, -3, 0, 3, 19], 2048),
])
def test_dia_kernel_x_paths_agree_bitwise(shape, offsets, tm, monkeypatch):
    """The resident and streamed kernels differ only in where each window
    comes from: the same shifts and order of accumulation give the same
    bits."""
    A = convert(banded_coo(shape, offsets), Format.DIA)
    x = jnp.asarray(RNG.standard_normal(shape[1]).astype(np.float32))
    ys = {}
    for path, budget in DIA_X_PATHS.items():
        monkeypatch.setattr(kops, "X_VMEM_BUDGET", budget)
        ys[path] = np.asarray(kops.dia_spmv(A, x, tm=tm))
    np.testing.assert_array_equal(ys["resident"], ys["streamed"])


@pytest.mark.parametrize("tm", [1024, 2048, 4096])
def test_dia_kernel_tile_sizes(tm):
    A = convert(banded_coo((700, 700), [-30, 0, 30]), Format.DIA)
    x = jnp.asarray(RNG.standard_normal(700).astype(np.float32))
    y_k = kops.dia_spmv(A, x, tm=tm)
    np.testing.assert_allclose(np.asarray(y_k), to_dense_np(A) @ np.asarray(x),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# ELL SpMV
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape,density", [
    ((64, 64), 0.1), ((200, 150), 0.08), ((513, 400), 0.05), ((1024, 1024), 0.01),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ell_kernel_sweep(shape, density, dtype):
    A = convert(random_coo(7, shape, density=density, dtype=dtype), Format.ELL)
    x = jnp.asarray(RNG.standard_normal(shape[1]), dtype=dtype)
    y_k = kops.ell_spmv(A, x)
    y_r = ell_spmv_ref(A.cols, A.data, x)
    np.testing.assert_allclose(np.asarray(y_k, np.float32),
                               np.asarray(y_r, np.float32), **_tol(dtype))


# ---------------------------------------------------------------------------
# CSR SpMV
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape,density", [
    ((64, 64), 0.1),         # single tile
    ((200, 150), 0.08),      # rectangular, non-tile-aligned rows
    ((513, 400), 0.05),      # non-multiple-of-tile rows AND cols
    ((1024, 1024), 0.01),    # multi-tile
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_csr_kernel_sweep(shape, density, dtype):
    A = convert(random_coo(13, shape, density=density, dtype=dtype), Format.CSR)
    x = jnp.asarray(RNG.standard_normal(shape[1]), dtype=dtype)
    y_k = kops.csr_spmv(A, x)
    y_r = csr_spmv_ref(A.indptr, A.indices, A.data, x, shape[0])
    np.testing.assert_allclose(np.asarray(y_k, np.float32),
                               np.asarray(y_r, np.float32), **_tol(dtype))


@pytest.mark.parametrize("tm,tk", [(128, 256), (256, 512), (512, 128)])
def test_csr_kernel_tile_sizes(tm, tk):
    A = convert(random_coo(14, (700, 700), density=0.03), Format.CSR)
    x = jnp.asarray(RNG.standard_normal(700).astype(np.float32))
    y_k = kops.csr_spmv(A, x, tm=tm, tk=tk)
    np.testing.assert_allclose(np.asarray(y_k), to_dense_np(A) @ np.asarray(x),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# Tile-config sweeps over adversarial shapes (the autotuner's search space)
# ---------------------------------------------------------------------------
# Every config the tuner may emit must agree with the reference SpMV to
# f32 machine precision (verified against a float64 dense oracle — exact
# bitwise identity with ref is not the spec: different tile boundaries
# legally reassociate the f32 accumulation) and must be bitwise
# *deterministic*: the same config always produces the same bits.

CSR_CFG_GRID = [{"tm": 32, "tk": 64}, {"tm": 128, "tk": 512},
                {"tm": 512, "tk": 128}, {"tm": 1024, "tk": 4096}]

# m (and n) chosen so m % tm != 0 for every tm in the grid: the last row
# tile is ragged and the last nnz chunk is partial.
CSR_RAGGED_SHAPES = [((97, 83), 0.08), ((513, 401), 0.03),
                     ((1021, 999), 0.01)]


@pytest.mark.parametrize("cfg", CSR_CFG_GRID)
@pytest.mark.parametrize("shape,density", CSR_RAGGED_SHAPES)
def test_csr_kernel_cfg_sweep_ragged(shape, density, cfg):
    A = convert(random_coo(21, shape, density=density), Format.CSR)
    x = jnp.asarray(RNG.standard_normal(shape[1]).astype(np.float32))
    y = kops.csr_spmv(A, x, cfg=cfg)
    oracle = to_dense_np(A).astype(np.float64) @ np.asarray(x, np.float64)
    np.testing.assert_allclose(np.asarray(y, np.float64), oracle,
                               rtol=2e-5, atol=2e-5)
    # bitwise determinism of a fixed config
    np.testing.assert_array_equal(np.asarray(y),
                                  np.asarray(kops.csr_spmv(A, x, cfg=cfg)))


def test_csr_kernel_mixed_magnitude_rows():
    """The segmented reduction must keep a tiny row's own relative accuracy
    when it shares an nnz chunk with huge rows — a plain prefix-sum
    difference loses it to catastrophic cancellation (error scales with
    the chunk's running total, not the row's magnitude)."""
    D = np.zeros((8, 8), np.float32)
    D[0:4, :4] = 1e7
    D[4, :4] = 1e-3
    A = convert(coo_from_dense_np(D), Format.CSR)
    x = jnp.ones((8,), jnp.float32)
    y = np.asarray(kops.csr_spmv(A, x, cfg={"tm": 8, "tk": 32}))
    assert y[4] == pytest.approx(4e-3, rel=1e-6), y


@pytest.mark.parametrize("cfg", CSR_CFG_GRID)
def test_csr_kernel_cfg_sweep_empty_rows(cfg):
    """Entire empty row-tiles (zero-width nnz windows) under every config."""
    D = np.zeros((300, 300), np.float32)
    mask = RNG.random((100, 300)) < 0.05
    D[200:, :] = np.where(mask, RNG.standard_normal((100, 300)), 0).astype(np.float32)
    A = convert(coo_from_dense_np(D, capacity=D.astype(bool).sum() + 333),
                Format.CSR)
    x = jnp.asarray(RNG.standard_normal(300).astype(np.float32))
    y = kops.csr_spmv(A, x, cfg=cfg)
    np.testing.assert_allclose(np.asarray(y, np.float64),
                               D.astype(np.float64) @ np.asarray(x, np.float64),
                               rtol=2e-5, atol=2e-5)


ELL_CFG_GRID = [{"tm": 32, "layout": "row"}, {"tm": 32, "layout": "col"},
                {"tm": 256, "layout": "col"}, {"tm": 1024, "layout": "row"}]


@pytest.mark.parametrize("cfg", ELL_CFG_GRID)
@pytest.mark.parametrize("shape,density", [((97, 83), 0.08), ((513, 401), 0.03)])
def test_ell_kernel_cfg_sweep_ragged(shape, density, cfg):
    A = convert(random_coo(22, shape, density=density), Format.ELL)
    x = jnp.asarray(RNG.standard_normal(shape[1]).astype(np.float32))
    y = kops.ell_spmv(A, x, cfg=cfg)
    oracle = to_dense_np(A).astype(np.float64) @ np.asarray(x, np.float64)
    np.testing.assert_allclose(np.asarray(y, np.float64), oracle,
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(np.asarray(y),
                                  np.asarray(kops.ell_spmv(A, x, cfg=cfg)))


@pytest.mark.parametrize("cfg", ELL_CFG_GRID)
def test_ell_kernel_k0(cfg):
    """k=0 ELL (all rows empty): nothing to stream, result is exactly 0."""
    from repro.core.formats import ELL
    A = ELL(jnp.zeros((70, 0), jnp.int32), jnp.zeros((70, 0), jnp.float32),
            (70, 50), 0)
    x = jnp.asarray(RNG.standard_normal(50).astype(np.float32))
    y = kops.ell_spmv(A, x, cfg=cfg)
    np.testing.assert_array_equal(np.asarray(y), np.zeros(70, np.float32))


@pytest.mark.parametrize("cfg", [{"tm": 1024}, {"tm": 2048}, {"tm": 8192}])
def test_dia_kernel_cfg_sweep_ragged(cfg):
    A = convert(banded_coo((517, 517), [-19, -3, 0, 3, 19]), Format.DIA)
    x = jnp.asarray(RNG.standard_normal(517).astype(np.float32))
    y = kops.dia_spmv(A, x, cfg=cfg)
    oracle = to_dense_np(A).astype(np.float64) @ np.asarray(x, np.float64)
    np.testing.assert_allclose(np.asarray(y, np.float64), oracle,
                               rtol=2e-5, atol=2e-5)


def test_csr_kernel_empty_rows_and_padding():
    """Empty rows cost nothing (zero-width windows); capacity padding past
    indptr[-1] is never read."""
    D = np.zeros((300, 300), np.float32)
    mask = RNG.random((150, 300)) < 0.05
    D[150:, :] = np.where(mask, RNG.standard_normal((150, 300)), 0).astype(np.float32)
    A = convert(coo_from_dense_np(D, capacity=D.astype(bool).sum() + 777),
                Format.CSR)
    x = jnp.asarray(RNG.standard_normal(300).astype(np.float32))
    np.testing.assert_allclose(np.asarray(kops.csr_spmv(A, x)),
                               D @ np.asarray(x), rtol=1e-4, atol=1e-4)


def test_csr_vmem_budget_fallback():
    """nnz arrays + x too large for VMEM residency -> ref fallback."""
    n = 2_000_000  # 8 MB f32 > budget
    A = convert(banded_coo((256, n), [0, 1000]), Format.CSR)
    x = jnp.ones((n,), jnp.float32)
    y = kops.csr_spmv(A, x)
    np.testing.assert_allclose(np.asarray(y), to_dense_np(A) @ np.ones(n),
                               rtol=1e-4, atol=1e-4)


def test_hyb_pallas_routes_tail_through_csr_kernel():
    A = random_coo(15, (200, 160), density=0.06)
    H = convert(A, Format.HYB, k=2)  # force a populated COO tail
    assert H.coo.capacity > 1
    x = jnp.asarray(RNG.standard_normal(160).astype(np.float32))
    y = kops.hyb_spmv(H, x)
    np.testing.assert_allclose(np.asarray(y), to_dense_np(A) @ np.asarray(x),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# BSR SpMM
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape,bs,kb", [
    ((256, 256), 64, 64), ((256, 384), 64, 96), ((512, 256), 128, 128),
    ((384, 384), 128, 40),   # K not a tile multiple
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_bsr_kernel_sweep(shape, bs, kb, dtype):
    A = convert(random_coo(9, shape, density=0.15, dtype=dtype), Format.BSR,
                block_size=bs)
    B = jnp.asarray(RNG.standard_normal((shape[1], kb)), dtype=dtype)
    y_k = kops.bsr_spmm(A, B)
    y_r = bsr_spmm_ref(A.indptr, A.indices, A.data, B, shape[0])
    np.testing.assert_allclose(np.asarray(y_k, np.float32),
                               np.asarray(y_r, np.float32), **_tol(dtype))


def test_bsr_empty_row_fallback():
    """Kernel precondition violated -> wrapper must fall back, stay correct."""
    # only one nonzero => most block rows empty
    A = convert(banded_coo((256, 256), [0], fill=[2.0]), Format.BSR, block_size=64)
    import dataclasses
    # carve out an empty block row by zeroing indptr ranges is fiddly; instead
    # build from a matrix with an all-zero top half
    import numpy as _np
    D = _np.zeros((256, 256), _np.float32)
    D[128:, :] = _np.asarray(to_dense_np(A))[128:, :]
    from repro.core import coo_from_dense_np
    Ab = convert(coo_from_dense_np(D), Format.BSR, block_size=64)
    B = jnp.asarray(RNG.standard_normal((256, 32)).astype(np.float32))
    y = kops.bsr_spmm(Ab, B)
    np.testing.assert_allclose(np.asarray(y), D @ np.asarray(B), rtol=1e-4, atol=1e-4)


def test_bsr_spmv_path():
    A = convert(random_coo(11, (256, 256), density=0.2), Format.BSR, block_size=64)
    x = jnp.asarray(RNG.standard_normal(256).astype(np.float32))
    np.testing.assert_allclose(np.asarray(kops.bsr_spmv(A, x)),
                               to_dense_np(A) @ np.asarray(x), rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# backend="pallas" dispatch through the core API
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fmt", [Format.CSR, Format.DIA, Format.ELL, Format.HYB])
def test_core_pallas_backend(fmt):
    from repro.core import spmv
    A = convert(banded_coo((256, 256), [-4, 0, 4]), fmt)
    x = jnp.asarray(RNG.standard_normal(256).astype(np.float32))
    y_p = spmv(A, x, backend="pallas")
    y_r = spmv(A, x, backend="ref")
    np.testing.assert_allclose(np.asarray(y_p), np.asarray(y_r), rtol=1e-4, atol=1e-4)


def test_pallas_backend_without_kernel_counts_ref():
    """backend="pallas" on a format with no kernel runs the reference and
    says so in kernel.route.ref, instead of passing it off as a kernel."""
    from repro.core import spmv
    from repro.obs import metrics
    A = convert(banded_coo((256, 256), [-4, 0, 4]), Format.COO)
    x = jnp.asarray(RNG.standard_normal(256).astype(np.float32))
    with metrics.scope() as s:
        y_p = spmv(A, x, backend="pallas")
    assert s.delta("kernel.route.ref") == 1
    np.testing.assert_allclose(np.asarray(y_p),
                               np.asarray(spmv(A, x, backend="ref")),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("fmt", [Format.DIA, Format.CSR])
def test_pallas_kernel_inside_shard_map(fmt):
    """A kernel call in a shard_map body (the distributed SpMV's local
    part) passes the varying-axes check and matches the reference."""
    from jax.sharding import PartitionSpec as P
    from repro.core import spmv
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1,), ("rows",))
    A = convert(banded_coo((1024, 1024), [-33, -1, 0, 1, 33]), fmt)
    x = jnp.asarray(RNG.standard_normal(1024).astype(np.float32))
    stacked = jax.tree.map(lambda a: a[None], A)
    f = jax.jit(jax.shard_map(
        lambda a, v: spmv(jax.tree.map(lambda l: l[0], a), v,
                          backend="pallas"),
        mesh=mesh, in_specs=(P("rows"), P("rows")), out_specs=P("rows")))
    np.testing.assert_allclose(np.asarray(f(stacked, x)),
                               to_dense_np(A) @ np.asarray(x),
                               rtol=1e-4, atol=1e-4)


def test_force_interpret_env_override(monkeypatch):
    """Interpret mode follows the backend alone: on the CPU backend the
    kernels run interpreted, and no environment variable (such as the
    removed REPRO_FORCE_INTERPRET) can switch a backend's mode."""
    assert jax.default_backend() == "cpu"
    assert kops.interpret_mode() is True
    monkeypatch.setenv("REPRO_FORCE_INTERPRET", "0")
    assert kops.interpret_mode() is True
    # the interpreted path executes end to end
    A = convert(banded_coo((128, 128), [-1, 0, 1]), Format.CSR)
    x = jnp.ones((128,), jnp.float32)
    np.testing.assert_allclose(np.asarray(kops.csr_spmv(A, x)),
                               to_dense_np(A) @ np.ones(128), rtol=1e-4, atol=1e-4)


def test_vmem_budget_fallback():
    """x far past any VMEM residency budget still runs the DIA kernel
    (x streams from HBM in per-tile windows) and matches the oracle; a
    diagonal table whose tiles cannot fit VMEM raises, naming the size."""
    n = 2_000_000  # 8 MB f32
    A = convert(banded_coo((1024, n), [0, 100]), Format.DIA)
    x = jnp.asarray(RNG.standard_normal(n).astype(np.float32))
    with metrics.scope() as s:
        y = kops.dia_spmv(A, x)
    assert s.delta("kernel.dia.x_streamed") == 1
    assert s.delta("kernel.dia.x_resident") == 0
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(dia_spmv_ref(A.offsets, A.data, x, n)),
                               rtol=1e-5, atol=1e-5)
    with pytest.raises(ValueError, match="tm=1048576"):
        kops.dia_spmv(A, x, tm=1 << 20)


# ---------------------------------------------------------------------------
# SpMM / transposed-rhs SpMM: rhs-width sweeps vs the dense oracle
# ---------------------------------------------------------------------------

SPMM_SHAPES = [((64, 64), 0.1), ((300, 257), 0.05), ((128, 512), 0.02)]


@pytest.mark.parametrize("shape,density", SPMM_SHAPES)
@pytest.mark.parametrize("b", [1, 5, 16])
def test_csr_spmm_sweep(shape, density, b):
    A = convert(random_coo(3, shape, density), Format.CSR)
    B = jnp.asarray(RNG.standard_normal((shape[1], b)).astype(np.float32))
    y = kops.csr_spmm(A, B)
    np.testing.assert_allclose(np.asarray(y), to_dense_np(A) @ np.asarray(B),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("shape,density", SPMM_SHAPES)
@pytest.mark.parametrize("b", [1, 5, 16])
def test_csr_spmm_t_sweep(shape, density, b):
    A = convert(random_coo(4, shape, density), Format.CSR)
    X = jnp.asarray(RNG.standard_normal((b, shape[1])).astype(np.float32))
    y = kops.csr_spmm_t(A, X)
    np.testing.assert_allclose(np.asarray(y), np.asarray(X) @ to_dense_np(A).T,
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("layout", ["row", "col"])
@pytest.mark.parametrize("b", [1, 7, 16])
def test_ell_spmm_sweep(layout, b):
    A = convert(random_coo(5, (200, 160), 0.05), Format.ELL)
    B = jnp.asarray(RNG.standard_normal((160, b)).astype(np.float32))
    y = kops.ell_spmm(A, B, layout=layout)
    np.testing.assert_allclose(np.asarray(y), to_dense_np(A) @ np.asarray(B),
                               rtol=1e-4, atol=1e-4)
    X = jnp.asarray(RNG.standard_normal((b, 160)).astype(np.float32))
    yt = kops.ell_spmm_t(A, X, layout=layout)
    np.testing.assert_allclose(np.asarray(yt), np.asarray(X) @ to_dense_np(A).T,
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("b", [1, 9])
def test_hyb_spmm_sweep(b):
    # skewed rows so the COO tail is non-empty
    d = np.zeros((96, 80), np.float32)
    d[:, :2] = RNG.standard_normal((96, 2))
    d[0, :] = RNG.standard_normal(80)
    A = convert(coo_from_dense_np(d), Format.HYB)
    assert int(A.coo.nnz) > 0
    B = jnp.asarray(RNG.standard_normal((80, b)).astype(np.float32))
    np.testing.assert_allclose(np.asarray(kops.hyb_spmm(A, B)),
                               d @ np.asarray(B), rtol=1e-4, atol=1e-4)
    X = jnp.asarray(RNG.standard_normal((b, 80)).astype(np.float32))
    np.testing.assert_allclose(np.asarray(kops.hyb_spmm_t(A, X)),
                               np.asarray(X) @ d.T, rtol=1e-4, atol=1e-4)


def test_core_spmm_t_backends_agree():
    from repro.core import spmm_t
    A = convert(random_coo(6, (128, 96), 0.08), Format.CSR)
    X = jnp.asarray(RNG.standard_normal((4, 96)).astype(np.float32))
    y_ref = spmm_t(A, X, backend="ref")
    y_pal = spmm_t(A, X, backend="pallas")
    np.testing.assert_allclose(np.asarray(y_pal), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-4)
    # ref path IS the double transpose it replaced at the layer level
    from repro.core import spmm
    np.testing.assert_allclose(np.asarray(y_ref),
                               np.asarray(spmm(A, X.T, backend="ref").T),
                               rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# Kernel names: each pallas_call is named after the function it serves, so
# a device profile's op paths say which kernel ran (``.../<name>/pallas_call``
# in every op's ``op_name``; on a TPU also the Mosaic ``kernel_name``).
# ---------------------------------------------------------------------------

# (kernel name, format, entry point, right-hand side: x, B (n, b) or X (b, n))
KERNEL_ENTRIES = [
    ("dia_spmv", Format.DIA, kops.dia_spmv, "x"),
    ("csr_spmv", Format.CSR, kops.csr_spmv, "x"),
    ("ell_spmv", Format.ELL, kops.ell_spmv, "x"),
    ("sell_spmv", Format.SELL, kops.sell_spmv, "x"),
    ("csr_spmm", Format.CSR, kops.csr_spmm, "B"),
    ("csr_spmm_t", Format.CSR, kops.csr_spmm_t, "X"),
    ("ell_spmm", Format.ELL, kops.ell_spmm, "B"),
    ("ell_spmm_t", Format.ELL, kops.ell_spmm_t, "X"),
    ("sell_spmm", Format.SELL, kops.sell_spmm, "B"),
    ("sell_spmm_t", Format.SELL, kops.sell_spmm_t, "X"),
    ("bsr_spmm", Format.BSR, kops.bsr_spmm, "B"),
]


@pytest.mark.parametrize("name,fmt,entry,rhs", KERNEL_ENTRIES,
                         ids=[e[0] for e in KERNEL_ENTRIES])
def test_pallas_call_is_named_after_its_function(name, fmt, entry, rhs):
    import re

    n, b = 256, 8
    kwargs = {"block_size": 64} if fmt == Format.BSR else {}
    # a band for DIA: a random pattern would hold hundreds of diagonals
    C = (banded_coo((n, n), [-1, 0, 1]) if fmt == Format.DIA
         else random_coo(12, (n, n), 0.05))
    A = convert(C, fmt, **kwargs)
    shape = {"x": (n,), "B": (n, b), "X": (b, n)}[rhs]
    v = jnp.ones(shape, jnp.float32)
    # A closed over: the wrappers read its structure on the host
    text = jax.jit(lambda v: entry(A, v)).lower(v).as_text(debug_info=True)
    assert set(re.findall(r"(?<![\w.])([\w.]+)/pallas_call", text)) == {name}
    # spmv_roofline counts the custom calls whose name holds "spmv"
    assert ("spmv" in name) == name.endswith("spmv")


def test_dia_kernel_name_reaches_mosaic(monkeypatch):
    """Lowered for a TPU from this host (nothing compiles): the Mosaic
    custom call carries the kernel's name."""
    import re

    monkeypatch.setattr(kops, "interpret_mode", lambda: False)
    A = convert(banded_coo((1024, 1024), [-1, 0, 1]), Format.DIA)
    text = jax.jit(lambda v: kops.dia_spmv(A, v)).trace(
        jnp.ones(1024, jnp.float32)).lower(
        lowering_platforms=("tpu",)).as_text()
    assert re.findall(r'kernel_name = "([^"]*)"', text) == ["dia_spmv"]
