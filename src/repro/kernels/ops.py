"""jit'd wrappers binding the Pallas kernels to the core containers.

Kernels run in the Pallas interpreter on the CPU backend only (correctness
checks: :func:`interpret_mode`); on a TPU they always compile through
Mosaic, and a kernel that Mosaic refuses is an error, never a quiet
interpreter run.

Every SpMV/SpMM entry point takes ``cfg=`` — a kernel tile-config dict
(e.g. ``{"tm": 256, "tk": 2048}`` for CSR, ``{"tm": 1024, "layout":
"col"}`` for ELL). Explicit keyword arguments win over ``cfg`` entries,
which win over :func:`default_config`'s density heuristic (tile sizes
derived from the matrix's shape and average row nnz). Measured winning
configs come from ``repro.tuning.kernel_tune`` and are threaded here by
``repro.core.ops.spmv(backend="auto")``.

The DIA wrapper (the stencil main path) keeps ``x`` resident in VMEM when
its padded copy fits :data:`X_VMEM_BUDGET` and streams it from HBM in
per-tile windows otherwise, so it has no reference fallback: a shape its
kernel cannot take raises a ``ValueError`` that names it. The other
wrappers still fall back to the pure-jnp reference path when their
VMEM-resident operands would not fit (e.g. x too large for VMEM
residency, empty BSR block rows).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.pallas import tpu as pltpu

from repro.core.formats import BSR, CSR, DIA, ELL, HYB, SELL
from repro.core.ops import vma
from repro.kernels import bsr_spmm as _bsr
from repro.kernels import csr_spmm as _csr_mm
from repro.kernels import csr_spmv as _csr
from repro.kernels import dia_spmv as _dia
from repro.kernels import ell_spmv as _ell
from repro.kernels import sell_spmv as _sell
from repro.obs import metrics as _metrics


def interpret_mode() -> bool:
    """True exactly on the CPU backend, where kernel bodies run in the
    Pallas interpreter; every other backend compiles them natively."""
    return jax.default_backend() == "cpu"


def _interpret(*operands):
    """The ``interpret=`` argument for a kernel call on ``operands``:
    ``False`` (compile) off the CPU backend. On CPU the HLO interpreter
    runs the kernel, except inside a ``shard_map`` body, where its grid
    loop trips the varying-axes check; there the TPU interpreter
    (``pltpu.InterpretParams``) runs it."""
    if not interpret_mode():
        return False
    return pltpu.InterpretParams() if vma(*jax.tree.leaves(operands)) else True


# VMEM residency budget for the x vector (bytes); beyond this the wrappers
# fall back to the reference path (v5e has ~16 MiB VMEM per core).
X_VMEM_BUDGET = 6 * 1024 * 1024

# VMEM for the DIA kernel's double-buffered (ndiag, tm) diagonal tiles.
DIA_VMEM_BUDGET = 8 * 1024 * 1024


# ---------------------------------------------------------------------------
# Default tile configs: the per-matrix density heuristic
# ---------------------------------------------------------------------------


def _pow2_clamp(v: float, lo: int, hi: int) -> int:
    """Smallest power of two >= v, clamped into [lo, hi]."""
    p = 1 << max(0, int(np.ceil(np.log2(max(1.0, float(v))))))
    return int(min(max(p, lo), hi))


def _csr_tiles(m: int, nnz: int, cfg: Optional[dict],
               tm: Optional[int] = None, tk: Optional[int] = None):
    """(tm, tk) for the CSR kernel: explicit args > cfg > density heuristic.

    Heuristic: tm rides the VPU sweet spot (256 rows, or the whole matrix
    when smaller); tk sizes each nnz chunk to roughly a quarter of the
    average tile's window (avg row nnz x tm / 4) so sparse tiles take one
    cheap chunk while dense tiles stream several full ones.
    """
    cfg = cfg or {}
    tm = int(tm if tm is not None else cfg.get("tm") or _pow2_clamp(min(m, 256), 8, 8192))
    avg = max(1.0, nnz / max(1, m))
    tk = int(tk if tk is not None else cfg.get("tk") or _pow2_clamp(avg * tm / 4, 256, 4096))
    return tm, tk


def resolve_config(A, cfg: Optional[dict], op: str = "spmv",
                   ncols: Optional[int] = None) -> dict:
    """The tile config a wrapper should run with: an explicit ``cfg``
    wins; otherwise the *tuned* winner cached for ``A``'s shape bucket
    (host dict lookup, trace-time only); otherwise the density heuristic.

    Consulting the tuned cache here — not just on the ``"auto"`` route —
    means resolve-then-dispatch callers (``resolve_backend("auto", A)``
    followed by ``spmv(backend="pallas")``) also run the measured winner
    rather than silently falling back to an untuned default. ``ncols``
    is the rhs width for the spmm ops — part of the tuned-record key (a
    winner measured at one batch width is never replayed at another).
    """
    if cfg is not None:
        return cfg
    try:
        from repro.tuning import kernel_tune  # lazy: tuning imports kernels
        rec = kernel_tune.best_config(A, op=op, ncols=ncols)
        if rec is not None:
            return dict(rec.cfg)
    except ImportError:  # pragma: no cover - partial installs
        pass
    return default_config(A, op=op, ncols=ncols)


def _pick(explicit, cfg: dict, key: str, A, op: str = "spmv",
          ncols: Optional[int] = None):
    """The one precedence rule for kernel params: explicit kwarg > ``cfg``
    entry > density-heuristic default (guards tuned records that predate a
    newly added key)."""
    if explicit is not None:
        return explicit
    v = cfg.get(key)
    return v if v is not None else default_config(A, op=op, ncols=ncols)[key]


def _rhs_tile(ncols: Optional[int]) -> int:
    """Default rhs tile: the whole (pow2-rounded) batch width up to 256 —
    b=1 decode runs a 1-lane tile instead of padding to a full slab."""
    return _pow2_clamp(ncols or 128, 1, 256)


def default_config(A, op: str = "spmv", ncols: Optional[int] = None) -> dict:
    """Density-heuristic tile config for ``A`` (the no-tuning default).

    ``repro.tuning.kernel_tune.best_config`` supersedes this with a
    measured winner when one is cached for the matrix's (shape bucket,
    rhs-width bucket) (see :func:`resolve_config`). ``op`` selects the
    kernel family: the spmm ops add the ``tn`` rhs tile, and ELL's layout
    default flips to the plane-streaming ``"col"`` once rows are long
    enough that a (tm, K, tn) row-layout gather would blow the transient
    footprint.
    """
    m = A.shape[0]
    nnz = max(1, int(getattr(A, "nnz", 1)))
    spmm = op in ("spmm", "spmm_t")
    if isinstance(A, CSR):
        tm, tk = _csr_tiles(m, nnz, None)
        if spmm:
            # wide rhs: each nnz chunk costs tk*tn work — shrink the chunk
            tk = _pow2_clamp(tk / max(1, _rhs_tile(ncols) // 8), 256, 4096)
            return {"tm": tm, "tk": tk, "tn": _rhs_tile(ncols)}
        return {"tm": tm, "tk": tk}
    if isinstance(A, ELL):
        k = A.data.shape[1]
        if spmm:
            layout = "row" if k <= 32 else "col"
            return {"tm": _pow2_clamp(min(m, 1024), 8, 8192),
                    "layout": layout, "tn": _rhs_tile(ncols)}
        # interpret mode pays per grid step: prefer one big tile; native
        # Mosaic wants lane-aligned (K, tm) tiles in VMEM.
        if interpret_mode():
            return {"tm": _pow2_clamp(m, 8, 8192), "layout": "row"}
        return {"tm": 256, "layout": "col"}
    if isinstance(A, SELL):
        # ts slices per program; aim for ~512 sorted rows per grid step
        # (interpret mode pays per step; each unrolled slice adds trace
        # size, so ts stays bounded). c/sigma are *container* parameters —
        # kernel_tune rebuilds the matrix to explore them; the wrapper
        # only picks the launch geometry.
        ts = _pow2_clamp(512 // max(1, A.c), 1, 64)
        if spmm:
            return {"ts": ts, "tn": _rhs_tile(ncols)}
        return {"ts": ts}
    if isinstance(A, DIA):
        return {"tm": _dia_tm(A)}
    if isinstance(A, BSR):
        return {"tn": 128}
    if isinstance(A, HYB):
        sub = {"ell": default_config(A.ell, op=op, ncols=ncols)}
        if spmm:
            tm, tk = _csr_tiles(m, max(1, int(A.coo.nnz)), None)
            sub["csr"] = {"tm": tm, "tk": tk, "tn": _rhs_tile(ncols)}
        return sub
    return {}


# ---------------------------------------------------------------------------
# SpMV / SpMM entry points (all take cfg=)
# ---------------------------------------------------------------------------


def _dia_tm(A: DIA) -> int:
    """Default DIA row tile: up to 32768 rows, no more than the matrix
    needs, shrunk until the double-buffered diagonal tiles fit
    :data:`DIA_VMEM_BUDGET`. The kernel pays a fixed cost per (tile,
    diagonal), so fewer, taller tiles are faster: with ``x`` resident,
    0.528 ms a call at 8192 rows against 0.202 ms at 32768 for HPCG's
    104^3 matrix on a TPU v5e."""
    unit = _dia.row_unit(A.dtype)
    per_row = 2 * max(1, A.ndiag) * A.dtype.itemsize
    fit = DIA_VMEM_BUDGET // per_row // unit * unit
    need = -(-A.shape[0] // unit) * unit
    return max(unit, min(32768, need, fit))


def dia_spmv(A: DIA, x: jax.Array, tm: Optional[int] = None,
             cfg: Optional[dict] = None) -> jax.Array:
    """DIA SpMV via the Pallas kernel, ``x`` resident in VMEM when its
    padded copy fits :data:`X_VMEM_BUDGET`, else streamed from HBM in
    per-tile windows; counts each traced call's path
    (``kernel.dia.x_resident`` / ``kernel.dia.x_streamed``). Raises
    ``ValueError`` when the (ndiag, tm) diagonal tiles exceed
    :data:`DIA_VMEM_BUDGET`."""
    cfg = resolve_config(A, cfg)
    tm = int(_pick(tm, cfg, "tm", A))
    need = 2 * A.ndiag * tm * A.dtype.itemsize
    if need > DIA_VMEM_BUDGET:
        raise ValueError(
            f"DIA kernel: {A.ndiag} diagonals x tm={tm} rows need {need} "
            f"bytes of VMEM for the diagonal tiles, over the "
            f"{DIA_VMEM_BUDGET}-byte budget; use a smaller tm or another "
            f"format")
    n = A.shape[1]
    resident = 4 * _dia.x_pad_len(n, tm) <= X_VMEM_BUDGET
    _metrics.inc("kernel.dia.x_resident" if resident
                 else "kernel.dia.x_streamed")
    return _dia.dia_spmv(A.offsets, A.data, x, n, tm=tm, x_resident=resident,
                         interpret=_interpret(A, x))


def ell_spmv(A: ELL, x: jax.Array, tm: Optional[int] = None,
             layout: Optional[str] = None,
             cfg: Optional[dict] = None) -> jax.Array:
    cfg = resolve_config(A, cfg)
    tm = int(_pick(tm, cfg, "tm", A))
    layout = _pick(layout, cfg, "layout", A)
    if x.size * x.dtype.itemsize > X_VMEM_BUDGET:
        from repro.core import ops as core_ops
        return core_ops._spmv_ell(A, x)
    return _ell.ell_spmv(A.cols, A.data, x, tm=tm, layout=layout,
                         interpret=_interpret(A, x))


def sell_spmv(A: SELL, x: jax.Array, ts: Optional[int] = None,
              cfg: Optional[dict] = None) -> jax.Array:
    """SELL-C-sigma SpMV via the slice-tiled Pallas kernel. ``cfg`` may
    carry ``c``/``sigma`` from a tuned record — those describe the
    container the tuner rebuilt, not a launch knob, and are ignored
    here; only ``ts`` (slices per program) shapes the launch."""
    cfg = resolve_config(A, cfg)
    ts = int(_pick(ts, cfg, "ts", A))
    if (2 * A.capacity + x.size) * 4 > X_VMEM_BUDGET:
        from repro.core import ops as core_ops
        return core_ops._spmv_sell(A, x)
    return _sell.sell_spmv(A.slice_ptrs, A.cols, A.data, A.perm, x,
                           m=A.shape[0], c=A.c, ts=ts,
                           interpret=_interpret(A, x))


def csr_spmv(A: CSR, x: jax.Array, tm: Optional[int] = None,
             tk: Optional[int] = None,
             cfg: Optional[dict] = None) -> jax.Array:
    """CSR SpMV via the 2-D row x nnz tiled Pallas kernel; the
    (rows, indices, data) arrays plus x must fit the VMEM residency
    budget, else ref fallback."""
    from repro.core import ops as core_ops
    resident = (3 * A.capacity + x.size) * 4
    if resident > X_VMEM_BUDGET:
        return core_ops._spmv_csr(A, x)
    tm, tk = _csr_tiles(A.shape[0], A.nnz, resolve_config(A, cfg), tm=tm, tk=tk)
    rows = core_ops.csr_row_ids(A.indptr, A.capacity, A.shape[0])
    return _csr.csr_spmv(A.indptr, rows, A.indices, A.data, x, tm=tm, tk=tk,
                         interpret=_interpret(A, x))


def hyb_spmv(A: HYB, x: jax.Array, cfg: Optional[dict] = None) -> jax.Array:
    """HYB SpMV: ELL kernel for the regular planes + the CSR kernel for the
    COO overflow tail. The tail's row ids are already in hand, so the CSR
    layout is assembled directly (stable sort + bincount row pointers);
    everything fuses with the caller under jit, and plan-built tails are
    already row-sorted so the sort is cheap. ``cfg`` nests per-part
    configs: ``{"ell": {...}, "csr": {...}}``."""
    from repro.core import ops as core_ops
    cfg = resolve_config(A, cfg)
    y = ell_spmv(A.ell, x, cfg=cfg.get("ell"))
    c = A.coo
    if (3 * c.capacity + x.size) * 4 > X_VMEM_BUDGET:
        return y + core_ops._spmv_coo(c, x)
    order = jnp.argsort(c.row, stable=True)
    rows = c.row[order]
    indptr = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32),
         jnp.cumsum(jnp.bincount(rows, length=A.shape[0])).astype(jnp.int32)])
    tm, tk = _csr_tiles(A.shape[0], c.nnz, cfg.get("csr"))
    tail = _csr.csr_spmv(indptr, rows, c.col[order], c.data[order], x,
                         tm=tm, tk=tk, interpret=_interpret(A, x))
    return y + tail


def _bsr_brow(A: BSR):
    """Precompute (host) the non-decreasing block-row id of every block."""
    indptr = np.asarray(A.indptr)
    nblk = A.nblocks
    brow = np.searchsorted(indptr, np.arange(nblk), side="right").astype(np.int32) - 1
    return jnp.asarray(np.clip(brow, 0, max(0, len(indptr) - 2)))


def _bsr_rows_nonempty(A: BSR) -> bool:
    indptr = np.asarray(A.indptr)
    return bool(np.all(np.diff(indptr) >= 1)) and int(indptr[-1]) == A.nblocks


def bsr_spmm(A: BSR, B: jax.Array, tn: Optional[int] = None,
             cfg: Optional[dict] = None, _op: str = "spmm") -> jax.Array:
    ncols = B.shape[1] if _op in ("spmm", "spmm_t") else None
    cfg = resolve_config(A, cfg, op=_op, ncols=ncols)
    tn = int(_pick(tn, cfg, "tn", A))
    if not _bsr_rows_nonempty(A):
        from repro.core import ops as core_ops
        return core_ops._spmm_bsr(A, B)
    brow = _bsr_brow(A)
    return _bsr.bsr_spmm(A.indptr, brow, A.indices, A.data, B, A.shape[0],
                         tn=tn, interpret=_interpret(A, B))


def bsr_spmv(A: BSR, x: jax.Array, tn: Optional[int] = None,
             cfg: Optional[dict] = None) -> jax.Array:
    # tuned as op="spmv": a BSR spmv record must not be read as spmm's
    return bsr_spmm(A, x[:, None], tn=tn, cfg=cfg, _op="spmv")[:, 0]


# ---------------------------------------------------------------------------
# SpMM wrappers: Y = A @ B (B (N, K)) and the transposed-rhs serving
# orientation Y = X @ A^T (X (T, N)). ``tn`` tiles the rhs/batch axis;
# defaults and tuned records are keyed by the rhs-width bucket.
# ---------------------------------------------------------------------------


def _spmm_cfg(A, cfg, op, ncols, tm=None, tk=None, tn=None):
    cfg = resolve_config(A, cfg, op=op, ncols=ncols)
    tm = int(_pick(tm, cfg, "tm", A, op=op, ncols=ncols))
    tk = int(_pick(tk, cfg, "tk", A, op=op, ncols=ncols))
    tn = int(_pick(tn, cfg, "tn", A, op=op, ncols=ncols))
    return tm, tk, tn


def csr_spmm(A: CSR, B: jax.Array, tm: Optional[int] = None,
             tk: Optional[int] = None, tn: Optional[int] = None,
             cfg: Optional[dict] = None) -> jax.Array:
    """Y = A @ B via the row x rhs tiled Pallas kernel. The VMEM check
    counts the per-tile B slab (N x tn), not all of B."""
    from repro.core import ops as core_ops
    tm, tk, tn = _spmm_cfg(A, cfg, "spmm", B.shape[1], tm=tm, tk=tk, tn=tn)
    resident = (3 * A.capacity + (A.shape[1] + tm) * tn) * 4
    if resident > X_VMEM_BUDGET:
        return core_ops._spmm_csr(A, B)
    rows = core_ops.csr_row_ids(A.indptr, A.capacity, A.shape[0])
    return _csr_mm.csr_spmm(A.indptr, rows, A.indices, A.data, B,
                            tm=tm, tk=tk, tn=tn, interpret=_interpret(A, B))


def csr_spmm_t(A: CSR, X: jax.Array, tm: Optional[int] = None,
               tk: Optional[int] = None, tn: Optional[int] = None,
               cfg: Optional[dict] = None) -> jax.Array:
    """Y = X @ A^T for activations X (T, N) — no activation transposes."""
    from repro.core import ops as core_ops
    tm, tk, tn = _spmm_cfg(A, cfg, "spmm_t", X.shape[0], tm=tm, tk=tk, tn=tn)
    resident = (3 * A.capacity + (A.shape[1] + tm) * tn) * 4
    if resident > X_VMEM_BUDGET:
        return core_ops._spmm_csr(A, X.T).T
    rows = core_ops.csr_row_ids(A.indptr, A.capacity, A.shape[0])
    return _csr_mm.csr_spmm_t(A.indptr, rows, A.indices, A.data, X,
                              tm=tm, tk=tk, tn=tn, interpret=_interpret(A, X))


def _ell_spmm_cfg(A, cfg, op, ncols, tm=None, layout=None, tn=None):
    cfg = resolve_config(A, cfg, op=op, ncols=ncols)
    tm = int(_pick(tm, cfg, "tm", A, op=op, ncols=ncols))
    layout = _pick(layout, cfg, "layout", A, op=op, ncols=ncols)
    tn = int(_pick(tn, cfg, "tn", A, op=op, ncols=ncols))
    return tm, layout, tn


def _ell_spmm_fits(A: ELL, tm: int, layout: str, tn: int, n: int) -> bool:
    k = A.data.shape[1]
    transient = tm * k * tn if layout == "row" else tm * tn
    resident = 2 * tm * k + n * tn + tm * tn + transient
    return resident * 4 <= X_VMEM_BUDGET


def ell_spmm(A: ELL, B: jax.Array, tm: Optional[int] = None,
             layout: Optional[str] = None, tn: Optional[int] = None,
             cfg: Optional[dict] = None) -> jax.Array:
    from repro.core import ops as core_ops
    tm, layout, tn = _ell_spmm_cfg(A, cfg, "spmm", B.shape[1],
                                   tm=tm, layout=layout, tn=tn)
    if not _ell_spmm_fits(A, tm, layout, tn, A.shape[1]):
        return core_ops._spmm_ell(A, B)
    return _ell.ell_spmm(A.cols, A.data, B, tm=tm, tn=tn, layout=layout,
                         interpret=_interpret(A, B))


def ell_spmm_t(A: ELL, X: jax.Array, tm: Optional[int] = None,
               layout: Optional[str] = None, tn: Optional[int] = None,
               cfg: Optional[dict] = None) -> jax.Array:
    from repro.core import ops as core_ops
    tm, layout, tn = _ell_spmm_cfg(A, cfg, "spmm_t", X.shape[0],
                                   tm=tm, layout=layout, tn=tn)
    if not _ell_spmm_fits(A, tm, layout, tn, A.shape[1]):
        return core_ops._spmm_ell(A, X.T).T
    return _ell.ell_spmm_t(A.cols, A.data, X, tm=tm, tn=tn, layout=layout,
                           interpret=_interpret(A, X))


def _hyb_tail_csr(A: HYB):
    """The COO overflow tail in CSR layout (stable sort + bincount row
    pointers), same assembly as :func:`hyb_spmv` — plan-built tails are
    already row-sorted so the sort is cheap under jit."""
    c = A.coo
    order = jnp.argsort(c.row, stable=True)
    rows = c.row[order]
    indptr = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32),
         jnp.cumsum(jnp.bincount(rows, length=A.shape[0])).astype(jnp.int32)])
    return indptr, rows, c.col[order], c.data[order]


def hyb_spmm(A: HYB, B: jax.Array, cfg: Optional[dict] = None) -> jax.Array:
    from repro.core import ops as core_ops
    cfg = resolve_config(A, cfg, op="spmm", ncols=B.shape[1])
    y = ell_spmm(A.ell, B, cfg=cfg.get("ell"))
    sub = cfg.get("csr") or {}
    tm, tk = _csr_tiles(A.shape[0], max(1, int(A.coo.nnz)), sub)
    tn = int(sub.get("tn") or _rhs_tile(B.shape[1]))
    if (3 * A.coo.capacity + (A.shape[1] + tm) * tn) * 4 > X_VMEM_BUDGET:
        return y + core_ops._spmm_coo(A.coo, B)
    indptr, rows, col, data = _hyb_tail_csr(A)
    tail = _csr_mm.csr_spmm(indptr, rows, col, data, B, tm=tm, tk=tk, tn=tn,
                            interpret=_interpret(A, B))
    return y + tail


def hyb_spmm_t(A: HYB, X: jax.Array, cfg: Optional[dict] = None) -> jax.Array:
    from repro.core import ops as core_ops
    cfg = resolve_config(A, cfg, op="spmm_t", ncols=X.shape[0])
    y = ell_spmm_t(A.ell, X, cfg=cfg.get("ell"))
    sub = cfg.get("csr") or {}
    tm, tk = _csr_tiles(A.shape[0], max(1, int(A.coo.nnz)), sub)
    tn = int(sub.get("tn") or _rhs_tile(X.shape[0]))
    if (3 * A.coo.capacity + (A.shape[1] + tm) * tn) * 4 > X_VMEM_BUDGET:
        return y + core_ops._spmm_coo(A.coo, X.T).T
    indptr, rows, col, data = _hyb_tail_csr(A)
    tail = _csr_mm.csr_spmm_t(indptr, rows, col, data, X, tm=tm, tk=tk,
                              tn=tn, interpret=_interpret(A, X))
    return y + tail


def _sell_spmm_cfg(A, cfg, op, ncols, ts=None, tn=None):
    cfg = resolve_config(A, cfg, op=op, ncols=ncols)
    ts = int(_pick(ts, cfg, "ts", A, op=op, ncols=ncols))
    tn = int(_pick(tn, cfg, "tn", A, op=op, ncols=ncols))
    return ts, tn


def sell_spmm(A: SELL, B: jax.Array, ts: Optional[int] = None,
              tn: Optional[int] = None,
              cfg: Optional[dict] = None) -> jax.Array:
    from repro.core import ops as core_ops
    ts, tn = _sell_spmm_cfg(A, cfg, "spmm", B.shape[1], ts=ts, tn=tn)
    if (2 * A.capacity + (A.shape[1] + ts * A.c) * tn) * 4 > X_VMEM_BUDGET:
        return core_ops._spmm_sell(A, B)
    return _sell.sell_spmm(A.slice_ptrs, A.cols, A.data, A.perm, B,
                           m=A.shape[0], c=A.c, ts=ts, tn=tn,
                           interpret=_interpret(A, B))


def sell_spmm_t(A: SELL, X: jax.Array, ts: Optional[int] = None,
                tn: Optional[int] = None,
                cfg: Optional[dict] = None) -> jax.Array:
    from repro.core import ops as core_ops
    ts, tn = _sell_spmm_cfg(A, cfg, "spmm_t", X.shape[0], ts=ts, tn=tn)
    if (2 * A.capacity + (A.shape[1] + ts * A.c) * tn) * 4 > X_VMEM_BUDGET:
        return core_ops._spmm_sell(A, X.T).T
    return _sell.sell_spmm_t(A.slice_ptrs, A.cols, A.data, A.perm, X,
                             m=A.shape[0], c=A.c, ts=ts, tn=tn,
                             interpret=_interpret(A, X))


def bsr_spmm_t(A: BSR, X: jax.Array, tn: Optional[int] = None,
               cfg: Optional[dict] = None) -> jax.Array:
    """BSR has no native transposed-rhs kernel yet: run the (N, K) kernel
    on X^T. Still one fused jit region, but pays the two transposes —
    tuned separately (op="spmm_t") so the veto is honest about that cost."""
    return bsr_spmm(A, X.T, tn=tn, cfg=cfg, _op="spmm_t").T


# Registries consumed by repro.core.ops.spmv/spmm(backend="pallas").
SPMV_PALLAS = {DIA: dia_spmv, ELL: ell_spmv, BSR: bsr_spmv, CSR: csr_spmv,
               HYB: hyb_spmv, SELL: sell_spmv}
SPMM_PALLAS = {BSR: bsr_spmm, CSR: csr_spmm, ELL: ell_spmm, HYB: hyb_spmm,
               SELL: sell_spmm}
SPMM_T_PALLAS = {CSR: csr_spmm_t, ELL: ell_spmm_t, HYB: hyb_spmm_t,
                 BSR: bsr_spmm_t, SELL: sell_spmm_t}
