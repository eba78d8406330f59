"""Algorithms over sparse containers (the paper's §III-D algorithm layer).

Every algorithm has one generic entry point that dispatches on the container
type at *trace* time — the JAX analogue of the paper's compile-time
introspection dispatch. The implementations here are the pure-jnp "reference
backend" (the paper's Serial/OpenMP backends); `repro.kernels` provides the
Pallas TPU backend for the hot formats, selected via ``backend=``.

SpMV is the paper's evaluated hot spot; we also provide SpMM (needed by the
block-sparse / MoE integration) and the dense-vector algorithms used by CG
(dot, waxpby, axpy, norm2) plus diagonal extract/update (HPCG's TestCG).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.formats import BSR, COO, CSR, DIA, ELL, Dense, HYB, SELL
from repro.obs import ledger as _ledger
from repro.obs import metrics as _metrics
from repro.obs import trace as _trace

# ---------------------------------------------------------------------------
# SpMV: y = A @ x
# ---------------------------------------------------------------------------


def csr_row_ids(indptr, capacity: int, m: int):
    """Per-entry row ids of a capacity-padded CSR layout (jit-able).

    The TPU replacement for a warp-per-row walk: recover every stored
    entry's row from the row-pointer array in one vectorised searchsorted.
    Padding entries past ``indptr[-1]`` clip to row ``m - 1`` (their values
    are zero, so they are inert under accumulate semantics). Shared by the
    reference SpMV/SpMM, the CSR Pallas wrapper, and CSR -> COO conversion.
    """
    k = jnp.arange(capacity, dtype=jnp.int32)
    rows = jnp.searchsorted(indptr, k, side="right").astype(jnp.int32) - 1
    return jnp.clip(rows, 0, m - 1)


def resolve_backend(backend: str, A) -> str:
    """Resolve the ``"auto"`` backend name to a concrete one for ``A``.

    ``auto`` answers from *measurement*: it routes to the Pallas kernels
    iff the kernel-config cache (``repro.tuning.kernel_tune``) holds a
    winner for ``A``'s (format, shape bucket, backend, device) whose
    measured time beats the reference path — a kernel that merely
    compiles, or that was measured slower, never takes the hot path.
    Concrete names pass through unchanged.
    """
    if backend != "auto":
        return backend
    return kernel_route(A)[0]


def kernel_route(A, op: str = "spmv", cache=None, ncols=None):
    """The measured ``"auto"`` decision for a concrete container.

    Returns ``("pallas", cfg)`` when a cached kernel-tune record for
    ``A``'s shape bucket beat the reference path (``cfg`` is the winning
    tile config), else ``("ref", None)`` — including when no record
    exists: an unmeasured kernel is never presumed faster. Host dict
    lookups only; safe at trace time (the decision is baked into the
    jitted program, so retune-then-retrace to pick up new winners).
    ``ncols`` is the rhs batch width for the spmm ops — lookups hit the
    matching rhs-width bucket only.
    """
    if isinstance(A, _DYN_TYPES):
        A = getattr(A, "concrete", A)
    if not hasattr(A, "format"):
        _metrics.inc("kernel.route.ref")
        if _ledger.enabled():
            _ledger.record("kernel.route", op=op, fmt=type(A).__name__,
                           route="ref", reason="not a sparse container — "
                           "no kernel exists for it")
        return "ref", None
    from repro.tuning import kernel_tune  # lazy: tuning imports core
    fmt_name = getattr(A.format, "name", str(A.format))
    rec = kernel_tune.best_config(A, op=op, ncols=ncols, cache=cache)
    if rec is not None and rec.speedup >= 1.0:
        _metrics.inc("kernel.route.pallas")
        if _trace.mode() != "off":
            _trace.event("kernel.route", op=op, route="pallas",
                         fmt=fmt_name, cfg=str(dict(rec.cfg)))
        if _ledger.enabled():
            _ledger.record("kernel.route", op=op, fmt=fmt_name,
                           route="pallas", kernel=_route_kernel_dict(rec),
                           bucket=_route_bucket(A, op, ncols))
        return "pallas", dict(rec.cfg)
    # distinguish "no record" from "a record exists but measured slower"
    _metrics.inc("kernel.route.veto" if rec is not None else "kernel.route.ref")
    if _trace.mode() != "off":
        _trace.event("kernel.route", op=op,
                     route="veto" if rec is not None else "ref",
                     fmt=fmt_name)
    if _ledger.enabled():
        if rec is not None:
            _ledger.record("kernel.route", op=op, fmt=fmt_name, route="veto",
                           kernel=_route_kernel_dict(rec),
                           bucket=_route_bucket(A, op, ncols),
                           reason=f"cached kernel measured {rec.speedup:.2f}x "
                                  "vs ref (< 1.0) — reference path kept")
        else:
            _ledger.record("kernel.route", op=op, fmt=fmt_name, route="ref",
                           bucket=_route_bucket(A, op, ncols),
                           reason="no tuned record for this bucket — an "
                                  "unmeasured kernel is never presumed faster")
    return "ref", None


def _count_no_kernel(A, op: str) -> None:
    """``backend="pallas"`` asked for a format with no kernel: the call
    runs the reference path, and says so in ``kernel.route.ref``."""
    _metrics.inc("kernel.route.ref")
    if _trace.mode() != "off":
        _trace.event("kernel.route", op=op, route="ref",
                     fmt=type(A).__name__)
    if _ledger.enabled():
        _ledger.record("kernel.route", op=op, fmt=type(A).__name__,
                       route="ref", reason="backend='pallas' but no kernel "
                       "exists for this format")


def _route_kernel_dict(rec) -> dict:
    return {"fmt": rec.fmt, "op": rec.op, "cfg": dict(rec.cfg),
            "kernel_us": float(rec.kernel_us), "ref_us": float(rec.ref_us),
            "speedup": float(rec.speedup)}


def _route_bucket(A, op: str, ncols) -> str:
    """The cache key ``kernel_route`` consulted (ledger context only)."""
    from repro.tuning import kernel_tune
    try:
        return kernel_tune.kernel_key(
            A.format, A.shape[0], A.shape[1],
            max(1, int(getattr(A, "nnz", 1))), op=op, ncols=ncols)
    except Exception:
        return "?"


def vma(*xs) -> frozenset:
    """The manual mesh axes any of ``xs`` varies over (empty outside
    ``shard_map``)."""
    return frozenset().union(*(jax.typeof(x).vma for x in xs))


def varying_like(z, *xs):
    """``z`` declared varying over every manual axis ``xs`` vary over.

    A value created inside a shard body (a zero loop carry) is invariant
    across the mesh; ``shard_map``'s type check rejects combining it with
    per-shard operands in a carry until it is cast to match them."""
    return jax.lax.pcast(z, tuple(vma(*xs)), to="varying")


def _spmv_coo(A: COO, x):
    contrib = A.data * jnp.take(x, A.col, mode="clip")
    return jax.ops.segment_sum(contrib, A.row, num_segments=A.shape[0])


def _spmv_csr(A: CSR, x):
    # TPU adaptation: no warp-per-row — recover row ids from indptr and use a
    # vectorised gather + segment reduction (see DESIGN.md §2).
    rows = csr_row_ids(A.indptr, A.capacity, A.shape[0])
    contrib = A.data * jnp.take(x, A.indices, mode="clip")
    return jax.ops.segment_sum(contrib, rows, num_segments=A.shape[0])


# Beyond this many diagonals the per-diagonal code duplication of a fully
# unrolled scan stops paying for itself (and DIA is the wrong format anyway).
_DIA_UNROLL_MAX = 64


def _spmv_dia(A: DIA, x):
    # The format's whole point: one *contiguous* shifted multiply-add per
    # diagonal, zero gathers. x is zero-padded by M on both sides so the
    # shifted window x[i + off] is a plain dynamic_slice for any offset in
    # [-(M-1), N-1], with out-of-matrix reads landing on the zero padding
    # (container invariant: data is zero wherever the diagonal leaves the
    # matrix, so no validity masking is needed).
    m, n = A.shape
    xp = jnp.pad(x, (m, m))

    def one_diag(acc, od):
        off, drow = od
        w = jax.lax.dynamic_slice(xp, (off + m,), (m,))
        return acc + drow * w, None

    acc0 = varying_like(jnp.zeros((m,), jnp.result_type(A.dtype, x.dtype)),
                        x, A.data)
    acc, _ = jax.lax.scan(one_diag, acc0,
                          (A.offsets.astype(jnp.int32), A.data),
                          unroll=min(A.ndiag, _DIA_UNROLL_MAX))
    return acc


def _spmv_ell(A: ELL, x):
    return jnp.sum(A.data * jnp.take(x, A.cols, mode="clip"), axis=1)


def _spmv_bsr(A: BSR, x):
    bs = A.block_size
    m, n = A.shape
    xb = x.reshape(n // bs, bs)
    gathered = jnp.take(xb, A.indices, axis=0, mode="clip")  # (nblk, bs)
    prod = jnp.einsum("nij,nj->ni", A.data, gathered)
    k = jnp.arange(A.nblocks, dtype=jnp.int32)
    brow = jnp.searchsorted(A.indptr, k, side="right").astype(jnp.int32) - 1
    brow = jnp.clip(brow, 0, m // bs - 1)
    yb = jax.ops.segment_sum(prod, brow, num_segments=m // bs)
    return yb.reshape(m)


def _spmv_dense(A: Dense, x):
    return A.data @ x


def _spmv_hyb(A: HYB, x):
    return _spmv_ell(A.ell, x) + _spmv_coo(A.coo, x)


def sell_sorted_ids(slice_ptrs, c: int, capacity: int, nslices: int):
    """Per-entry *sorted row position* of a flat SELL layout (jit-able).

    The SELL analogue of :func:`csr_row_ids`: recover each stored entry's
    (slice, lane) from the slice-pointer array in one vectorised
    searchsorted — column-major within a slice means position ``q`` of
    slice ``s`` sits on lane ``(q - slice_ptrs[s]) % C``. Used by the
    diagonal update/extract paths; the reference SpMV/SpMM reduce over
    whole planes instead (:func:`_sell_plane_ids` — one searchsorted per
    *plane*, not per entry).
    """
    q = jnp.arange(capacity, dtype=jnp.int32)
    s = jnp.searchsorted(slice_ptrs, q, side="right").astype(jnp.int32) - 1
    s = jnp.clip(s, 0, nslices - 1)
    lane = (q - slice_ptrs[s]) % c
    return s * c + lane


def _sell_plane_ids(A: SELL):
    """Slice id of each width *plane* (capacity is always a multiple of C,
    so the flat arrays are exactly ``capacity // C`` planes of C lanes)."""
    t = A.capacity // A.c
    sid = jnp.searchsorted(A.slice_ptrs,
                           jnp.arange(t, dtype=jnp.int32) * A.c,
                           side="right").astype(jnp.int32) - 1
    return jnp.clip(sid, 0, A.nslices - 1)


def _spmv_sell(A: SELL, x):
    # plane-wise: one (planes, C) gather + a segment reduction over planes
    # grouped by slice — far cheaper than per-entry segment ids over the
    # padded capacity.
    m = A.shape[0]
    c = A.c
    t = A.capacity // c
    contrib = A.data.reshape(t, c) * jnp.take(x, A.cols.reshape(t, c),
                                              mode="clip")
    y_sorted = jax.ops.segment_sum(contrib, _sell_plane_ids(A),
                                   num_segments=A.nslices).reshape(-1)
    # ghost lanes carry perm == m and are dropped by the OOB scatter
    return jnp.zeros((m,), y_sorted.dtype).at[A.perm].add(y_sorted)


_SPMV = {COO: _spmv_coo, CSR: _spmv_csr, DIA: _spmv_dia, ELL: _spmv_ell,
         BSR: _spmv_bsr, Dense: _spmv_dense, HYB: _spmv_hyb,
         SELL: _spmv_sell}


def spmv(A, x, backend: str = "ref", cfg=None):
    """y = A @ x. ``backend='ref'`` pure-jnp; ``'pallas'`` TPU kernels where
    available (CSR/DIA/ELL/BSR/HYB), falling back to ref otherwise;
    ``'auto'`` picks pallas exactly when a measured kernel config beats the
    reference path (see :func:`kernel_route`) and threads that config.
    ``cfg`` overrides the kernel tile config (dict, e.g. ``{"tm": 256,
    "tk": 2048}``); None uses the tuned winner (auto) or the density
    heuristic (pallas)."""
    if isinstance(A, _DYN_TYPES):
        return A.spmv(x, backend=backend, cfg=cfg)
    if backend == "auto":
        backend, auto_cfg = kernel_route(A)
        cfg = cfg if cfg is not None else auto_cfg
    if backend == "pallas":
        from repro.kernels import ops as kops  # lazy: keep core import-light
        fn = kops.SPMV_PALLAS.get(type(A))
        if fn is not None:
            return fn(A, x, cfg=cfg)
        _count_no_kernel(A, "spmv")
    return _SPMV[type(A)](A, x)


# ---------------------------------------------------------------------------
# SpMM: Y = A @ B (B dense, column-major tiles on TPU)
# ---------------------------------------------------------------------------


def _spmm_coo(A: COO, B):
    contrib = A.data[:, None] * jnp.take(B, A.col, axis=0, mode="clip")
    return jax.ops.segment_sum(contrib, A.row, num_segments=A.shape[0])


def _spmm_csr(A: CSR, B):
    rows = csr_row_ids(A.indptr, A.capacity, A.shape[0])
    contrib = A.data[:, None] * jnp.take(B, A.indices, axis=0, mode="clip")
    return jax.ops.segment_sum(contrib, rows, num_segments=A.shape[0])


def _spmm_dia(A: DIA, B):
    m, n = A.shape
    i = jnp.arange(m, dtype=jnp.int32)[None, :]
    cols = i + A.offsets[:, None].astype(jnp.int32)
    valid = (cols >= 0) & (cols < n)
    bv = jnp.take(B, jnp.clip(cols, 0, n - 1), axis=0, mode="clip")  # (nd, M, K)
    return jnp.sum(jnp.where(valid[..., None], A.data[..., None] * bv, 0), axis=0)


def _spmm_ell(A: ELL, B):
    bv = jnp.take(B, A.cols, axis=0, mode="clip")  # (M, K, Kb)
    return jnp.sum(A.data[..., None] * bv, axis=1)


def _spmm_bsr(A: BSR, B):
    # The MXU path: every stored block is a (bs x bs) x (bs x Kb) matmul.
    bs = A.block_size
    m, n = A.shape
    kb = B.shape[1]
    Bb = B.reshape(n // bs, bs, kb)
    gathered = jnp.take(Bb, A.indices, axis=0, mode="clip")  # (nblk, bs, Kb)
    prod = jnp.einsum("nij,njk->nik", A.data, gathered)
    k = jnp.arange(A.nblocks, dtype=jnp.int32)
    brow = jnp.searchsorted(A.indptr, k, side="right").astype(jnp.int32) - 1
    brow = jnp.clip(brow, 0, m // bs - 1)
    yb = jax.ops.segment_sum(prod, brow, num_segments=m // bs)
    return yb.reshape(m, kb)


def _spmm_dense(A: Dense, B):
    return A.data @ B


def _spmm_hyb(A: HYB, B):
    return _spmm_ell(A.ell, B) + _spmm_coo(A.coo, B)


def _spmm_sell(A: SELL, B):
    m = A.shape[0]
    kb = B.shape[1]
    c = A.c
    t = A.capacity // c
    bv = jnp.take(B, A.cols.reshape(t, c), axis=0, mode="clip")  # (t, c, Kb)
    contrib = A.data.reshape(t, c)[..., None] * bv
    y_sorted = jax.ops.segment_sum(contrib, _sell_plane_ids(A),
                                   num_segments=A.nslices)
    y_sorted = y_sorted.reshape(A.nslices * c, kb)
    return jnp.zeros((m, kb), y_sorted.dtype).at[A.perm].add(y_sorted)


_SPMM = {COO: _spmm_coo, CSR: _spmm_csr, DIA: _spmm_dia, ELL: _spmm_ell,
         BSR: _spmm_bsr, Dense: _spmm_dense, HYB: _spmm_hyb,
         SELL: _spmm_sell}


def spmm(A, B, backend: str = "ref", cfg=None):
    """Y = A @ B with dense B of shape (N, K). ``backend``/``cfg`` as in
    :func:`spmv` (auto routing keys on the ``op="spmm"`` records, bucketed
    by the rhs width K — a winner measured at one batch width never
    routes another)."""
    if isinstance(A, _DYN_TYPES):
        return A.spmm(B, backend=backend, cfg=cfg)
    if backend == "auto":
        backend, auto_cfg = kernel_route(A, op="spmm", ncols=B.shape[1])
        cfg = cfg if cfg is not None else auto_cfg
    if backend == "pallas":
        from repro.kernels import ops as kops
        fn = kops.SPMM_PALLAS.get(type(A))
        if fn is not None:
            return fn(A, B, cfg=cfg)
        _count_no_kernel(A, "spmm")
    return _SPMM[type(A)](A, B)


def spmm_t(A, X, backend: str = "ref", cfg=None):
    """Y = X @ A^T for activations X of shape (T, N); returns (T, M).

    The serving orientation: ``LinearSparse`` keeps its weight transposed
    ((d_out, d_in)) and activations row-major, so this is the layer
    matmul with **no activation transposes** on the Pallas path. The
    reference path *is* the classic double transpose
    (``spmm(A, X.T).T``) — the baseline the equivalence tests compare
    against, and what the fused-transpose kernels must beat to route.
    Auto routing keys on ``op="spmm_t"`` records bucketed by T.
    """
    if isinstance(A, _DYN_TYPES):
        return A.spmm_t(X, backend=backend, cfg=cfg)
    if backend == "auto":
        backend, auto_cfg = kernel_route(A, op="spmm_t", ncols=X.shape[0])
        cfg = cfg if cfg is not None else auto_cfg
    if backend == "pallas":
        from repro.kernels import ops as kops
        fn = kops.SPMM_T_PALLAS.get(type(A))
        if fn is not None:
            return fn(A, X, cfg=cfg)
        _count_no_kernel(A, "spmm_t")
    return _SPMM[type(A)](A, X.T).T


# ---------------------------------------------------------------------------
# Diagonal extract / update (HPCG's TestCG mutates the diagonal)
# ---------------------------------------------------------------------------


def extract_diagonal(A):
    m, n = A.shape
    d = min(m, n)
    if isinstance(A, HYB):
        return extract_diagonal(A.ell) + extract_diagonal(A.coo)
    if isinstance(A, COO):
        on = (A.row == A.col) & (A.row < d)
        return jax.ops.segment_sum(jnp.where(on, A.data, 0), jnp.clip(A.row, 0, d - 1), num_segments=d)
    if isinstance(A, CSR):
        from repro.core.convert import csr_to_coo
        return extract_diagonal(csr_to_coo(A))
    if isinstance(A, DIA):
        slot = jnp.argmax(A.offsets == 0)
        has = jnp.any(A.offsets == 0)
        return jnp.where(has, A.data[slot, :d], 0)
    if isinstance(A, ELL):
        i = jnp.arange(A.shape[0], dtype=jnp.int32)[:, None]
        on = A.cols == i
        return jnp.sum(jnp.where(on, A.data, 0), axis=1)[:d]
    if isinstance(A, BSR):
        from repro.core.convert import bsr_to_coo
        return extract_diagonal(bsr_to_coo(A))
    if isinstance(A, SELL):
        from repro.core.convert import sell_to_coo
        return extract_diagonal(sell_to_coo(A))
    if isinstance(A, Dense):
        return jnp.diagonal(A.data)[:d]
    raise TypeError(type(A))


def update_diagonal(A, new_diag):
    """Replace the main diagonal values (pattern must already contain it)."""
    if isinstance(A, COO):
        on = (A.row == A.col)
        return COO(A.row, A.col, jnp.where(on, jnp.take(new_diag, jnp.clip(A.row, 0, new_diag.shape[0] - 1), mode="clip"), A.data), A.shape, A.nnz)
    if isinstance(A, CSR):
        rows = csr_row_ids(A.indptr, A.capacity, A.shape[0])
        on = A.indices == rows
        return CSR(A.indptr, A.indices, jnp.where(on, jnp.take(new_diag, rows, mode="clip"), A.data), A.shape, A.nnz)
    if isinstance(A, DIA):
        slot = jnp.argmax(A.offsets == 0)
        row = jnp.zeros((A.data.shape[1],), A.dtype).at[:new_diag.shape[0]].set(new_diag.astype(A.dtype))
        return DIA(A.offsets, A.data.at[slot].set(row), A.shape, A.nnz)
    if isinstance(A, ELL):
        i = jnp.arange(A.shape[0], dtype=jnp.int32)[:, None]
        on = A.cols == i
        vals = jnp.take(new_diag, jnp.clip(i[:, 0], 0, new_diag.shape[0] - 1), mode="clip")[:, None]
        return ELL(A.cols, jnp.where(on, vals, A.data), A.shape, A.nnz)
    if isinstance(A, SELL):
        p = sell_sorted_ids(A.slice_ptrs, A.c, A.capacity, A.nslices)
        rows = jnp.take(A.perm, p, mode="clip")
        on = A.cols == rows  # padding col=-1 never matches a row id
        vals = jnp.take(new_diag,
                        jnp.clip(rows, 0, new_diag.shape[0] - 1), mode="clip")
        return SELL(A.cols, jnp.where(on, vals, A.data), A.perm,
                    A.slice_ptrs, A.shape, A.nnz, A.c, A.sigma)
    if isinstance(A, Dense):
        d = min(A.shape)
        i = jnp.arange(d)
        return Dense(A.data.at[i, i].set(new_diag[:d].astype(A.dtype)), A.shape, A.nnz)
    raise TypeError(type(A))


# ---------------------------------------------------------------------------
# Dense-vector algorithms (paper §III-D: dot, WAXPBY, reduction, assign)
# ---------------------------------------------------------------------------


def dot(x, y):
    return jnp.dot(x, y)


def waxpby(alpha, x, beta, y):
    """w = alpha*x + beta*y (HPCG's vector update)."""
    return alpha * x + beta * y


def axpy(alpha, x, y):
    return alpha * x + y


def norm2(x):
    return jnp.sqrt(jnp.dot(x, x))


def assign(x, value):
    """Morpheus::assign — fill (ZeroVector when value == 0)."""
    return jnp.full_like(x, value)


def reduction(x):
    return jnp.sum(x)


def scan(x):
    return jnp.cumsum(x)


# populated by repro.core.dynamic to avoid a circular import
_DYN_TYPES: tuple = ()


def _register_dynamic(*types):
    global _DYN_TYPES
    _DYN_TYPES = tuple(set(_DYN_TYPES) | set(types))
