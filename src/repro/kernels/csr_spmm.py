"""Pallas TPU kernel: CSR-format SpMM — Y = A @ B for a dense rhs batch.

SpMM is SpMV whose computation changed: a request batch widens the
right-hand side from a vector to (N, K), and the winning schedule moves
with K (the paper's runtime-selection thesis applied to the *operation*,
not just the pattern — Stylianou et al., arXiv:2303.05098). This kernel
extends the row x nnz tiling of ``csr_spmv.py`` (the segmented-prefix-sum
schedule that made CSR SpMV 2.5x vs ref) with a third **rhs tile axis**:

  * grid over (row tiles of ``tm`` rows) x (rhs tiles of ``tn`` columns);
    the row-pointer array rides in SMEM via scalar prefetch and bounds
    each row tile's nnz window exactly as in SpMV;
  * the window streams in ``tk``-entry chunks; per chunk the gather of B
    becomes a *row* gather — ``B[cols]`` is (tk, tn), tn lanes wide, so
    every stored entry now feeds tn MACs instead of one (the arithmetic
    intensity jump that makes wide-batch SpMM compute-bound where SpMV
    was bandwidth-bound);
  * the segmented prefix sum (Hillis-Steele, resets at row boundaries)
    runs unchanged along the nnz axis, broadcast over the tn lanes; each
    row's chunk partial reads out at its last position as a (tm, tn) tile.

Two rhs orientations, because the serving stack hands activations over
row-major:

  * :func:`csr_spmm` — B is (N, K) (columns of the classic SpMM); output
    (M, K). The rhs tile is a ``(N, tn)`` VMEM-resident slab.
  * :func:`csr_spmm_t` — X is (T, N): a batch of T row-vector activations
    (``LinearSparse``'s layout — one jit'd call computes ``X @ A^T`` with
    **no transposes of the activations on either side**). The scan runs
    along the minor axis; the output tile is (tb, tm) with rows on the
    lanes.

Tile sizes ``(tm, tk, tn)`` are the tuning space — searched per
(shape bucket, **rhs-width bucket**, backend, device) by
``repro.tuning.kernel_tune``: a config tuned at K=1 is never replayed at
K=256.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.ops import vma


def _segmented_cumsum(v: jax.Array, flags: jax.Array, axis: int = 0) -> jax.Array:
    """Inclusive prefix sum of ``v`` along ``axis`` that restarts wherever
    ``flags`` (1-D along that axis) is True. Hillis-Steele, statically
    unrolled — vector shifts and adds only, no scatter; the flag vector is
    broadcast over the other (rhs-lane) axis."""
    n = v.shape[axis]
    f = flags
    d = 1

    def shift(a, by, ax):
        pad = [(0, 0)] * a.ndim
        pad[ax] = (by, 0)
        sl = [slice(None)] * a.ndim
        sl[ax] = slice(None, -by)
        return jnp.pad(a[tuple(sl)], pad)

    while d < n:
        vs = shift(v, d, axis)
        fs = jnp.concatenate([jnp.zeros((d,), jnp.bool_), f[:-d]])
        mask = f if v.ndim == 1 else jnp.expand_dims(f, 1 - axis)
        v = v + jnp.where(mask, jnp.zeros((), v.dtype), vs)
        f = f | fs
        d *= 2
    return v


def _spmm_kernel(indptr_ref, starts_ref, ends_ref, rows_ref, indices_ref,
                 data_ref, b_ref, y_ref, *, tm: int, tk: int, tn: int):
    """One (row tile i, rhs tile j) output block; B tile is (N, tn)."""
    i = pl.program_id(0)
    row0 = i * tm
    w0 = indptr_ref[row0]
    wend = indptr_ref[row0 + tm]
    starts = starts_ref[...]
    ends = ends_ref[...]
    b = b_ref[...]                      # (N, tn) rhs slab for this j

    def window(w, acc):
        base = w0 + w * tk
        cols = indices_ref[pl.ds(base, tk)]
        vals = data_ref[pl.ds(base, tk)]
        rws = rows_ref[pl.ds(base, tk)]
        gathered = jnp.take(b, cols, axis=0, mode="clip")      # (tk, tn)
        contrib = vals.astype(jnp.float32)[:, None] * gathered.astype(jnp.float32)
        flags = jnp.concatenate(
            [jnp.zeros((1,), jnp.bool_), rws[1:] != rws[:-1]])
        seg = _segmented_cumsum(contrib, flags, axis=0)
        lo = jnp.clip(starts - base, 0, tk)
        hi = jnp.clip(ends - base, 0, tk)
        part = jnp.take(seg, jnp.maximum(hi - 1, 0), axis=0)   # (tm, tn)
        return acc + jnp.where((hi > lo)[:, None], part, 0.0)

    nwin = (wend - w0 + tk - 1) // tk
    acc = jax.lax.fori_loop(0, nwin, window,
                            jnp.zeros((tm, tn), jnp.float32))
    y_ref[...] = acc.astype(y_ref.dtype)


def _spmm_t_kernel(indptr_ref, starts_ref, ends_ref, rows_ref, indices_ref,
                   data_ref, x_ref, y_ref, *, tm: int, tk: int, tn: int):
    """Transposed-rhs orientation: X tile is (tn, N) activations; the
    segmented scan runs along the minor (nnz) axis and the output tile is
    (tn, tm) — activations never transpose on either side."""
    i = pl.program_id(0)
    row0 = i * tm
    w0 = indptr_ref[row0]
    wend = indptr_ref[row0 + tm]
    starts = starts_ref[...]
    ends = ends_ref[...]
    x = x_ref[...]                      # (tn, N) activation rows

    def window(w, acc):
        base = w0 + w * tk
        cols = indices_ref[pl.ds(base, tk)]
        vals = data_ref[pl.ds(base, tk)]
        rws = rows_ref[pl.ds(base, tk)]
        gathered = jnp.take(x, jnp.clip(cols, 0, x.shape[1] - 1), axis=1)
        contrib = vals.astype(jnp.float32)[None, :] * gathered.astype(jnp.float32)
        flags = jnp.concatenate(
            [jnp.zeros((1,), jnp.bool_), rws[1:] != rws[:-1]])
        seg = _segmented_cumsum(contrib, flags, axis=1)        # (tn, tk)
        lo = jnp.clip(starts - base, 0, tk)
        hi = jnp.clip(ends - base, 0, tk)
        part = jnp.take(seg, jnp.maximum(hi - 1, 0), axis=1)   # (tn, tm)
        return acc + jnp.where((hi > lo)[None, :], part, 0.0)

    nwin = (wend - w0 + tk - 1) // tk
    acc = jax.lax.fori_loop(0, nwin, window,
                            jnp.zeros((tn, tm), jnp.float32))
    y_ref[...] = acc.astype(y_ref.dtype)


def _pad_csr(indptr, rows, indices, data, m, cap, tm, tk):
    """Shared row/nnz padding: rows pad to a tm multiple with empty
    windows, entry arrays pad so any ``pl.ds`` chunk start stays in
    bounds (padding past ``indptr[-1]`` is never read out)."""
    mp = ((m + tm - 1) // tm) * tm
    indptr = indptr.astype(jnp.int32)
    if mp != m:
        indptr = jnp.concatenate(
            [indptr, jnp.broadcast_to(indptr[-1], (mp - m,))])
    capp = ((cap + tk - 1) // tk) * tk + tk
    rows = jnp.pad(rows, (0, capp - cap))
    indices = jnp.pad(indices, (0, capp - cap))
    data = jnp.pad(data, (0, capp - cap))
    return indptr, rows, indices, data, mp


@functools.partial(jax.jit,
                   static_argnames=("tm", "tk", "tn", "interpret"))
def csr_spmm(indptr: jax.Array, rows: jax.Array, indices: jax.Array,
             data: jax.Array, B: jax.Array, tm: int = 256, tk: int = 512,
             tn: int = 128, interpret: bool = True) -> jax.Array:
    """Y = A @ B for CSR A and dense B of shape (N, K); returns (M, K).

    ``rows`` is the precomputed per-entry row id array
    (``repro.core.ops.csr_row_ids``). K pads to a ``tn`` multiple; the
    pad columns are sliced off before returning.
    """
    m = indptr.shape[0] - 1
    cap = data.shape[0]
    n, kb = B.shape
    indptr, rows, indices, data, mp = _pad_csr(
        indptr, rows, indices, data, m, cap, tm, tk)
    kp = ((kb + tn - 1) // tn) * tn
    if kp != kb:
        B = jnp.pad(B, ((0, 0), (0, kp - kb)))

    grid = (mp // tm, kp // tn)
    kernel = functools.partial(_spmm_kernel, tm=tm, tk=tk, tn=tn)
    y = pl.pallas_call(
        kernel,
        name="csr_spmm",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((tm,), lambda i, j, *_: (i,)),
                pl.BlockSpec((tm,), lambda i, j, *_: (i,)),
                pl.BlockSpec(rows.shape, lambda i, j, *_: (0,)),
                pl.BlockSpec(indices.shape, lambda i, j, *_: (0,)),
                pl.BlockSpec(data.shape, lambda i, j, *_: (0,)),
                pl.BlockSpec((n, tn), lambda i, j, *_: (0, j)),
            ],
            out_specs=pl.BlockSpec((tm, tn), lambda i, j, *_: (i, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((mp, kp), B.dtype,
                                       vma=vma(data, B)),
        interpret=interpret,
    )(indptr, starts_of(indptr), ends_of(indptr), rows, indices, data, B)
    return y[:m, :kb]


def starts_of(indptr: jax.Array) -> jax.Array:
    return indptr[:-1]


def ends_of(indptr: jax.Array) -> jax.Array:
    return indptr[1:]


@functools.partial(jax.jit,
                   static_argnames=("tm", "tk", "tn", "interpret"))
def csr_spmm_t(indptr: jax.Array, rows: jax.Array, indices: jax.Array,
               data: jax.Array, X: jax.Array, tm: int = 256, tk: int = 512,
               tn: int = 8, interpret: bool = True) -> jax.Array:
    """Y = X @ A^T for CSR A and activations X of shape (T, N); returns
    (T, M) — the serving layout, no activation transposes."""
    m = indptr.shape[0] - 1
    cap = data.shape[0]
    t, n = X.shape
    indptr, rows, indices, data, mp = _pad_csr(
        indptr, rows, indices, data, m, cap, tm, tk)
    tp = ((t + tn - 1) // tn) * tn
    if tp != t:
        X = jnp.pad(X, ((0, tp - t), (0, 0)))

    grid = (mp // tm, tp // tn)
    kernel = functools.partial(_spmm_t_kernel, tm=tm, tk=tk, tn=tn)
    y = pl.pallas_call(
        kernel,
        name="csr_spmm_t",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((tm,), lambda i, j, *_: (i,)),
                pl.BlockSpec((tm,), lambda i, j, *_: (i,)),
                pl.BlockSpec(rows.shape, lambda i, j, *_: (0,)),
                pl.BlockSpec(indices.shape, lambda i, j, *_: (0,)),
                pl.BlockSpec(data.shape, lambda i, j, *_: (0,)),
                pl.BlockSpec((tn, n), lambda i, j, *_: (j, 0)),
            ],
            out_specs=pl.BlockSpec((tn, tm), lambda i, j, *_: (j, i)),
        ),
        out_shape=jax.ShapeDtypeStruct((tp, mp), X.dtype,
                                       vma=vma(data, X)),
        interpret=interpret,
    )(indptr, starts_of(indptr), ends_of(indptr), rows, indices, data, X)
    return y[:t, :m]
