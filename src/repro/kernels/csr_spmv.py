"""Pallas TPU kernel: CSR-format SpMV — the paper's reference format.

CSR's row walk is serial on paper but the layout is still the densest
general-purpose encoding, so the reference format deserves a real kernel
rather than the pure-jnp segment-sum fallback. The TPU derivation
(DESIGN.md §2, §8) replaces the GPU's warp-per-row trick with a 2-D
row x nnz tiling:

  * grid over row tiles of ``tm`` rows; the row-pointer array rides in
    SMEM via scalar prefetch and bounds each tile's nnz window
    ``[indptr[row0], indptr[row0 + tm])``;
  * the window streams through in fixed ``tk``-entry chunks via ``pl.ds``
    dynamic-start loads from the VMEM-resident value/index arrays — the
    trip count is the tile's *own* nnz (the per-tile density heuristic:
    a sparse tile costs its actual entries, a dense tile streams more
    chunks; load imbalance never pads), which makes this an
    nnz-partitioned schedule rather than a padded one;
  * per chunk: VPU gather of x at the stored columns, f32 multiply, then
    a segment reduction onto the tile's rows via a **segmented prefix
    sum** (Hillis-Steele, log2(tk) statically-unrolled shift/add steps)
    whose running sum *resets at every row boundary*: row r's chunk
    partial reads out directly at its last position, so it only ever
    accumulates r's own entries. This keeps the O(tk log tk + tm) cost
    that replaced the one-hot ``(tk, tm)`` matmul (O(tk*tm) MACs per
    chunk, the term that dominated the kernel's cost) *without* the
    catastrophic cancellation of a plain prefix-sum difference, whose
    per-row error scales with the chunk's running total rather than the
    row's own magnitude;
  * f32 accumulation throughout, cast to the output dtype once.

Chunk tails need no masking: the scan is a prefix — positions past the
tile's window belong to later rows, sit after a row-boundary reset, and
are never read out; capacity padding past ``indptr[-1]`` is zero.

Tile sizes ``(tm, tk)`` are the kernel's tuning space — searched by
``repro.tuning.kernel_tune`` per (shape bucket, backend, device) and
threaded through ``repro.kernels.ops`` as ``cfg=``. Preconditions handled
by the ops wrapper: per-entry row ids are precomputed on device (one
searchsorted over indptr — jit-able, fused with the caller), and the
(rows, indices, data) arrays plus x must fit the VMEM residency budget,
else it falls back to the reference path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.ops import vma


def _segmented_cumsum(v: jax.Array, flags: jax.Array) -> jax.Array:
    """Inclusive prefix sum of ``v`` that restarts wherever ``flags`` is
    True (Hillis-Steele, statically unrolled — vector shifts and adds
    only, no scatter)."""
    n = v.shape[0]
    f = flags
    d = 1
    while d < n:
        vs = jnp.concatenate([jnp.zeros((d,), v.dtype), v[:-d]])
        fs = jnp.concatenate([jnp.zeros((d,), jnp.bool_), f[:-d]])
        v = v + jnp.where(f, jnp.zeros((), v.dtype), vs)
        f = f | fs
        d *= 2
    return v


def _csr_kernel(indptr_ref, starts_ref, ends_ref, rows_ref, indices_ref,
                data_ref, x_ref, y_ref, *, tm: int, tk: int):
    i = pl.program_id(0)
    row0 = i * tm
    w0 = indptr_ref[row0]          # this tile's nnz window [w0, wend)
    wend = indptr_ref[row0 + tm]
    starts = starts_ref[...]       # (tm,) per-row entry ranges
    ends = ends_ref[...]
    x = x_ref[...]

    def window(w, acc):
        base = w0 + w * tk
        cols = indices_ref[pl.ds(base, tk)]
        vals = data_ref[pl.ds(base, tk)]
        rws = rows_ref[pl.ds(base, tk)]
        contrib = (vals.astype(jnp.float32)
                   * jnp.take(x, cols, mode="clip").astype(jnp.float32))
        # segment boundaries = row changes; the scan implicitly restarts at
        # the chunk start, which is exactly a row's continuation point.
        flags = jnp.concatenate(
            [jnp.zeros((1,), jnp.bool_), rws[1:] != rws[:-1]])
        seg = _segmented_cumsum(contrib, flags)
        lo = jnp.clip(starts - base, 0, tk)
        hi = jnp.clip(ends - base, 0, tk)
        # row r's partial over this chunk reads out at its last position
        part = jnp.take(seg, jnp.maximum(hi - 1, 0))
        return acc + jnp.where(hi > lo, part, 0.0)

    nwin = (wend - w0 + tk - 1) // tk  # this tile's own nnz, in chunks
    acc = jax.lax.fori_loop(0, nwin, window, jnp.zeros((tm,), jnp.float32))
    y_ref[...] = acc.astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tm", "tk", "interpret"))
def csr_spmv(indptr: jax.Array, rows: jax.Array, indices: jax.Array,
             data: jax.Array, x: jax.Array, tm: int = 256, tk: int = 512,
             interpret: bool = True) -> jax.Array:
    """y = A @ x for CSR A given as (indptr[M+1], indices[cap], data[cap]).

    ``rows`` is the precomputed per-entry row id array (see
    ``repro.core.ops.csr_row_ids``); capacity padding past ``indptr[-1]``
    is never read because every tile stops at its own window end.
    """
    m = indptr.shape[0] - 1
    cap = data.shape[0]
    mp = ((m + tm - 1) // tm) * tm
    indptr = indptr.astype(jnp.int32)
    if mp != m:
        # padded rows are empty: their window [indptr[-1], indptr[-1]) is nil
        indptr = jnp.concatenate(
            [indptr, jnp.broadcast_to(indptr[-1], (mp - m,))])
    starts = indptr[:-1]
    ends = indptr[1:]
    # window loads start anywhere in [0, end); pad so the last chunk of the
    # last window stays in bounds for any start alignment.
    capp = ((cap + tk - 1) // tk) * tk + tk
    rows = jnp.pad(rows, (0, capp - cap))
    indices = jnp.pad(indices, (0, capp - cap))
    data = jnp.pad(data, (0, capp - cap))

    grid = (mp // tm,)
    kernel = functools.partial(_csr_kernel, tm=tm, tk=tk)
    y = pl.pallas_call(
        kernel,
        name="csr_spmv",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((tm,), lambda i, *_: (i,)),
                pl.BlockSpec((tm,), lambda i, *_: (i,)),
                pl.BlockSpec(rows.shape, lambda i, *_: (0,)),
                pl.BlockSpec(indices.shape, lambda i, *_: (0,)),
                pl.BlockSpec(data.shape, lambda i, *_: (0,)),
                pl.BlockSpec(x.shape, lambda i, *_: (0,)),
            ],
            out_specs=pl.BlockSpec((tm,), lambda i, *_: (i,)),
        ),
        out_shape=jax.ShapeDtypeStruct((mp,), x.dtype, vma=vma(data, x)),
        interpret=interpret,
    )(indptr, starts, ends, rows, indices, data, x)
    return y[:m]
