"""Pallas TPU kernel: SELL-C-sigma SpMV / SpMM / SpMM_T.

SELL-C-sigma (Kreutzer et al., arXiv:1307.6209) stores sigma-window
length-sorted rows in slices of C, each padded only to its own width, flat
and column-major within the slice — so every width *plane* of a slice is C
contiguous lanes holding one entry of C consecutive sorted rows. That is
exactly the lane-aligned orientation the ELL "col" layout manufactures per
tile with an in-VMEM transpose (``ell_spmv.py``), except here the layout
is native and the padded width is per-slice instead of the global kmax:

  * grid over *slice tiles* of ``ts`` slices; the slice-pointer array
    rides in SMEM via scalar prefetch (the CSR kernel's idiom) and bounds
    each slice's flat window ``[ptrs[s], ptrs[s+1])``;
  * per slice, a ``fori_loop`` whose trip count is the slice's *own*
    width streams C-entry planes via ``pl.ds`` dynamic-start loads: VPU
    gather of x at the stored columns, f32 multiply-accumulate onto a
    (C,) lane accumulator — one output element per lane, no segmented
    reduction at all (the sort guarantees a lane is one row);
  * the kernel computes y in *sorted row order*; the wrapper scatters it
    back through the container's permutation (ghost lanes carry row id M
    and are dropped by the out-of-bounds scatter).

Work is ``sum_s C * width_s`` — nnz plus the per-slice padding the
sigma-sort minimizes — vs ELL's ``M * kmax`` blowup and CSR's log-depth
segmented scan per chunk. ``(c, sigma)`` reshape the container itself and
``ts`` the launch geometry; all three are searched by
``repro.tuning.kernel_tune`` per (shape bucket, backend, device).

SpMM streams (C, tn) gather-FMA planes per rhs tile; SpMM_T takes
activations (T, N) row-major and accumulates (tn, C) planes along the
minor axis — no activation transposes (see ``csr_spmm.py``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.ops import vma


def _pad_ptrs(slice_ptrs: jax.Array, ts: int):
    """Pad the slice-pointer array so the grid covers whole slice tiles;
    padded slices are empty (zero-width windows at the capacity end)."""
    nslices = slice_ptrs.shape[0] - 1
    nsp = (nslices + ts - 1) // ts
    ptrs = slice_ptrs.astype(jnp.int32)
    pad = nsp * ts - nslices
    if pad:
        ptrs = jnp.concatenate([ptrs, jnp.broadcast_to(ptrs[-1], (pad,))])
    return ptrs, nsp


def _sell_kernel(ptrs_ref, cols_ref, data_ref, x_ref, y_ref, *, c: int,
                 ts: int):
    i = pl.program_id(0)
    s0 = i * ts
    x = x_ref[...]
    for j in range(ts):  # static unroll over the tile's slices
        w0 = ptrs_ref[s0 + j]
        w1 = ptrs_ref[s0 + j + 1]

        def plane(t, acc, w0=w0):
            base = w0 + t * c
            cc = cols_ref[pl.ds(base, c)]
            vv = data_ref[pl.ds(base, c)]
            g = jnp.take(x, cc, mode="clip").astype(jnp.float32)
            return acc + vv.astype(jnp.float32) * g

        acc = jax.lax.fori_loop(0, (w1 - w0) // c, plane,
                                jnp.zeros((c,), jnp.float32))
        y_ref[pl.ds(j * c, c)] = acc.astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("m", "c", "ts", "interpret"))
def sell_spmv(slice_ptrs: jax.Array, cols: jax.Array, data: jax.Array,
              perm: jax.Array, x: jax.Array, m: int, c: int,
              ts: int = 8, interpret: bool = True) -> jax.Array:
    """y = A @ x for SELL A given as flat (slice_ptrs, cols, data, perm)."""
    nslices = slice_ptrs.shape[0] - 1
    ptrs, nsp = _pad_ptrs(slice_ptrs, ts)
    grid = (nsp,)
    kernel = functools.partial(_sell_kernel, c=c, ts=ts)
    y_sorted = pl.pallas_call(
        kernel,
        name="sell_spmv",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec(cols.shape, lambda i, *_: (0,)),
                pl.BlockSpec(data.shape, lambda i, *_: (0,)),
                pl.BlockSpec(x.shape, lambda i, *_: (0,)),
            ],
            out_specs=pl.BlockSpec((ts * c,), lambda i, *_: (i,)),
        ),
        out_shape=jax.ShapeDtypeStruct((nsp * ts * c,), x.dtype,
                                       vma=vma(data, x)),
        interpret=interpret,
    )(ptrs, cols, data, x)
    # back to matrix row order; ghost lanes (perm == m) drop out of bounds
    return jnp.zeros((m,), x.dtype).at[perm].set(y_sorted[:nslices * c])


# ---------------------------------------------------------------------------
# SpMM: Y = A @ B (and the transposed-rhs serving orientation)
# ---------------------------------------------------------------------------


def _sell_spmm_kernel(ptrs_ref, cols_ref, data_ref, b_ref, y_ref, *, c: int,
                      ts: int, tn: int):
    i = pl.program_id(0)
    s0 = i * ts
    b = b_ref[...]                             # (N, tn)
    for j in range(ts):
        w0 = ptrs_ref[s0 + j]
        w1 = ptrs_ref[s0 + j + 1]

        def plane(t, acc, w0=w0):
            base = w0 + t * c
            cc = cols_ref[pl.ds(base, c)]
            vv = data_ref[pl.ds(base, c)]
            g = jnp.take(b, cc, axis=0, mode="clip").astype(jnp.float32)
            return acc + vv.astype(jnp.float32)[:, None] * g

        acc = jax.lax.fori_loop(0, (w1 - w0) // c, plane,
                                jnp.zeros((c, tn), jnp.float32))
        y_ref[pl.ds(j * c, c), :] = acc.astype(y_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("m", "c", "ts", "tn", "interpret"))
def sell_spmm(slice_ptrs: jax.Array, cols: jax.Array, data: jax.Array,
              perm: jax.Array, B: jax.Array, m: int, c: int,
              ts: int = 8, tn: int = 128, interpret: bool = True
              ) -> jax.Array:
    """Y = A @ B for SELL A and dense B (N, Kb)."""
    n, kb = B.shape
    nslices = slice_ptrs.shape[0] - 1
    ptrs, nsp = _pad_ptrs(slice_ptrs, ts)
    kp = ((kb + tn - 1) // tn) * tn
    if kp != kb:
        B = jnp.pad(B, ((0, 0), (0, kp - kb)))
    grid = (nsp, kp // tn)
    kernel = functools.partial(_sell_spmm_kernel, c=c, ts=ts, tn=tn)
    y_sorted = pl.pallas_call(
        kernel,
        name="sell_spmm",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec(cols.shape, lambda i, j, *_: (0,)),
                pl.BlockSpec(data.shape, lambda i, j, *_: (0,)),
                pl.BlockSpec((n, tn), lambda i, j, *_: (0, j)),
            ],
            out_specs=pl.BlockSpec((ts * c, tn), lambda i, j, *_: (i, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((nsp * ts * c, kp), B.dtype,
                                       vma=vma(data, B)),
        interpret=interpret,
    )(ptrs, cols, data, B)
    return jnp.zeros((m, kb), B.dtype).at[perm].set(
        y_sorted[:nslices * c, :kb])


def _sell_spmm_t_kernel(ptrs_ref, cols_ref, data_ref, x_ref, y_ref, *,
                        c: int, ts: int, tn: int):
    i = pl.program_id(0)
    s0 = i * ts
    x = x_ref[...]                             # (tn, N)
    for j in range(ts):
        w0 = ptrs_ref[s0 + j]
        w1 = ptrs_ref[s0 + j + 1]

        def plane(t, acc, w0=w0):
            base = w0 + t * c
            cc = cols_ref[pl.ds(base, c)]
            vv = data_ref[pl.ds(base, c)]
            g = jnp.take(x, jnp.clip(cc, 0, x.shape[1] - 1),
                         axis=1).astype(jnp.float32)  # (tn, c)
            return acc + vv.astype(jnp.float32)[None, :] * g

        acc = jax.lax.fori_loop(0, (w1 - w0) // c, plane,
                                jnp.zeros((tn, c), jnp.float32))
        y_ref[:, pl.ds(j * c, c)] = acc.astype(y_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("m", "c", "ts", "tn", "interpret"))
def sell_spmm_t(slice_ptrs: jax.Array, cols: jax.Array, data: jax.Array,
                perm: jax.Array, X: jax.Array, m: int, c: int,
                ts: int = 8, tn: int = 8, interpret: bool = True
                ) -> jax.Array:
    """Y = X @ A^T for SELL A and activations X (T, N); returns (T, M)."""
    t, n = X.shape
    nslices = slice_ptrs.shape[0] - 1
    ptrs, nsp = _pad_ptrs(slice_ptrs, ts)
    tp = ((t + tn - 1) // tn) * tn
    if tp != t:
        X = jnp.pad(X, ((0, tp - t), (0, 0)))
    grid = (nsp, tp // tn)
    kernel = functools.partial(_sell_spmm_t_kernel, c=c, ts=ts, tn=tn)
    y_sorted = pl.pallas_call(
        kernel,
        name="sell_spmm_t",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec(cols.shape, lambda i, j, *_: (0,)),
                pl.BlockSpec(data.shape, lambda i, j, *_: (0,)),
                pl.BlockSpec((tn, n), lambda i, j, *_: (j, 0)),
            ],
            out_specs=pl.BlockSpec((tn, ts * c), lambda i, j, *_: (j, i)),
        ),
        out_shape=jax.ShapeDtypeStruct((tp, nsp * ts * c), X.dtype,
                                       vma=vma(data, X)),
        interpret=interpret,
    )(ptrs, cols, data, X)
    return jnp.zeros((t, m), X.dtype).at[:, perm].set(
        y_sorted[:t, :nslices * c])
