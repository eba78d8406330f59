"""Pallas TPU kernel: ELL-format SpMV.

ELL pads every row to K entries, turning CSR's serial row walk into dense
(rows x K) vector arithmetic — the TPU-idiomatic replacement for the GPU's
warp-per-row CSR tricks (DESIGN.md §2, §8). The single data-dependent step
is the gather of x at the stored column indices, which maps to the VPU's
dynamic-gather path; everything else is dense multiply-reduce.

Two layouts, selected by ``layout`` (part of the kernel's tuning space):

  * ``"row"`` — the container's native (tm, K) tiles; the reduction runs
    across the minor axis. Wins where the gather dominates and K is the
    contiguous axis (measured fastest on CPU/interpret).
  * ``"col"`` — the same (tm, K) tiles, transposed *per tile inside the
    kernel* (a VMEM-register reshape, never a materialized (K, M) copy —
    a whole-array transpose would add O(nnz) HBM traffic to every call),
    so rows map onto the 128-lane minor axis and the K-loop walks
    contiguous row-vectors: each of the K planes is one lane-aligned
    gather + multiply-accumulate. This is the TPU-friendly orientation.

Blocking: grid over row tiles of ``tm`` rows; x resident in VMEM (ops
wrapper falls back to ref when it would not fit). ``(tm, layout)`` are
searched per (shape bucket, backend, device) by
``repro.tuning.kernel_tune``.

SpMM (:func:`ell_spmm` / :func:`ell_spmm_t`) reuses the same lane-aligned
layouts with an rhs tile axis ``tn``: ``"row"`` materialises the full
(tm, K, tn) gather (one wide VPU pass — wins for small K), ``"col"``
streams K planes of (tm, tn) gather-FMA through a ``fori_loop`` so the
transient footprint stays (tm, tn) no matter how long the rows are (the
pruned-weight case, K in the hundreds). The ``_t`` variant takes
activations (T, N) row-major and scans planes of (tn, tm) gathers along
the minor axis — no activation transposes (see ``csr_spmm.py``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.ops import vma


def _ell_kernel_row(cols_ref, data_ref, x_ref, y_ref):
    cols = cols_ref[...]                       # (tm, K)
    vals = data_ref[...]
    x = x_ref[...]
    gathered = jnp.take(x, cols, mode="clip")  # VPU dynamic gather
    acc = jnp.sum(vals.astype(jnp.float32) * gathered.astype(jnp.float32),
                  axis=1)
    y_ref[...] = acc.astype(y_ref.dtype)


def _ell_kernel_col(cols_ref, data_ref, x_ref, y_ref):
    cols = cols_ref[...].T                     # (K, tm): rows on the lanes,
    vals = data_ref[...].T                     # transposed per tile in VMEM
    x = x_ref[...]
    gathered = jnp.take(x, cols, mode="clip")
    acc = jnp.sum(vals.astype(jnp.float32) * gathered.astype(jnp.float32),
                  axis=0)
    y_ref[...] = acc.astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tm", "layout", "interpret"))
def ell_spmv(cols: jax.Array, data: jax.Array, x: jax.Array,
             tm: int = 256, layout: str = "row",
             interpret: bool = True) -> jax.Array:
    """y = A @ x for ELL A given as (cols[M, K], data[M, K])."""
    if layout not in ("row", "col"):
        raise ValueError(f"layout {layout!r} not in ('row', 'col')")
    m, k = data.shape
    if k == 0:  # every row empty: nothing to stream, nothing to launch
        return jnp.zeros((m,), x.dtype)
    mp = ((m + tm - 1) // tm) * tm
    if mp != m:
        cols = jnp.pad(cols, ((0, mp - m), (0, 0)))
        data = jnp.pad(data, ((0, mp - m), (0, 0)))

    grid = (mp // tm,)
    in_specs = [
        pl.BlockSpec((tm, k), lambda i: (i, 0)),
        pl.BlockSpec((tm, k), lambda i: (i, 0)),
        pl.BlockSpec(x.shape, lambda i: (0,)),
    ]
    kernel = _ell_kernel_col if layout == "col" else _ell_kernel_row
    y = pl.pallas_call(
        kernel,
        name="ell_spmv",
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((tm,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((mp,), x.dtype, vma=vma(data, x)),
        interpret=interpret,
    )(cols, data, x)
    return y[:m]


# ---------------------------------------------------------------------------
# SpMM: Y = A @ B (and the transposed-rhs serving orientation)
# ---------------------------------------------------------------------------


def _ell_spmm_kernel_row(cols_ref, data_ref, b_ref, y_ref):
    cols = cols_ref[...]                       # (tm, K)
    vals = data_ref[...]
    b = b_ref[...]                             # (N, tn)
    gathered = jnp.take(b, cols, axis=0, mode="clip")   # (tm, K, tn)
    acc = jnp.sum(vals.astype(jnp.float32)[..., None]
                  * gathered.astype(jnp.float32), axis=1)
    y_ref[...] = acc.astype(y_ref.dtype)


def _ell_spmm_kernel_col(cols_ref, data_ref, b_ref, y_ref, *, tn: int):
    cols = cols_ref[...]                       # (tm, K)
    vals = data_ref[...]
    b = b_ref[...]                             # (N, tn)
    tm, k = cols.shape

    def plane(kk, acc):
        c = jax.lax.dynamic_index_in_dim(cols, kk, 1, keepdims=False)
        v = jax.lax.dynamic_index_in_dim(vals, kk, 1, keepdims=False)
        g = jnp.take(b, c, axis=0, mode="clip")          # (tm, tn)
        return acc + v.astype(jnp.float32)[:, None] * g.astype(jnp.float32)

    acc = jax.lax.fori_loop(0, k, plane, jnp.zeros((tm, tn), jnp.float32))
    y_ref[...] = acc.astype(y_ref.dtype)


def _ell_spmm_t_kernel_row(cols_ref, data_ref, x_ref, y_ref):
    cols = cols_ref[...]                       # (tm, K)
    vals = data_ref[...]
    x = x_ref[...]                             # (tn, N)
    safe = jnp.clip(cols, 0, x.shape[1] - 1)
    gathered = jnp.take(x, safe, axis=1)       # (tn, tm, K)
    acc = jnp.sum(vals.astype(jnp.float32)[None, ...]
                  * gathered.astype(jnp.float32), axis=2)
    y_ref[...] = acc.astype(y_ref.dtype)       # (tn, tm)


def _ell_spmm_t_kernel_col(cols_ref, data_ref, x_ref, y_ref, *, tn: int):
    cols = cols_ref[...]                       # (tm, K)
    vals = data_ref[...]
    x = x_ref[...]                             # (tn, N)
    tm, k = cols.shape

    def plane(kk, acc):
        c = jax.lax.dynamic_index_in_dim(cols, kk, 1, keepdims=False)
        v = jax.lax.dynamic_index_in_dim(vals, kk, 1, keepdims=False)
        g = jnp.take(x, jnp.clip(c, 0, x.shape[1] - 1), axis=1)  # (tn, tm)
        return acc + v.astype(jnp.float32)[None, :] * g.astype(jnp.float32)

    acc = jax.lax.fori_loop(0, k, plane, jnp.zeros((tn, tm), jnp.float32))
    y_ref[...] = acc.astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tm", "tn", "layout", "interpret"))
def ell_spmm(cols: jax.Array, data: jax.Array, B: jax.Array,
             tm: int = 256, tn: int = 128, layout: str = "col",
             interpret: bool = True) -> jax.Array:
    """Y = A @ B for ELL A (cols[M, K], data[M, K]) and dense B (N, Kb)."""
    if layout not in ("row", "col"):
        raise ValueError(f"layout {layout!r} not in ('row', 'col')")
    m, k = data.shape
    n, kb = B.shape
    if k == 0:
        return jnp.zeros((m, kb), B.dtype)
    mp = ((m + tm - 1) // tm) * tm
    if mp != m:
        cols = jnp.pad(cols, ((0, mp - m), (0, 0)))
        data = jnp.pad(data, ((0, mp - m), (0, 0)))
    kp = ((kb + tn - 1) // tn) * tn
    if kp != kb:
        B = jnp.pad(B, ((0, 0), (0, kp - kb)))

    grid = (mp // tm, kp // tn)
    in_specs = [
        pl.BlockSpec((tm, k), lambda i, j: (i, 0)),
        pl.BlockSpec((tm, k), lambda i, j: (i, 0)),
        pl.BlockSpec((n, tn), lambda i, j: (0, j)),
    ]
    kernel = (functools.partial(_ell_spmm_kernel_col, tn=tn)
              if layout == "col" else _ell_spmm_kernel_row)
    y = pl.pallas_call(
        kernel,
        name="ell_spmm",
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((tm, tn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, kp), B.dtype,
                                       vma=vma(data, B)),
        interpret=interpret,
    )(cols, data, B)
    return y[:m, :kb]


@functools.partial(jax.jit, static_argnames=("tm", "tn", "layout", "interpret"))
def ell_spmm_t(cols: jax.Array, data: jax.Array, X: jax.Array,
               tm: int = 256, tn: int = 8, layout: str = "col",
               interpret: bool = True) -> jax.Array:
    """Y = X @ A^T for ELL A and activations X (T, N); returns (T, M)."""
    if layout not in ("row", "col"):
        raise ValueError(f"layout {layout!r} not in ('row', 'col')")
    m, k = data.shape
    t, n = X.shape
    if k == 0:
        return jnp.zeros((t, m), X.dtype)
    mp = ((m + tm - 1) // tm) * tm
    if mp != m:
        cols = jnp.pad(cols, ((0, mp - m), (0, 0)))
        data = jnp.pad(data, ((0, mp - m), (0, 0)))
    tp = ((t + tn - 1) // tn) * tn
    if tp != t:
        X = jnp.pad(X, ((0, tp - t), (0, 0)))

    grid = (mp // tm, tp // tn)
    in_specs = [
        pl.BlockSpec((tm, k), lambda i, j: (i, 0)),
        pl.BlockSpec((tm, k), lambda i, j: (i, 0)),
        pl.BlockSpec((tn, n), lambda i, j: (j, 0)),
    ]
    kernel = (functools.partial(_ell_spmm_t_kernel_col, tn=tn)
              if layout == "col" else _ell_spmm_t_kernel_row)
    y = pl.pallas_call(
        kernel,
        name="ell_spmm_t",
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((tn, tm), lambda i, j: (j, i)),
        out_shape=jax.ShapeDtypeStruct((tp, mp), X.dtype,
                                       vma=vma(data, X)),
        interpret=interpret,
    )(cols, data, X)
    return y[:t, :m]
