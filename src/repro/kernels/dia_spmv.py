"""Pallas TPU kernel: DIA-format SpMV.

The paper's winning format for stencil matrices, re-derived for TPU
(DESIGN.md §2): every diagonal contributes one *contiguous, shifted*
multiply-add — pure VPU work, zero gathers. Vectors live in the lane-dense
``(rows / 128, 128)`` layout, so a row tile of ``tm`` rows is a ``(T, 128)``
block with ``T = tm / 128``.

Blocking strategy:
  * grid over row tiles; the lane-tiled diagonal table
    ``data[ndiag, M / 128, 128]`` streams through VMEM one
    ``(ndiag, T, 128)`` block per grid step;
  * each (tile, diagonal) reads the 8-row-aligned ``(T + 16, 128)`` window
    that covers ``x[row0 + off : row0 + off + tm]`` from one of two homes,
    chosen by the caller from the padded ``x``'s size:
      - resident: the whole padded ``x`` is one VMEM operand, copied in
        once per call, and the window is an aligned dynamic slice of it;
      - streamed: ``x`` stays in HBM and the window is a DMA into a
        double-buffered VMEM slot (the next diagonal's window is in flight
        while this one is summed), so VMEM holds two windows, never the
        whole vector, whatever the reach;
  * the window is shifted into place in registers: a sublane rotate by
    the row remainder, a lane rotate by the lane remainder, and one select
    between the row and the row below it. No unaligned dynamic load. Both
    homes share this step and the order of accumulation, so they give
    bitwise the same ``y``;
  * ``offsets`` ride in SMEM via scalar prefetch and place the windows.
    A diagonal that misses the tile entirely is skipped (its window is
    clamped in bounds and its contribution dropped); partial windows read
    the zero padding of ``x`` outside ``[0, n)``, so out-of-matrix entries
    contribute nothing.

VMEM per step: 2 * ndiag * tm * itemsize (data, double-buffered) +
2 * tm * 4 (y) + either 4 * x_pad_len(n, tm) (resident ``x``) or
2 * (tm + 2048) * 4 (streamed windows).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.formats import tile_dia_table
from repro.core.ops import vma

LANES = 128
_SUB = 8  # f32 sublanes per vreg: window starts are aligned to this many rows


def row_unit(dtype) -> int:
    """Row-tile granule: ``tm`` must be a multiple of this — whole
    ``(sublane-tile, 128)`` blocks of the diagonal table's dtype."""
    return LANES * _SUB * max(1, 4 // jnp.dtype(dtype).itemsize)


def x_pad_len(n: int, tm: int) -> int:
    """Length of ``x`` in the kernel's padded coordinates: ``tm`` zeros on
    the left (a live window starts above ``-tm``), then ``x``, then zeros
    so the last live window's ``(tm / 128 + 16)``-row read ends in bounds;
    a whole number of 8-row blocks."""
    block = _SUB * LANES
    return -(-(tm + n + tm + 2 * block) // block) * block


def _dia_kernel(offsets_ref, data_ref, x_ref, y_ref, *stream,
                tm: int, n: int, lpad: int, smax: int):
    """One row tile. ``x_ref`` is the whole padded ``x`` in VMEM, or, with
    ``stream`` = (window buffers, DMA semaphores), in HBM; the two differ
    only in where each diagonal's window comes from."""
    t = tm // LANES
    w = t + 2 * _SUB
    ndiag = data_ref.shape[0]
    row0 = pl.program_id(0) * tm

    def start_of(d):
        # element start of the diagonal's window in padded coordinates,
        # clamped so the read stays in bounds (a clamped window is dead)
        return jnp.clip(row0 + offsets_ref[d] + lpad, 0, smax)

    def aligned_row(d):
        return pl.multiple_of(start_of(d) // (LANES * _SUB) * _SUB, _SUB)

    if stream:
        buf, sem = stream

        def window_copy(d, slot):
            return pltpu.make_async_copy(x_ref.at[pl.ds(aligned_row(d), w)],
                                         buf.at[slot], sem.at[slot])

        window_copy(0, 0).start()

        def window(d):
            slot = d % 2

            @pl.when(d + 1 < ndiag)
            def _():
                window_copy(d + 1, 1 - slot).start()

            window_copy(d, slot).wait()
            return buf[slot]
    else:
        def window(d):
            return x_ref[pl.ds(aligned_row(d), w), :]

    lane = jax.lax.broadcasted_iota(jnp.int32, (t, LANES), 1)

    def body(d, acc):
        xw = window(d)                                  # (w, 128)
        s = start_of(d)
        sub = s // LANES % _SUB           # row remainder inside the window
        b = s % LANES                     # lane remainder
        xw = pltpu.roll(xw, (w - sub) % w, 0)           # row sub -> row 0
        xw = pltpu.roll(xw, (LANES - b) % LANES, 1)     # lane b -> lane 0
        nxt = pltpu.roll(xw, w - 1, 0)                  # the row below
        win = jnp.where(lane < LANES - b, xw[:t], nxt[:t])
        off = offsets_ref[d]
        live = (row0 + off < n) & (row0 + off + tm > 0)
        contrib = data_ref[d].astype(jnp.float32) * win
        return jnp.where(live, acc + contrib, acc)

    acc = jax.lax.fori_loop(0, ndiag, body,
                            jnp.zeros((t, LANES), jnp.float32))
    y_ref[...] = acc


@functools.partial(jax.jit,
                   static_argnames=("n", "m", "tm", "x_resident", "interpret"))
def dia_spmv(offsets: jax.Array, data: jax.Array, x: jax.Array, n: int,
             m: Optional[int] = None, tm: int = 8192,
             x_resident: bool = False, interpret: bool = False) -> jax.Array:
    """y = A @ x for DIA A given as (offsets[ndiag], data[ndiag, M]), or
    with ``data`` lane-tiled, ``[ndiag, ceil(M/128), 128]``.

    ``x`` has length ``n`` (rectangular matrices supported). ``data`` rows
    follow the cusp convention data[d, i] = A[i, i + offsets[d]]; entries
    whose column falls outside ``[0, n)`` contribute nothing. A lane-tiled
    table is the kernel's own operand and goes in as it is; a 2-D one is
    padded to whole lanes and reshaped to it first. ``m`` is M (default:
    the table's rows, padding included). ``tm`` must be a multiple of
    :func:`row_unit` of the data dtype. ``x_resident`` keeps the whole
    padded ``x`` (``4 * x_pad_len(n, tm)`` bytes) in VMEM; otherwise each
    window is a DMA from HBM.
    """
    unit = row_unit(data.dtype)
    if tm <= 0 or tm % unit:
        raise ValueError(f"DIA kernel row tile tm={tm} is not a positive "
                         f"multiple of {unit} rows for "
                         f"{jnp.dtype(data.dtype).name} data")
    ndiag = data.shape[0]
    if data.ndim == 2:
        m = data.shape[1] if m is None else m
        data = tile_dia_table(data)
    m128 = data.shape[1] * LANES
    m = m128 if m is None else m
    t = tm // LANES
    lpad = tm
    total = x_pad_len(n, tm)
    x_pad = jnp.pad(x.astype(jnp.float32), (lpad, total - lpad - n))
    smax = total - (t + 2 * _SUB) * LANES
    kernel = functools.partial(_dia_kernel, tm=tm, n=n, lpad=lpad, smax=smax)
    if x_resident:
        x_spec, scratch = pl.BlockSpec(memory_space=pltpu.VMEM), []
    else:
        x_spec = pl.BlockSpec(memory_space=pl.ANY)
        scratch = [pltpu.VMEM((2, t + 2 * _SUB, LANES), jnp.float32),
                   pltpu.SemaphoreType.DMA((2,))]
    y = pl.pallas_call(
        kernel,
        name="dia_spmv",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(pl.cdiv(m128 // LANES, t),),
            in_specs=[
                pl.BlockSpec((ndiag, t, LANES), lambda i, *_: (0, i, 0)),
                x_spec,
            ],
            out_specs=pl.BlockSpec((t, LANES), lambda i, *_: (i, 0)),
            scratch_shapes=scratch,
        ),
        out_shape=jax.ShapeDtypeStruct((m128 // LANES, LANES), jnp.float32,
                                       vma=vma(offsets, data, x)),
        interpret=interpret,
    )(offsets.astype(jnp.int32), data, x_pad.reshape(total // LANES, LANES))
    return y.reshape(m128)[:m].astype(x.dtype)
