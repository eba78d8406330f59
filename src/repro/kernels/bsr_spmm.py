"""Pallas TPU kernel: block-CSR (BSR) SpMM — Y = A @ B.

The flagship TPU-native kernel (DESIGN.md §2): every stored (bs x bs) block
is one MXU matmul against a (bs, tn) tile of B. This is the format/kernel
pair that carries the paper's "switch to the format the hardware loves"
thesis onto the MXU, and the compute path for the block-sparse / MoE
integration in the model stack.

Blocking strategy (output-revisiting accumulation):
  * grid = (N/tn, nblk) with the B-column tile j OUTER and the stored-block
    index k INNER: for a fixed j, ``block_row[k]`` is non-decreasing, so all
    k belonging to one output tile (row, j) are *consecutive* grid steps —
    Pallas keeps the out tile resident in VMEM across them and only writes
    back on the row change (the TPU revisiting idiom; non-consecutive
    revisits would be read-modify-write-incorrect on real hardware);
  * ``indptr``/``block_row``/``block_col`` ride in SMEM via scalar prefetch
    and drive the BlockSpec index maps (data-dependent tiling);
  * the out tile is zero-initialised on the first block of each row.

Requirement: every block row must own >= 1 block (the ops wrapper verifies
and falls back to ref otherwise; conversion can pad empty rows).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.ops import vma


def _bsr_kernel(indptr_ref, brow_ref, bcol_ref, blocks_ref, b_ref, y_ref, acc_ref):
    k = pl.program_id(1)
    row = brow_ref[k]

    @pl.when(k == indptr_ref[row])  # first block of this output row
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    block = blocks_ref[0]  # (bs, bs)
    btile = b_ref[...]  # (bs, tn)
    acc_ref[...] += jnp.dot(block.astype(jnp.float32), btile.astype(jnp.float32),
                            preferred_element_type=jnp.float32)

    @pl.when(k == indptr_ref[row + 1] - 1)  # last block: single write-back
    def _():
        y_ref[...] = acc_ref[...].astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("m", "tn", "interpret"))
def bsr_spmm(indptr: jax.Array, brow: jax.Array, bcol: jax.Array,
             blocks: jax.Array, B: jax.Array, m: int,
             tn: int = 128, interpret: bool = True) -> jax.Array:
    """Y = A @ B.

    A is block-CSR: ``blocks[nblk, bs, bs]``, ``bcol[nblk]`` block columns,
    ``indptr[Mb+1]`` block-row pointers and ``brow[nblk]`` the (precomputed,
    non-decreasing) block row of every stored block. B is (N, K); K is padded
    to a multiple of ``tn`` by the wrapper. Every block row must be non-empty.
    """
    nblk, bs, _ = blocks.shape
    n, kb = B.shape
    kbp = ((kb + tn - 1) // tn) * tn
    if kbp != kb:
        B = jnp.pad(B, ((0, 0), (0, kbp - kb)))

    grid = (kbp // tn, nblk)  # j outer, k inner => consecutive accumulation
    y = pl.pallas_call(
        _bsr_kernel,
        name="bsr_spmm",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=grid,
            in_specs=[
                # one stored block per step
                pl.BlockSpec((1, bs, bs), lambda j, k, ptr, br, bc: (k, 0, 0)),
                # the B tile addressed by the block's column (data-dependent)
                pl.BlockSpec((bs, tn), lambda j, k, ptr, br, bc: (bc[k], j)),
            ],
            out_specs=pl.BlockSpec((bs, tn), lambda j, k, ptr, br, bc: (br[k], j)),
            scratch_shapes=[pltpu.VMEM((bs, tn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((m, kbp), B.dtype,
                                       vma=vma(blocks, B)),
        interpret=interpret,
    )(indptr, brow, bcol, blocks, B)
    return y[:, :kb]
