"""Least HBM bytes per chip of a solve sharded over chips, and the
device time of its collectives.

A configuration with ``shards`` divides its grid's rows over that many
chips, each chip one slab. The least bytes follow ``bench/work.py``
(every stored float32 value once per SpMV, no index bytes, no vectors):
a chip's share of one SpMV is its rows' values, ``stencil_nnz(grid) /
shards`` x 4 bytes. The boundary rows' padding, a second table per
chip, or a halo held twice are the implementation's and count nowhere,
so a share of the peak built on these bytes cannot pass 100% whatever
implements the SpMV.
"""
from __future__ import annotations

from bench import tracing, work

# opcodes of the collectives a sharded solve issues: the halo exchange
# (collective-permute, or all-gather for a gathered halo) and the
# all-reduces of the dot products, each also as its -start/-done pair
COLLECTIVES = ("collective-permute", "all-reduce", "all-gather")


def shards(config: dict) -> int:
    return int(config["shards"])


def chip_spmv_bytes(config: dict) -> float:
    """Least bytes one chip reads in one SpMV: its rows' values."""
    return work.spmv_bytes(work.stencil_nnz(*config["grid"])) / shards(config)


def spmv_calls(window) -> int:
    """SpMVs of a window of CG solves: one per iteration plus the initial
    residual's."""
    return sum(k + 1 for k in window.iters)


def window_hbm_share(ctx):
    """Least bytes of the window's solves over what ``shards`` chips' HBM
    moves in the window at its peak (%), or None without a peak."""
    share = work.window_hbm_share(ctx)
    return None if share is None else share / shards(ctx.config)


def is_collective(text: str) -> bool:
    """Whether a device op's HLO text is a collective, by its opcode."""
    if " = " not in text:
        return False
    return tracing.op_name(text).rsplit(" ", 1)[-1].startswith(COLLECTIVES)
