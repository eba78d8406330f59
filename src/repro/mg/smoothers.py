"""Vector-friendly smoothers: multicolored SymGS and weighted Jacobi.

HPCG's reference symmetric Gauss-Seidel sweeps rows in lexicographic
order — each update reads the previous one, which serialises the sweep and
is why the paper benchmarks with the preconditioner disabled (§IV-B). The
classic cure is a **grid coloring**: under the 2x2x2 (8-color) coloring of
a 3D grid, same-color points are at distance >= 2 along every axis, so the
27-point stencil never couples two points of one color. Gauss-Seidel in
*color order* then updates each color's rows simultaneously:

    for color c (ascending = forward, descending = backward):
        x[c] += (b[c] - (A x)[c]) / diag[c]

Each per-color partial ``(A x)[c]`` is one SpMV of the color's **row
block** — an ordinary (rows_c, n) sparse matrix stored in any of the
library's formats, so the sweep runs on the existing CSR/ELL Pallas
kernels through ``repro.core.ops.spmv`` and the measured ``backend="auto"``
routing. The sweep is *exactly* sequential Gauss-Seidel over the
color-permuted row ordering (the permutation is applied implicitly: blocks
carry their global row ids and updates scatter back through them).

Build path mirrors the distributed multiformat pipeline: the 8 row blocks
are extracted as ONE stacked ``(ncolors, cap)`` COO batch (a single device
scatter), featurised in one ``FormatPolicy.select_batch`` pass when a
policy is given, and converted per color through the plan/execute numeric
phase — so every color block can live in its own format.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import ops as _ops
from repro.core.convert import (_planned_pull, convert_execute, plan_switch,
                                to_coo)
from repro.core.distributed import group_ranks
from repro.core.formats import COO, Format

NCOLORS = 8


def color_grid(nx: int, ny: int, nz: int) -> np.ndarray:
    """2x2x2 parity coloring of the x-fastest-ordered grid: color =
    (x%2) + 2*(y%2) + 4*(z%2). Proper for any stencil of reach <= 1 per
    axis (the 27-point stencil): no two same-color points are coupled."""
    idx = np.arange(nx * ny * nz)
    x, y, z = idx % nx, (idx // nx) % ny, idx // (nx * ny)
    return ((x % 2) + 2 * (y % 2) + 4 * (z % 2)).astype(np.int32)


def check_coloring(C: COO, colors: np.ndarray) -> None:
    """Raise if ``colors`` is not a proper coloring of ``C``'s live
    off-diagonal pattern (same-color coupling would silently turn the
    parallel sweep into chaotic relaxation)."""
    r = np.asarray(C.row)
    c = np.asarray(C.col)
    live = (np.asarray(C.data) != 0) & (r != c)
    bad = colors[r[live]] == colors[c[live]]
    if bad.any():
        i = int(np.argmax(bad))
        rr, cc = r[live][i], c[live][i]
        raise ValueError(
            f"improper coloring: rows {rr} and {cc} share color "
            f"{int(colors[rr])} but are coupled; a colored sweep would not "
            f"match sequential Gauss-Seidel")


@dataclasses.dataclass(frozen=True)
class ColoredSystem:
    """Color-permuted view of a square system for parallel Gauss-Seidel.

    ``blocks[c]`` is the (rmax, n) row block of color ``c`` (any format;
    inert padding rows when colors are unevenly sized); ``rows[c]`` holds
    the blocks' global row ids, padded with ``n`` so padded lanes clip on
    gather and drop on scatter; ``diag`` is the full diagonal of A.
    """

    blocks: Tuple
    rows: Tuple[jax.Array, ...]
    diag: jax.Array
    shape: Tuple[int, int]

    @property
    def ncolors(self) -> int:
        return len(self.blocks)

    @property
    def formats(self) -> Tuple[Format, ...]:
        return tuple(Format(b.format) for b in self.blocks)


def color_ranks(colors: np.ndarray) -> np.ndarray:
    """(n,) rank of every row within its color (host; shared metadata)."""
    order = np.argsort(colors, kind="stable")
    rank = np.empty_like(order)
    rank[order] = np.arange(len(order)) - np.concatenate(
        [[0], np.cumsum(np.bincount(colors, minlength=NCOLORS))])[colors[order]]
    return rank.astype(np.int32)


def _split_colors_device(row, col, data, colors_d, rank_d, cap: int):
    """Pure device core of the color split: one scatter drops the entries
    of a (cap0,) COO part into ``(NCOLORS, cap)`` planes. Entry (i, j, v)
    lands in plane ``colors[i]`` at row ``rank_of_i_within_color``, in the
    slot given by its stable rank among that color's entries; dead entries
    and per-color overflow land in a dropped guard slot. jit/vmap-able —
    the distributed builder vmaps it over the shard axis. The same scatter
    as ``distributed.partition_execute``, with the color id in place of
    the shard id.
    """
    key = jnp.where(data != 0, colors_d[row], NCOLORS)
    erank = group_ranks(key, NCOLORS)
    ok = (key < NCOLORS) & (erank < cap)
    dest = jnp.where(ok, key * cap + jnp.minimum(erank, cap - 1), NCOLORS * cap)
    lrow = rank_d[row]
    out = []
    for xs in (lrow, col, data):
        buf = jnp.zeros((NCOLORS * cap + 1,), xs.dtype).at[dest].set(
            jnp.where(ok, xs, jnp.zeros((), xs.dtype)))
        out.append(buf[:NCOLORS * cap].reshape(NCOLORS, cap))
    return out[0], out[1], out[2]


def split_colors_stacked(C: COO, colors: np.ndarray,
                         rmax: int, cap: int) -> COO:
    """One device scatter: (cap0,) COO -> stacked (ncolors, cap) row blocks
    (``cap`` must come from a prior count — see :func:`build_colored`)."""
    colors_d = jnp.asarray(colors)
    rank_d = jnp.asarray(color_ranks(colors))
    r, c, v = _split_colors_device(C.row, C.col, C.data, colors_d, rank_d, cap)
    return COO(r, c, v, (rmax, C.shape[1]), cap)


def color_rows_padded(colors: np.ndarray, n: int, rmax: int) -> np.ndarray:
    """(ncolors, rmax) global row ids per color, padded with ``n``."""
    rows = np.full((NCOLORS, rmax), n, np.int32)
    for c in range(NCOLORS):
        ids = np.nonzero(colors == c)[0]
        rows[c, :len(ids)] = ids
    return rows


def build_colored(A, colors: Optional[np.ndarray] = None,
                  dims: Optional[Tuple[int, int, int]] = None,
                  fmt: Format = Format.CSR, policy=None,
                  check: bool = False) -> ColoredSystem:
    """Build the per-color row blocks of a square operator ``A``.

    ``colors`` (or ``dims``, from which the 2x2x2 grid coloring is
    derived) assigns every row a color. With a ``FormatPolicy`` each color
    block picks its own format from ONE batched ``select_batch`` pass over
    the stacked blocks; otherwise all blocks use ``fmt``. ``check=True``
    verifies the coloring is proper (host scan).
    """
    C = to_coo(A.concrete if hasattr(A, "concrete") else A)
    n = C.shape[0]
    if colors is None:
        if dims is None:
            raise ValueError("build_colored needs colors= or dims=")
        colors = color_grid(*dims)
    colors = np.asarray(colors, np.int32)
    if len(colors) != n:
        raise ValueError(f"{len(colors)} colors for {n} rows")
    if check:
        check_coloring(C, colors)

    counts = np.bincount(colors, minlength=NCOLORS)
    rmax = max(1, int(counts.max()))
    # per-color entry capacity: one device pass + one planned pull
    live = C.data != 0
    ecnt = jnp.bincount(jnp.where(live, jnp.asarray(colors)[C.row], NCOLORS),
                        length=NCOLORS + 1)[:NCOLORS]
    cap = max(1, int(_planned_pull(jnp.max(ecnt))))

    stacked = split_colors_stacked(C, colors, rmax, cap)
    if policy is not None:
        ids = policy.select_batch(stacked)
        fmts = [policy.candidates[i] for i in ids]
    else:
        fmts = [Format(fmt)] * NCOLORS
    blocks = []
    for c in range(NCOLORS):
        blk = jax.tree.map(lambda a, c=c: a[c], stacked)
        blk = COO(blk.row, blk.col, blk.data, (rmax, n), cap)
        blocks.append(convert_execute(blk, plan_switch(blk, fmts[c])))
    rows_np = color_rows_padded(colors, n, rmax)
    rows = tuple(jnp.asarray(rows_np[c]) for c in range(NCOLORS))
    diag = _ops.extract_diagonal(C)
    return ColoredSystem(tuple(blocks), rows, diag, (n, n))


# ---------------------------------------------------------------------------
# Sweeps (jit-able; the color loop unrolls at trace time)
# ---------------------------------------------------------------------------


def gs_sweep(cs: ColoredSystem, b: jax.Array, x: jax.Array,
             forward: bool = True, backend: str = "auto",
             cfg=None) -> jax.Array:
    """One Gauss-Seidel sweep in color order (exact GS over the color
    permutation). Each color is one row-block SpMV + a masked scatter."""
    n = cs.shape[0]
    order = range(cs.ncolors) if forward else range(cs.ncolors - 1, -1, -1)
    for c in order:
        y = _ops.spmv(cs.blocks[c], x, backend=backend, cfg=cfg)
        rows = cs.rows[c]
        bc = jnp.take(b, rows, mode="clip")
        dc = jnp.take(cs.diag, rows, mode="clip")
        delta = (bc - y) / jnp.where(dc != 0, dc, 1.0)
        x = x.at[rows].add(delta)  # padded lanes (id n) drop
    return x


def symgs(cs: ColoredSystem, b: jax.Array, x: Optional[jax.Array] = None,
          sweeps: int = 1, backend: str = "auto", cfg=None) -> jax.Array:
    """Symmetric Gauss-Seidel: forward then backward color sweep,
    ``sweeps`` times. Self-adjoint in the A-inner product — the V-cycle
    smoother that keeps ``apply_M`` a symmetric preconditioner."""
    if x is None:
        x = jnp.zeros_like(b)
    for _ in range(int(sweeps)):
        x = gs_sweep(cs, b, x, forward=True, backend=backend, cfg=cfg)
        x = gs_sweep(cs, b, x, forward=False, backend=backend, cfg=cfg)
    return x


def jacobi(diag: jax.Array, apply_A, b: jax.Array,
           x: Optional[jax.Array] = None, sweeps: int = 1,
           omega: float = 2.0 / 3.0) -> jax.Array:
    """Weighted-Jacobi fallback smoother (for operators without a proper
    coloring): x += omega * (b - A x) / diag."""
    minv = jnp.where(jnp.abs(diag) > 1e-30, omega / diag, 0.0)
    if x is None:
        x = minv * b
        start = 1
    else:
        start = 0
    for _ in range(start, int(sweeps)):
        x = x + minv * (b - apply_A(x))
    return x


def symgs_reference_np(row, col, val, colors: np.ndarray, b: np.ndarray,
                       x: np.ndarray, sweeps: int = 1) -> np.ndarray:
    """Sequential NumPy SymGS oracle over the color-permuted row ordering.

    Processes rows one at a time in (color, row) order — forward then
    reverse — always reading the latest x. With a proper coloring the
    parallel :func:`symgs` matches this exactly (up to float summation
    order).
    """
    row = np.asarray(row)
    col = np.asarray(col)
    val = np.asarray(val, np.float64)
    x = np.asarray(x, np.float64).copy()
    b = np.asarray(b, np.float64)
    n = len(x)
    diag = np.zeros(n)
    np.add.at(diag, row[row == col], val[row == col])
    perm = np.lexsort((np.arange(n), colors))  # rows in (color, id) order
    by_row = [[] for _ in range(n)]
    for r, c, v in zip(row, col, val):
        if v != 0:
            by_row[r].append((c, v))
    for _ in range(sweeps):
        for ordering in (perm, perm[::-1]):
            for r in ordering:
                s = sum(v * x[c] for c, v in by_row[r])
                x[r] += (b[r] - s) / diag[r]
    return x
