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
library's formats. The sweep is *exactly* sequential Gauss-Seidel over the
color-permuted row ordering.

Two layouts of the blocks' columns and of the vectors:

- **color-major**, wherever the grid's dims are all even. Every color then
  holds m = n/8 points on an (nx/2, ny/2, nz/2) sub-grid, and the points
  are numbered color first, then x-fastest within the color. A stencil
  neighbour (dx, dy, dz) of a point of color c has color
  c' = c xor parity(dx, dy, dz) and sits at a constant shift within c''s
  sub-grid, so each row block is exactly 27 diagonals, at offsets
  ``c' * m + shift`` (:func:`color_block_offsets`). A sweep moves b and x
  into color-major order once (a reshape and transpose of the grid, no
  gather), updates color c's contiguous slice ``[c*m, (c+1)*m)`` in place,
  and moves x back once. A DIA block there is summed as 27 static shifted
  slices: no index array, no gather, no scatter.
- **natural**, the fallback for a grid with an odd dim (colors of unequal
  size) or a coloring given as ``colors=``: blocks carry natural column
  ids and their global row ids, and each color's update gathers b and the
  diagonal and scatters into x through them.

Build path mirrors the distributed multiformat pipeline: the 8 row blocks
are extracted as ONE stacked ``(ncolors, cap)`` COO batch (a single device
scatter), featurised in one ``FormatPolicy.select_batch`` pass when a
policy is given, and converted per color through the plan/execute numeric
phase — so every color block can live in its own format.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import ops as _ops
from repro.core.convert import (_planned_pull, convert_execute, plan_switch,
                                plan_switch_batch, to_coo)
from repro.core.distributed import group_ranks
from repro.core.formats import COO, DIA, Format
from repro.obs import metrics as _metrics

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

    ``blocks[c]`` is the (rmax, n) row block of color ``c`` (any format).
    With ``dims`` set the layout is color-major (module docstring): the
    blocks' columns, and ``diag``, are in color-major order, every color
    has ``rmax = n/8`` rows and ``rows`` is None. With ``dims`` None the
    layout is natural: ``rows[c]`` holds the block's global row ids, padded
    with ``n`` so padded lanes clip on gather and drop on scatter (inert
    padding rows when colors are unevenly sized), and ``diag`` is the
    diagonal of A in natural order.
    """

    blocks: Tuple
    rows: Optional[Tuple[jax.Array, ...]]
    diag: jax.Array
    shape: Tuple[int, int]
    dims: Optional[Tuple[int, int, int]] = None

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


# ---------------------------------------------------------------------------
# The color-major layout
# ---------------------------------------------------------------------------


def color_major(dims) -> bool:
    """Whether a grid (or shard slab) of ``dims`` = (nx, ny, nz) takes the
    color-major layout: every dim even, so all 8 colors hold n/8 points."""
    return dims is not None and all(int(d) % 2 == 0 for d in dims)


def to_color_major(v: jax.Array, dims) -> jax.Array:
    """Natural (x-fastest) grid vector -> color-major order: reshapes and
    transposes of the grid, no gather. The x parity is split off while x
    is a major dimension, between two 2-D transposes: split off as the
    minor dimension, in one 6-D transpose, every pair of values would take
    a whole row of vector lanes on a TPU (310 MB for a 104^3 grid)."""
    nx, ny, nz = dims
    hx, hy, hz = nx // 2, ny // 2, nz // 2
    t = v.reshape(nz * ny, nx).T                       # (x, zy)
    t = t.reshape(hx, 2, nz * ny).transpose(1, 2, 0)   # (px, zy, qx)
    t = t.reshape(2, hz, 2, hy, 2, hx).transpose(2, 4, 0, 1, 3, 5)
    return t.reshape(v.shape)


def from_color_major(v: jax.Array, dims) -> jax.Array:
    """Inverse of :func:`to_color_major`, by the same steps backwards."""
    nx, ny, nz = dims
    hx, hy, hz = nx // 2, ny // 2, nz // 2
    t = v.reshape(2, 2, 2, hz, hy, hx).transpose(2, 3, 0, 4, 1, 5)
    t = t.reshape(2, nz * ny, hx).transpose(2, 0, 1)   # (qx, px, zy)
    return t.reshape(nx, nz * ny).T.reshape(v.shape)


def color_major_index(dims) -> np.ndarray:
    """(n,) color-major position of every natural grid point (host): its
    color times n/8 plus its rank within the color."""
    colors = color_grid(*dims)
    return (colors * (len(colors) // NCOLORS)
            + color_ranks(colors)).astype(np.int32)


@functools.lru_cache(maxsize=None)
def color_block_offsets(dims: Tuple[int, int, int], c: int) -> Tuple[int, ...]:
    """Ascending DIA offsets of color ``c``'s row block in color-major
    columns: one per stencil neighbour (dx, dy, dz) that lies in the grid
    for some point of the color, at ``c' * m + shift``. 27 where every dim
    is at least 4; a dim of 2 leaves out the shifts that would leave it."""
    h = tuple(int(d) // 2 for d in dims)
    m = h[0] * h[1] * h[2]
    stride = (1, h[0], h[0] * h[1])
    axes = []
    for a in range(3):
        p = (c >> a) & 1
        opts = []
        for d in (-1, 0, 1):
            shift, parity = divmod(p + d, 2)
            if shift == 0 or h[a] > 1:
                opts.append((parity << a, shift * stride[a]))
        axes.append(opts)
    return tuple(sorted({(cx + cy + cz) * m + sx + sy + sz
                         for cx, sx in axes[0] for cy, sy in axes[1]
                         for cz, sz in axes[2]}))


def count_layout(dims) -> str:
    """The layout a level built on ``dims`` takes, counted in the
    always-on ``mg.smoother.color_major`` / ``mg.smoother.gather``
    counters (once per level built)."""
    layout = "color_major" if color_major(dims) else "gather"
    _metrics.inc(f"mg.smoother.{layout}")
    return layout


def plan_color_block(C: COO, fmt: Format, dims, c: int,
                     batch: bool = False):
    """The conversion plan of color ``c``'s block ``C`` (a stacked batch of
    shard blocks when ``batch``). A DIA block in the color-major layout
    gets the offsets :func:`color_block_offsets` gives, which the sweep
    takes as static; an entry off them would be dropped, so the live
    diagonals are checked to lie among them."""
    plan = (plan_switch_batch if batch else plan_switch)(C, fmt)
    if Format(fmt) == Format.DIA and color_major(dims):
        offs = color_block_offsets(tuple(dims), c)
        if not set(plan.dia_offsets) <= set(offs):
            raise ValueError(
                f"color {c} block has diagonals off the 27-point stencil's "
                f"color-major offsets: "
                f"{sorted(set(plan.dia_offsets) - set(offs))[:4]}")
        plan = dataclasses.replace(plan, dia_offsets=offs)
    return plan


def _split_colors_device(row, col, data, colors_d, rank_d, cap: int,
                         colpos_d=None):
    """Pure device core of the color split: one scatter drops the entries
    of a (cap0,) COO part into ``(NCOLORS, cap)`` planes. Entry (i, j, v)
    lands in plane ``colors[i]`` at row ``rank_of_i_within_color``, in the
    slot given by its stable rank among that color's entries; dead entries
    and per-color overflow land in a dropped guard slot. ``colpos_d``
    renumbers the columns (the color-major layout). jit/vmap-able —
    the distributed builder vmaps it over the shard axis. The same scatter
    as ``distributed.partition_execute``, with the color id in place of
    the shard id.
    """
    key = jnp.where(data != 0, colors_d[row], NCOLORS)
    erank = group_ranks(key, NCOLORS)
    ok = (key < NCOLORS) & (erank < cap)
    dest = jnp.where(ok, key * cap + jnp.minimum(erank, cap - 1), NCOLORS * cap)
    lrow = rank_d[row]
    if colpos_d is not None:
        col = colpos_d[col]
    out = []
    for xs in (lrow, col, data):
        buf = jnp.zeros((NCOLORS * cap + 1,), xs.dtype).at[dest].set(
            jnp.where(ok, xs, jnp.zeros((), xs.dtype)))
        out.append(buf[:NCOLORS * cap].reshape(NCOLORS, cap))
    return out[0], out[1], out[2]


def split_colors_stacked(C: COO, colors: np.ndarray,
                         rmax: int, cap: int, colpos=None) -> COO:
    """One device scatter: (cap0,) COO -> stacked (ncolors, cap) row blocks
    (``cap`` must come from a prior count — see :func:`build_colored`)."""
    colors_d = jnp.asarray(colors)
    rank_d = jnp.asarray(color_ranks(colors))
    colpos_d = None if colpos is None else jnp.asarray(colpos)
    r, c, v = _split_colors_device(C.row, C.col, C.data, colors_d, rank_d,
                                   cap, colpos_d)
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
    derived) assigns every row a color. Given ``dims`` with every dim
    even, the blocks take the color-major layout; otherwise the natural
    one. With a ``FormatPolicy`` each color block picks its own format
    from ONE batched ``select_batch`` pass over the stacked blocks;
    otherwise all blocks use ``fmt``. ``check=True`` verifies the coloring
    is proper (host scan).
    """
    C = to_coo(A.concrete if hasattr(A, "concrete") else A)
    n = C.shape[0]
    cm_dims = None
    if colors is None:
        if dims is None:
            raise ValueError("build_colored needs colors= or dims=")
        colors = color_grid(*dims)
        if count_layout(dims) == "color_major":
            cm_dims = tuple(int(d) for d in dims)
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

    colpos = None if cm_dims is None else color_major_index(cm_dims)
    stacked = split_colors_stacked(C, colors, rmax, cap, colpos)
    if policy is not None:
        ids = policy.select_batch(stacked)
        fmts = [policy.candidates[i] for i in ids]
    else:
        fmts = [Format(fmt)] * NCOLORS
    blocks = []
    for c in range(NCOLORS):
        blk = jax.tree.map(lambda a, c=c: a[c], stacked)
        blk = COO(blk.row, blk.col, blk.data, (rmax, n), cap)
        blocks.append(convert_execute(
            blk, plan_color_block(blk, fmts[c], cm_dims, c)))
    diag = _ops.extract_diagonal(C)
    if cm_dims is not None:
        return ColoredSystem(tuple(blocks), None,
                             to_color_major(diag, cm_dims), (n, n), cm_dims)
    rows_np = color_rows_padded(colors, n, rmax)
    rows = tuple(jnp.asarray(rows_np[c]) for c in range(NCOLORS))
    return ColoredSystem(tuple(blocks), rows, diag, (n, n))


# ---------------------------------------------------------------------------
# Sweeps (jit-able; the color loop unrolls at trace time)
# ---------------------------------------------------------------------------


def _dia_static_matvec(data: jax.Array, offsets: Tuple[int, ...]):
    """``x -> A x`` for a DIA table whose offsets are known at trace time:
    one static shifted slice of zero-padded ``x`` per diagonal. Written in
    ``lax`` ops, and with the table's rows split once, since a V-cycle
    unrolls it for every color, direction, sweep and level."""
    m = data.shape[-1]
    rows = [jax.lax.index_in_dim(data, d, keepdims=False)
            for d in range(len(offsets))]

    def matvec(x):
        lo, hi = max(0, -offsets[0]), max(0, offsets[-1] + m - x.shape[0])
        xp = jax.lax.pad(x.astype(data.dtype), jnp.zeros((), data.dtype),
                         [(lo, hi, 0)])
        y = None
        for row, off in zip(rows, offsets):
            t = jax.lax.mul(row, jax.lax.slice_in_dim(xp, off + lo,
                                                      off + lo + m))
            y = t if y is None else jax.lax.add(y, t)
        return y

    return matvec


def _block_matvec(blk, dims, c: int, backend: str, cfg):
    """``x -> blk x`` for color ``c``'s block. A DIA block of the
    color-major layout on the reference path takes its offsets as static
    from ``dims``; every other block goes through ``repro.core.ops.spmv``."""
    if isinstance(blk, DIA) and dims is not None:
        route, auto_cfg = ((backend, None) if backend != "auto"
                           else _ops.kernel_route(blk))
        if route == "ref":
            return _dia_static_matvec(blk.data, color_block_offsets(dims, c))
        backend, cfg = route, cfg if cfg is not None else auto_cfg
    return lambda x: _ops.spmv(blk, x, backend=backend, cfg=cfg)


def _color_order(forward: bool):
    return range(NCOLORS) if forward else range(NCOLORS - 1, -1, -1)


def _sweep_natural(blocks, rows, diag, b, x, forward: bool, backend, cfg):
    for c in _color_order(forward):
        y = _ops.spmv(blocks[c], x, backend=backend, cfg=cfg)
        bc = jnp.take(b, rows[c], mode="clip")
        dc = jnp.take(diag, rows[c], mode="clip")
        delta = (bc - y) / jnp.where(dc != 0, dc, 1.0)
        x = x.at[rows[c]].add(delta)  # padded lanes (id n) drop
    return x


def _color_slices(v: jax.Array):
    m = v.shape[0] // NCOLORS
    return [jax.lax.slice_in_dim(v, c * m, (c + 1) * m)
            for c in range(NCOLORS)]


def _sweep_color_major(matvecs, dslices, bslices, x, forward: bool):
    """One color-order sweep on color-major ``x``: color c's rows are the
    static slice ``[c*m, (c+1)*m)``; ``dslices``/``bslices`` are the
    diagonal's and the right-hand side's slices."""
    m = x.shape[0] // NCOLORS
    for c in _color_order(forward):
        y = matvecs[c](x)
        xc = jax.lax.slice_in_dim(x, c * m, (c + 1) * m)
        xc = xc + (bslices[c] - y) / dslices[c]
        x = jax.lax.dynamic_update_slice_in_dim(x, xc, c * m, 0)
    return x


def _color_major_sweeps(blocks, diag, rhs, x, sweeps: int, dims, backend,
                        cfg, directions=(True, False)):
    """The color-major path of :func:`symgs_sweeps` (``directions`` per
    sweep): ``x`` moves to color-major order once and back once, a
    right-hand side once per distinct array."""
    matvecs = [_block_matvec(blk, dims, c, backend, cfg)
               for c, blk in enumerate(blocks)]
    dslices = _color_slices(jnp.where(diag != 0, diag, 1.0))
    x_nat, x = x, to_color_major(x, dims)
    b_nat = bslices = None
    for s in range(int(sweeps)):
        b = rhs(s, lambda: x_nat if s == 0 else from_color_major(x, dims))
        if b is not b_nat:
            b_nat, bslices = b, _color_slices(to_color_major(b, dims))
        for forward in directions:
            x = _sweep_color_major(matvecs, dslices, bslices, x, forward)
    return from_color_major(x, dims)


def gs_sweep(cs: ColoredSystem, b: jax.Array, x: jax.Array,
             forward: bool = True, backend: str = "auto",
             cfg=None) -> jax.Array:
    """One Gauss-Seidel sweep in color order (exact GS over the color
    permutation): per color one row-block SpMV and the update of the
    color's rows. ``b`` and ``x`` are in natural order."""
    if cs.dims is None:
        return _sweep_natural(cs.blocks, cs.rows, cs.diag, b, x, forward,
                              backend, cfg)
    return _color_major_sweeps(cs.blocks, cs.diag, lambda s, x_of: b, x, 1,
                               cs.dims, backend, cfg, directions=(forward,))


def symgs_sweeps(blocks, rows, diag, rhs: Callable, x: jax.Array,
                 sweeps: int, dims=None, backend: str = "auto",
                 cfg=None) -> jax.Array:
    """``sweeps`` symmetric (forward then backward) color sweeps over the
    blocks of one layout: color-major when ``dims`` is given, else natural
    with ``rows``. ``rhs(s, x_of)`` returns sweep ``s``'s right-hand side
    in natural order; ``x_of()`` gives the current iterate in natural order
    where the right-hand side depends on it (the distributed smoother's
    frozen halo). ``x`` comes and goes in natural order."""
    if dims is not None:
        return _color_major_sweeps(blocks, diag, rhs, x, sweeps, dims,
                                   backend, cfg)
    for s in range(int(sweeps)):
        b = rhs(s, lambda: x)
        for forward in (True, False):
            x = _sweep_natural(blocks, rows, diag, b, x, forward, backend,
                               cfg)
    return x


def symgs(cs: ColoredSystem, b: jax.Array, x: Optional[jax.Array] = None,
          sweeps: int = 1, backend: str = "auto", cfg=None) -> jax.Array:
    """Symmetric Gauss-Seidel: forward then backward color sweep,
    ``sweeps`` times. Self-adjoint in the A-inner product — the V-cycle
    smoother that keeps ``apply_M`` a symmetric preconditioner."""
    if x is None:
        x = jnp.zeros_like(b)
    return symgs_sweeps(cs.blocks, cs.rows, cs.diag, lambda s, x_of: b, x,
                        sweeps, cs.dims, backend, cfg)


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
