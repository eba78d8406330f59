"""The least HBM bytes an HPCG solve needs, whatever implements it.

The structure follows HPCG 3.1's per-kernel accounting (``ReportResults``):
every pass over a matrix reads each stored value once, the symmetric
Gauss-Seidel sweep passes over its matrix twice (forward and back), the
V-cycle's residual once. Values are float32, 4 bytes, as the
configurations state. Two things HPCG counts are left out, because an
implementation need not move them through HBM:

- index bytes: the 27-point stencil's pattern is implicit, so a format
  can do without them;
- vector traffic: at 104^3 a vector is 4.5 MB and v5e's on-chip VMEM
  holds 128 MiB, and XLA already keeps the CG vectors there (their
  layouts carry ``S(1)`` in the device trace).

So the counts depend on neither format, padding nor a table's layout, and
a share of the HBM peak built on them cannot pass 100% while values are
read as the float32 the configuration states.
"""
from __future__ import annotations

from typing import List, Sequence

F32 = 4


def stencil_nnz(nx: int, ny: int, nz: int) -> int:
    """Stored values of the 27-point stencil on an nx x ny x nz grid."""
    return (3 * nx - 2) * (3 * ny - 2) * (3 * nz - 2)


def level_nnz(grid: Sequence[int], nlevels: int) -> List[int]:
    """Stored values of each multigrid level (2:1 coarsening per axis)."""
    out, dims = [], tuple(int(d) for d in grid)
    for _ in range(nlevels):
        out.append(stencil_nnz(*dims))
        dims = tuple(d // 2 for d in dims)
    return out


def spmv_bytes(nnz: int) -> int:
    """y = A x, or the residual b - A x: every value once."""
    return F32 * nnz


def symgs_bytes(nnz: int) -> int:
    """One symmetric Gauss-Seidel sweep: forward and back over A."""
    return 2 * F32 * nnz


def vcycle_bytes(nnzs: Sequence[int], pre: int, post: int,
                 coarse_sweeps: int) -> int:
    """One V-cycle: per level above the coarsest, pre- and post-smoothing
    and the residual; sweeps on the coarsest. Injection restriction and
    prolongation touch vectors only."""
    fine = sum((pre + post) * symgs_bytes(z) + spmv_bytes(z)
               for z in nnzs[:-1])
    return fine + coarse_sweeps * symgs_bytes(nnzs[-1])


def solve_bytes(config: dict, iters: int) -> int:
    """Least HBM bytes of one solve of ``config`` that ran ``iters``
    iterations from x0: the initial residual's SpMV (and, under MG, the
    V-cycle for z0), then per iteration A p (and one V-cycle)."""
    grid = config["grid"]
    if config["solver"] == "cg":
        return (iters + 1) * spmv_bytes(stencil_nnz(*grid))
    mg = config["mg"]
    nnzs = level_nnz(grid, mg["levels"])
    step = spmv_bytes(nnzs[0]) + vcycle_bytes(nnzs, mg["pre"], mg["post"],
                                              mg["coarse_sweeps"])
    return (iters + 1) * step


def window_hbm_share(ctx) -> float:
    """Least bytes of every solve of the window over what the chip's HBM
    moves in the window at its peak (%), or None without a peak."""
    if ctx.peaks is None:
        return None
    total = sum(solve_bytes(ctx.config, k) for k in ctx.window.iters)
    return 100.0 * total / (ctx.peaks["hbm_bytes_per_s"] * ctx.window.seconds)
