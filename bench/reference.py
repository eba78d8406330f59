"""Plain reference of HPCG's problem and solvers, written from HPCG 3.1.

It imports nothing of the program under test. The operator is the
27-point stencil on an nx x ny x nz grid, 26 on the diagonal and -1 to
each neighbour, applied as ``27 u - (3x3x3 box sum of u)`` over arrays of
shape ``(nz, ny, nx)`` (x fastest, as HPCG orders its rows). The
multigrid preconditioner is HPCG's V-cycle: symmetric Gauss-Seidel
smoothing in the 8-color order (color = x%2 + 2 (y%2) + 4 (z%2), all
points of one color updated together, forward colors then backward),
injection restriction to the even points, injection prolongation, the
stencil rediscretized on every level, and sweeps on the coarsest.

Every function takes the array module ``xp`` (NumPy for the float64
reference, ``jax.numpy`` for the lower-precision control) and computes in
the dtype of the arrays it is given.
"""
from __future__ import annotations

from typing import Sequence, Tuple

import numpy as np

DIAG = 26.0


def _axis_slice(axis: int, start: int, stop: int):
    return (slice(None),) * axis + (slice(start, stop),)


def box_sum(xp, u):
    """Sum over each point's 3x3x3 neighbourhood, zero outside the grid."""
    for axis in range(3):
        m = u.shape[axis]
        pad = [(1, 1) if a == axis else (0, 0) for a in range(3)]
        p = xp.pad(u, pad)
        u = (p[_axis_slice(axis, 0, m)] + p[_axis_slice(axis, 1, m + 1)]
             + p[_axis_slice(axis, 2, m + 2)])
    return u


def apply_A(xp, u):
    """A u for the 27-point stencil, u of shape (nz, ny, nx)."""
    return (DIAG + 1) * u - box_sum(xp, u)


def _add_at(xp, u, sl, v):
    if xp is np:
        u[sl] += v
        return u
    return u.at[sl].add(v)


def _color_slice(c: int):
    px, py, pz = c & 1, (c >> 1) & 1, (c >> 2) & 1
    return (slice(pz, None, 2), slice(py, None, 2), slice(px, None, 2))


def symgs(xp, u, b, sweeps: int):
    """``sweeps`` symmetric Gauss-Seidel sweeps in color order from u."""
    u = u.copy() if xp is np else u
    for _ in range(sweeps):
        for c in list(range(8)) + list(range(7, -1, -1)):
            sl = _color_slice(c)
            u = _add_at(xp, u, sl, (b[sl] - apply_A(xp, u)[sl]) / DIAG)
    return u


def vcycle(xp, r, nlevels: int, pre: int, post: int, coarse_sweeps: int):
    """z = M r: one V-cycle from a zero guess."""
    zero = xp.zeros_like(r)
    if nlevels == 1:
        return symgs(xp, zero, r, coarse_sweeps)
    x = symgs(xp, zero, r, pre)
    res = r - apply_A(xp, x)
    xc = vcycle(xp, res[::2, ::2, ::2], nlevels - 1, pre, post,
                coarse_sweeps)
    x = _add_at(xp, x, (slice(None, None, 2),) * 3, xc)
    return symgs(xp, x, r, post)


def _dot(xp, a, b):
    return xp.sum(a * b)


def cg(xp, b, tol: float, maxiter: int):
    """Unpreconditioned CG from zero to ||r|| <= tol ||r0||: (x, iters)."""
    x = xp.zeros_like(b)
    r = b - apply_A(xp, x)
    p = r
    rs = _dot(xp, r, r)
    stop = float(tol) ** 2 * float(rs)
    k = 0
    while k < maxiter and float(rs) > stop:
        Ap = apply_A(xp, p)
        alpha = rs / _dot(xp, p, Ap)
        x = x + alpha * p
        r = r - alpha * Ap
        rs_new = _dot(xp, r, r)
        p = r + (rs_new / rs) * p
        rs = rs_new
        k += 1
    return x, k


def pcg(xp, b, iters: int, nlevels: int, pre: int, post: int,
        coarse_sweeps: int):
    """MG-preconditioned CG from zero through exactly ``iters``
    iterations (HPCG's timed set: tolerance 0)."""
    M = lambda v: vcycle(xp, v, nlevels, pre, post, coarse_sweeps)  # noqa: E731
    x = xp.zeros_like(b)
    r = b - apply_A(xp, x)
    z = M(r)
    p = z
    rz = _dot(xp, r, z)
    for _ in range(iters):
        Ap = apply_A(xp, p)
        alpha = rz / _dot(xp, p, Ap)
        x = x + alpha * p
        r = r - alpha * Ap
        z = M(r)
        rz_new = _dot(xp, r, z)
        p = z + (rz_new / rz) * p
        rz = rz_new
    return x


def grid_shape(grid: Sequence[int]) -> Tuple[int, int, int]:
    """(nz, ny, nx) array shape of an (nx, ny, nz) grid."""
    nx, ny, nz = (int(d) for d in grid)
    return nz, ny, nx


def solve(xp, config: dict, b, iters: int):
    """The configuration's solve over ``b`` (shape ``grid_shape``), in b's
    dtype: CG to the configuration's tolerance, or ``iters`` MG-PCG
    iterations. Returns ``(x, iterations run)``."""
    if config["solver"] == "cg":
        return cg(xp, b, config["tol"], config["maxiter"])
    mg = config["mg"]
    return pcg(xp, b, iters, mg["levels"], mg["pre"], mg["post"],
               mg["coarse_sweeps"]), iters
