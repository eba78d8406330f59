"""HPCG problem substrate (paper §II-B, §IV-B).

Synthetic Poisson problem on a regular 3D grid, 27-point stencil — the
matrix whose regular, diagonal-dominated pattern makes DIA the winning
format on a single node, and whose MPI local/remote split creates the
irregular remote part motivating per-part/per-shard format selection.

Grid ordering is x-fastest (idx = x + nx*(y + ny*z)); partitioning along z
in whole planes makes every remote column fall in the neighbouring slab's
boundary plane => halo width = nx*ny per side (neighbor exchange).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax.numpy as jnp
import numpy as np

from repro.core.formats import COO, coo_from_arrays


@dataclasses.dataclass(frozen=True)
class HPCGProblem:
    nx: int
    ny: int
    nz: int
    row: np.ndarray
    col: np.ndarray
    val: np.ndarray
    shape: Tuple[int, int]

    @property
    def nrows(self) -> int:
        return self.nx * self.ny * self.nz


def generate_problem(nx: int, ny: int, nz: int, dtype=np.float32) -> HPCGProblem:
    """27-point stencil: diag = 26, off-diag = -1 (HPCG's synthetic system)."""
    n = nx * ny * nz
    # grid coordinates of rows 0..n-1 (x-fastest), and for every row its 27
    # neighbours in (dz, dy, dx) order — ascending column within each row,
    # so the row-major compaction below is already (row, col)-sorted
    z, y, x = (a.ravel() for a in np.meshgrid(
        np.arange(nz), np.arange(ny), np.arange(nx), indexing="ij"))
    d = np.array([-1, 0, 1])
    dz, dy, dx = (a.ravel() for a in np.meshgrid(d, d, d, indexing="ij"))
    xp, yp, zp = x[:, None] + dx, y[:, None] + dy, z[:, None] + dz
    ok = ((xp >= 0) & (xp < nx) & (yp >= 0) & (yp < ny)
          & (zp >= 0) & (zp < nz))
    col = (xp + nx * (yp + ny * zp))[ok].astype(np.int64)
    row = np.repeat(np.arange(n, dtype=np.int64), ok.sum(axis=1))
    val = np.where(row == col, 26.0, -1.0).astype(dtype)
    return HPCGProblem(nx, ny, nz, row, col, val, (n, n))


def to_coo(prob: HPCGProblem, capacity: Optional[int] = None,
           dtype=jnp.float32) -> COO:
    return coo_from_arrays(prob.row, prob.col, prob.val, prob.shape,
                           capacity=capacity, dtype=dtype)


def slab_plan(prob: HPCGProblem, nshards: int) -> "DistPlan":
    """Analytic :class:`~repro.core.distributed.DistPlan` for the z-slab
    partition of the stencil problem.

    The partition structure is known a priori — slabs of ``nz/P`` whole x-y
    planes, every remote column in the neighbouring slab's boundary plane,
    halo width ``nx*ny`` per side — so no reach scan over the global
    triplets is needed; the only data-dependent metadata (per-shard
    capacities) comes from one vectorised bincount. Feed the plan to
    ``build_dist_matrix(..., plan=..., check_plan=False)`` (the plan is
    correct by construction) and the global triplets are touched exactly
    once, by the on-device ``partition_execute`` scatter; with the default
    ``check_plan=True`` the builder additionally runs its one-pass
    stale-plan validation scan on host.
    """
    from repro.core.distributed import DistPlan, _split_caps

    n = prob.shape[0]
    if nshards <= 0 or prob.nz % nshards:
        raise ValueError(
            f"z-slab partition needs nz % P == 0, got nz={prob.nz} / {nshards}")
    mp = n // nshards
    shard = prob.row // mp
    local_mask = (prob.col // mp) == shard
    lcounts = np.bincount(shard[local_mask], minlength=nshards)
    rcounts = np.bincount(shard[~local_mask], minlength=nshards)
    remote_empty = nshards == 1
    # interior/boundary overlap caps (boundary = the slab's first/last x-y
    # planes): computed here so a split build skips its own host scan.
    icap, bcap = (None, None) if remote_empty else _split_caps(
        prob.row, prob.col, prob.val, mp, nshards)
    return DistPlan(nshards=nshards, mp=mp,
                    hw=0 if remote_empty else prob.nx * prob.ny,
                    halo_mode="neighbor", shape=prob.shape,
                    local_cap=max(1, int(lcounts.max())),
                    remote_cap=max(1, int(rcounts.max())),
                    remote_empty=remote_empty,
                    interior_cap=icap, boundary_cap=bcap)


def partition_problem(prob: HPCGProblem, nshards: int, dtype=jnp.float32):
    """Slab-aware problem partitioner: ``(local, remote, plan)``.

    Returns the stacked per-shard local/remote COO containers directly on
    device — the global triplets are never re-materialised into per-shard
    host copies (the pre-plan builder's second materialisation).
    """
    from repro.core.distributed import partition_execute_jit

    plan = slab_plan(prob, nshards)
    local, remote = partition_execute_jit(prob.row, prob.col, prob.val,
                                          plan=plan, dtype=dtype)
    return local, remote, plan


def rhs_for_ones(prob: HPCGProblem, dtype=np.float32) -> np.ndarray:
    """b = A @ 1 — HPCG's exact solution is the all-ones vector."""
    b = np.zeros(prob.shape[0], dtype=np.float64)
    np.add.at(b, prob.row, prob.val.astype(np.float64))
    return b.astype(dtype)


def exact_solution(prob: HPCGProblem, dtype=np.float32) -> np.ndarray:
    return np.ones(prob.shape[0], dtype=dtype)
