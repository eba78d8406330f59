"""MGHierarchy — geometric multigrid V-cycle over DynamicMatrix levels.

Each level is an *independent* sparse operator with its own sparsity
structure — exactly the scenario where runtime format selection pays
(Morpheus unleashed, arXiv:2304.09511): the fine stencil favours DIA, the
small coarse systems favour whatever the policy measures/predicts for
their shape bucket. ``build_hierarchy`` therefore routes every level's
operator *and* every smoother color block through one
``FormatPolicy`` (``select`` for the level operator, one batched
``select_batch`` pass per level for its stacked color blocks) when a
policy is given.

``apply_M()`` returns a jit-able closure ``r -> z`` (the level loop
unrolls at trace time; level data lowers to on-device constants) that
plugs straight into ``repro.core.solvers.pcg(apply_A, b, apply_M=...)``.
The default configuration — SymGS pre/post smoothing with equal sweep
counts, injection transfer pair ``P = R^T``, a symmetric coarse solve
(SymGS sweeps) — keeps M symmetric positive definite, which plain
(non-flexible) PCG requires; ``tests/test_mg.py`` checks both properties
against the densified operator.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import ops as _ops
from repro.core.convert import convert_execute, plan_switch
from repro.core.formats import COO, Format
from repro.core.hpcg import HPCGProblem, to_coo as hpcg_to_coo
from repro.mg.coarsen import (Coarsening, coarsen_execute, plan_coarsen,
                              prolong, restrict)
from repro.mg.smoothers import ColoredSystem, build_colored, jacobi, symgs
from repro.obs import trace as _trace

# Coarsening stops once a level has this few rows (the coarse solve —
# SymGS sweeps — handles the rest).
MIN_COARSE_ROWS = 8


@dataclasses.dataclass(frozen=True)
class MGLevel:
    """One level: operator + smoother + (except coarsest) the coarsening."""

    A: object                      # level operator, any concrete format
    diag: jax.Array                # diag(A) for the Jacobi fallback
    smoother: Optional[ColoredSystem]   # None -> weighted Jacobi
    coarsen: Optional[Coarsening]       # None on the coarsest level
    dims: Tuple[int, int, int]

    @property
    def n(self) -> int:
        return self.A.shape[0]

    @property
    def format(self) -> Format:
        return Format(self.A.format)


@dataclasses.dataclass(frozen=True)
class MGHierarchy:
    """The V-cycle preconditioner M^{-1} ~ A^{-1} over a level stack."""

    levels: Tuple[MGLevel, ...]
    pre: int = 1
    post: int = 1
    coarse_sweeps: int = 4
    backend: str = "auto"

    @property
    def nlevels(self) -> int:
        return len(self.levels)

    def apply_M(self) -> Callable:
        """``r -> z = M^{-1} r``: one V-cycle, jit-able (close over the
        hierarchy; level containers lower to on-device constants)."""
        return lambda r: v_cycle(self, r)

    def formats(self):
        """Per-level (operator format, color-block formats) — the
        introspection hook the selection tests/benchmarks read."""
        return [{
            "level": i, "dims": lev.dims, "n": lev.n,
            "A": lev.format.name,
            "colors": ([f.name for f in lev.smoother.formats]
                       if lev.smoother is not None else None),
        } for i, lev in enumerate(self.levels)]

    def __repr__(self):
        dims = " > ".join("x".join(map(str, lev.dims)) for lev in self.levels)
        return (f"MGHierarchy({dims}; pre={self.pre}, post={self.post}, "
                f"coarse_sweeps={self.coarse_sweeps})")


def _smooth(hier: MGHierarchy, lev: MGLevel, b, x, sweeps: int):
    if sweeps <= 0:
        return x if x is not None else jnp.zeros_like(b)
    if lev.smoother is not None:
        return symgs(lev.smoother, b, x, sweeps=sweeps, backend=hier.backend)
    return jacobi(lev.diag, lambda v: _ops.spmv(lev.A, v, backend=hier.backend),
                  b, x, sweeps=sweeps)


def v_cycle(hier: MGHierarchy, r: jax.Array, level: int = 0) -> jax.Array:
    """One V-cycle on ``A_level z = r`` from a zero initial guess.

    Each step is a named scope of its level in the compiled program:
    ``mg.l<k>.smooth`` (the coarsest level's sweeps too),
    ``mg.l<k>.residual``, ``mg.l<k>.restrict`` and ``mg.l<k>.prolong``.
    Level ``k + 1`` runs outside level ``k``'s scopes, so no level's scope
    holds another's ops.
    """
    lev = hier.levels[level]
    if level == hier.nlevels - 1:
        with jax.named_scope(f"mg.l{level}.smooth"):
            return _smooth(hier, lev, r, None, hier.coarse_sweeps)
    with jax.named_scope(f"mg.l{level}.smooth"):
        x = _smooth(hier, lev, r, None, hier.pre)
    with jax.named_scope(f"mg.l{level}.residual"):
        res = r - _ops.spmv(lev.A, x, backend=hier.backend)
    with jax.named_scope(f"mg.l{level}.restrict"):
        rc = restrict(lev.coarsen, res)
    xc = v_cycle(hier, rc, level + 1)
    with jax.named_scope(f"mg.l{level}.prolong"):
        x = x + prolong(lev.coarsen, xc)
    with jax.named_scope(f"mg.l{level}.smooth"):
        return _smooth(hier, lev, r, x, hier.post)


def _pick_format(C: COO, policy, fmt: Format):
    best = policy.select(C).best if policy is not None else Format(fmt)
    return convert_execute(C, plan_switch(C, best))


def build_hierarchy(prob: HPCGProblem, nlevels: Optional[int] = None,
                    fmt: Format = Format.CSR, policy=None,
                    smoother: str = "symgs",
                    pre: int = 1, post: int = 1, coarse_sweeps: int = 4,
                    prolong: str = "injection",
                    coarse_op: str = "rediscretize",
                    backend: str = "auto",
                    dtype=jnp.float32) -> MGHierarchy:
    """Construct the geometric hierarchy for an HPCG stencil problem.

    Levels coarsen 2:1 while every grid dim stays even and the level keeps
    at least ``MIN_COARSE_ROWS`` rows (or until ``nlevels``). Each level's
    operator format comes from ``policy.select`` (falling back to ``fmt``
    without a policy); each level's smoother color blocks come from one
    ``policy.select_batch`` pass over the stacked blocks. ``smoother`` is
    ``"symgs"`` (colored symmetric Gauss-Seidel) or ``"jacobi"``.

    Hierarchy construction is the plan/execute pipeline: per step one
    static :class:`~repro.mg.coarsen.CoarsenPlan` plus the jit-compiled
    device :func:`~repro.mg.coarsen.coarsen_execute` (rediscretized coarse
    stencil, injection/trilinear tables) — index arrays never round-trip
    through host.
    """
    if smoother not in ("symgs", "jacobi"):
        raise ValueError(f"unknown smoother {smoother!r}")
    dims = (prob.nx, prob.ny, prob.nz)
    C = hpcg_to_coo(prob, dtype=dtype)

    levels = []
    while True:
        last = ((nlevels is not None and len(levels) + 1 >= nlevels)
                or any(d % 2 for d in dims)
                or (C.shape[0] // 8) < MIN_COARSE_ROWS)
        with _trace.span("build.mg_level", level=len(levels),
                         dims="x".join(map(str, dims))) as sp:
            cz = None
            if not last:
                plan = plan_coarsen(*dims, prolong=prolong,
                                    coarse_op=coarse_op)
                cz = coarsen_execute(plan, Af=C)
            A = _pick_format(C, policy, fmt)
            cs = (build_colored(C, dims=dims, fmt=fmt, policy=policy)
                  if smoother == "symgs" else None)
            diag = _ops.extract_diagonal(C)
            sp.set(fmt=Format(A.format).name).sync(diag)
        levels.append(MGLevel(A, diag, cs, cz, dims))
        if last:
            break
        C = cz.Ac
        dims = plan.coarse
    return MGHierarchy(tuple(levels), pre=pre, post=post,
                       coarse_sweeps=coarse_sweeps, backend=backend)
