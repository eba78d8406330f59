"""Iterative solvers over (dynamic, possibly distributed) sparse matrices.

CG is the paper's workload (HPCG — benchmarked there with the
preconditioner disabled, §IV-B; ``pcg(apply_M=...)`` restores it via the
``repro.mg`` multigrid V-cycle with the colored SymGS smoother). The
solvers are generic over an ``apply_A`` closure so the same loop runs:
  * single device, any concrete/dynamic format       (paper Fig. 4)
  * distributed local/remote split across a mesh     (paper Fig. 5)
Vector algebra goes through repro.core.ops (dot/waxpby/axpy/norm2), the
algorithms the paper exposes for DenseVector.

Every solve names its layers in the compiled program: ``solver.spmv``
around each ``apply_A`` (the initial residual's included),
``solver.precond`` around ``apply_M`` and ``solver.vector`` around the
dots and vector updates. A ``jax.named_scope`` costs nothing at run time:
XLA keeps it as the ``op_name`` metadata of every op it lowers to, fusions
and ops hoisted out of the loop included, so a device profile attributes
each op to its layer (``repro.obs.trace``).
"""
from __future__ import annotations

import functools
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import ops as _ops


class CGResult(NamedTuple):
    x: jax.Array
    iters: jax.Array
    resnorm: jax.Array  # final ||r||_2
    # Fixed-size residual-norm history: ``history[k] = ||r_k||_2`` with
    # ``history[0]`` the initial residual; entries past the converged
    # iteration are NaN. The shape is ``(maxiter + 1,)`` regardless of
    # where the solve stopped, so the whole result is jit/vmap-friendly
    # (no data-dependent shapes). None for legacy constructions.
    history: Optional[jax.Array] = None


def operator(A, mesh=None, backend: str = "auto", cfg=None) -> Callable:
    """``apply_A`` closure for the solvers, over any matrix flavour.

    Accepts a concrete container, a (Switch)DynamicMatrix, or a
    ``DistSparseMatrix`` (then ``mesh`` is required and the closure is the
    overlapped distributed SpMV — including the interior/boundary overlap
    schedule when the matrix was built split, which every CG iteration's
    ``apply_A`` then inherits). ``backend="auto"`` routes every SpMV —
    per shard and per format — through the measured kernel-config cache
    (``repro.core.ops.kernel_route``): the Pallas kernels take the hot
    path exactly where a tuned tile config beat the reference path, so a
    distributed HPCG CG inherits tuned kernels on each shard by default.
    ``cfg`` pins an explicit kernel tile config instead (dict, forwarded
    to every SpMV the closure issues; None keeps the tuned/heuristic
    resolution per shard and format).
    """
    from repro.core.distributed import DistSparseMatrix, dist_spmv

    if isinstance(A, DistSparseMatrix):
        if mesh is None:
            raise ValueError("operator(DistSparseMatrix) requires mesh=")
        return lambda v: dist_spmv(A, v, mesh, backend=backend, cfg=cfg)
    return lambda v: _ops.spmv(A, v, backend=backend, cfg=cfg)


def _cg_step(apply_A: Callable, state):
    """One CG iteration (shared by :func:`cg` and :func:`cg_fixed_iters`):
    (x, r, p, rs) -> (x, r, p, rs). All reductions are global (XLA emits
    the cross-shard all-reduce when the vectors are sharded)."""
    x, r, p, rs = state
    with jax.named_scope("solver.spmv"):
        Ap = apply_A(p)
    with jax.named_scope("solver.vector"):
        alpha = rs / jnp.maximum(_ops.dot(p, Ap), 1e-30)
        x = _ops.axpy(alpha, p, x)
        r = _ops.axpy(-alpha, Ap, r)
        rs_new = _ops.dot(r, r)
        beta = rs_new / jnp.maximum(rs, 1e-30)
        p = _ops.waxpby(1.0, r, beta, p)
    return x, r, p, rs_new


def _residual0(apply_A: Callable, b, x0):
    """``(x0, r0 = b - A x0)``, ``x0`` defaulting to zeros."""
    with jax.named_scope("solver.vector"):
        x0 = jnp.zeros_like(b) if x0 is None else x0
    with jax.named_scope("solver.spmv"):
        Ax0 = apply_A(x0)
    with jax.named_scope("solver.vector"):
        return x0, b - Ax0


def cg(apply_A: Callable, b: jax.Array, x0: Optional[jax.Array] = None,
       tol: float = 1e-8, maxiter: int = 100) -> CGResult:
    """Unpreconditioned conjugate gradients (HPCG's optimized-phase solve).

    Runs a fixed-shape lax.while_loop over the shared :func:`_cg_step`.
    """
    x0, r0 = _residual0(apply_A, b, x0)
    with jax.named_scope("solver.vector"):
        rs0 = _ops.dot(r0, r0)
        tol2 = jnp.asarray(tol, b.dtype) ** 2 * jnp.maximum(rs0, 1e-30)
        hist0 = jnp.full((maxiter + 1,), jnp.nan,
                         b.dtype).at[0].set(jnp.sqrt(rs0))

    def cond(state):
        (_, _, _, rs), k, _ = state
        return (rs > tol2) & (k < maxiter)

    def body(state):
        s, k, hist = state
        s = _cg_step(apply_A, s)
        with jax.named_scope("solver.vector"):
            return s, k + 1, hist.at[k + 1].set(jnp.sqrt(s[3]))

    (x, r, p, rs), k, hist = jax.lax.while_loop(cond, body,
                                                ((x0, r0, r0, rs0), 0, hist0))
    return CGResult(x, k, jnp.sqrt(rs), hist)


def cg_fixed_iters(apply_A: Callable, b: jax.Array,
                   x0: Optional[jax.Array] = None, iters: int = 50) -> CGResult:
    """Fixed-iteration CG (benchmark timing variant: no early exit, the
    HPCG 'optimized problem timing' loop shape). Same :func:`_cg_step`
    body as :func:`cg`, under ``lax.scan``."""
    x0, r0 = _residual0(apply_A, b, x0)
    with jax.named_scope("solver.vector"):
        rs0 = _ops.dot(r0, r0)

    def body(state, _):
        state = _cg_step(apply_A, state)
        with jax.named_scope("solver.vector"):
            return state, jnp.sqrt(state[3])

    (x, r, _, rs), norms = jax.lax.scan(body, (x0, r0, r0, rs0), None,
                                        length=iters)
    with jax.named_scope("solver.vector"):
        hist = jnp.concatenate([jnp.sqrt(rs0)[None], norms])
    return CGResult(x, jnp.asarray(iters), jnp.sqrt(rs), hist)


def pcg(apply_A: Callable, b: jax.Array,
        diag_A: Optional[jax.Array] = None,
        x0: Optional[jax.Array] = None, tol: float = 1e-8,
        maxiter: int = 100, *, apply_M: Optional[Callable] = None) -> CGResult:
    """Preconditioned CG, generic over the preconditioner ``z = M^{-1} r``.

    ``apply_M`` is any symmetric-positive-definite linear map — in
    particular ``repro.mg.MGHierarchy.apply_M()``, the multigrid V-cycle
    with the multicolored symmetric Gauss-Seidel smoother
    (``repro.mg.smoothers``). The coloring makes HPCG's reference SymGS
    sweep vector-parallel (per-color row-block SpMVs), so the
    preconditioner the paper had to disable (§IV-B: sequential triangular
    sweeps) runs on the same dynamic-format SpMV machinery as the
    operator itself.

    Without ``apply_M``, ``diag_A`` (from extract_diagonal() on any
    dynamic format) selects the classic Jacobi preconditioner
    M = diag(A) — the cheap fallback for operators with no usable
    coloring.
    """
    if apply_M is None:
        if diag_A is None:
            raise ValueError("pcg needs apply_M= (e.g. an MG V-cycle) or "
                             "diag_A= (Jacobi)")
        minv = jnp.where(jnp.abs(diag_A) > 1e-30, 1.0 / diag_A, 0.0)
        apply_M = lambda r: minv * r  # noqa: E731
    x0, r0 = _residual0(apply_A, b, x0)
    with jax.named_scope("solver.precond"):
        z0 = apply_M(r0)
    with jax.named_scope("solver.vector"):
        rz0 = _ops.dot(r0, z0)
        rr0 = _ops.dot(r0, r0)
        tol2 = jnp.asarray(tol, b.dtype) ** 2 * jnp.maximum(rr0, 1e-30)
        hist0 = jnp.full((maxiter + 1,), jnp.nan,
                         b.dtype).at[0].set(jnp.sqrt(rr0))

    # ||r||^2 is carried in the loop state: the convergence test reads it
    # instead of re-reducing r every cond evaluation, and computing it next
    # to dot(r, z) in the body lets XLA batch the two reductions into one
    # all-reduce under sharding — one fewer global reduction per iteration.
    def cond(state):
        _, _, _, _, rr, k, _ = state
        return (rr > tol2) & (k < maxiter)

    def body(state):
        x, r, p, rz, _, k, hist = state
        with jax.named_scope("solver.spmv"):
            Ap = apply_A(p)
        with jax.named_scope("solver.vector"):
            alpha = rz / jnp.maximum(_ops.dot(p, Ap), 1e-30)
            x = _ops.axpy(alpha, p, x)
            r = _ops.axpy(-alpha, Ap, r)
        with jax.named_scope("solver.precond"):
            z = apply_M(r)
        with jax.named_scope("solver.vector"):
            rz_new = _ops.dot(r, z)
            rr_new = _ops.dot(r, r)
            beta = rz_new / jnp.maximum(rz, 1e-30)
            p = _ops.waxpby(1.0, z, beta, p)
            return (x, r, p, rz_new, rr_new, k + 1,
                    hist.at[k + 1].set(jnp.sqrt(rr_new)))

    x, r, p, rz, rr, k, hist = jax.lax.while_loop(
        cond, body, (x0, r0, z0, rz0, rr0, 0, hist0))
    return CGResult(x, k, jnp.sqrt(rr), hist)
