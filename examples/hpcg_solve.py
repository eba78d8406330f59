"""Morpheus-enabled HPCG (paper §IV-B): distributed CG with dynamic formats.

Reproduces the paper's workflow end-to-end:
  1. Problem setup          — 27-point-stencil Poisson system on a 3D grid
  2. Problem optimization   — partition into local/remote parts per shard,
                              select formats (fixed or auto-tuned per shard)
  3. Optimized timing       — CG solve, SpMV-dominated
  4. Validation             — solution must be the all-ones vector

Run (8 simulated devices):
  HPCG_DEVICES=8 PYTHONPATH=src python examples/hpcg_solve.py --mode multiformat
  HPCG_DEVICES=8 PYTHONPATH=src python examples/hpcg_solve.py \
      --mode multiformat --tune cached   # warm cache: zero profiling runs
  HPCG_DEVICES=8 PYTHONPATH=src python examples/hpcg_solve.py \
      --precond mg --mode multiformat    # full MG-PCG, per-level DistPlans
  PYTHONPATH=src python examples/hpcg_solve.py --local DIA --remote COO
  REPRO_TRACE=summary PYTHONPATH=src python examples/hpcg_solve.py \
      --profile /tmp/hpcg-profile   # device time by layer (README)

``main(argv)`` returns an :class:`HPCGRun` — the exit code plus what a
caller in the same process checks (the ``CGResult``, the compiled solve's
HLO text, phase timings, the operator); the CLI exits with its ``code``.
"""
import argparse
import contextlib
import dataclasses
import os
import sys
import time
from typing import Any, Optional

if __name__ == "__main__":
    # repro.env is jax-free: backend-gated XLA flags land before jax
    # initializes (async collectives on GPU, forced host devices for SPMD)
    _here = os.path.dirname(os.path.abspath(__file__))
    sys.path.insert(0, os.path.join(_here, "..", "src"))
    from repro import env as _env

    _env.apply(host_devices=int(os.environ.get("HPCG_DEVICES", 0)) or None)

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.core import Format, hpcg  # noqa: E402
from repro.core.distributed import (build_dist_matrix,  # noqa: E402
                                    distribute_vector)
from repro.core.solvers import cg, operator, pcg  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.obs import trace  # noqa: E402


@dataclasses.dataclass
class HPCGRun:
    """One HPCG run: ``code`` is the CLI exit code (0 iff validation
    passed); the rest is for in-process callers."""
    code: int
    grid: tuple
    n: int
    nnz: int
    setup_s: float
    optimize_s: float
    compile_s: float
    solve_s: float
    err: float                 # max|x - 1|
    result: Any                # CGResult
    hlo: str                   # compiled solve, as text
    A: Any                     # DistSparseMatrix (level 0 under --precond mg)
    hier: Optional[Any] = None  # DistMGHierarchy under --precond mg


def _optimize(args, prob, mesh, ndev):
    """The distributed operator, and the MG hierarchy under --precond mg
    (its level 0 IS the operator: building it separately would run the
    partition + per-shard selection twice)."""
    if args.precond == "mg":
        from repro.mg import build_dist_hierarchy

        hier = build_dist_hierarchy(
            prob, mesh, "rows", nlevels=args.mg_levels, mode=args.mode,
            tune=args.tune, local_format=Format[args.local],
            remote_format=Format[args.remote], backend=args.backend)
        return hier.levels[0].A, hier
    # The z-slab structure of the stencil is known analytically: slab_plan
    # replaces the partition scan, and being correct by construction it can
    # also skip the builder's stale-plan validation (check_plan=False) — the
    # triplets are then touched exactly once, by the device scatter.
    plan = hpcg.slab_plan(prob, ndev) if prob.nz % ndev == 0 else None
    A = build_dist_matrix(prob.row, prob.col, prob.val, prob.shape, mesh,
                          "rows", local_format=Format[args.local],
                          remote_format=Format[args.remote], mode=args.mode,
                          tune=args.tune, plan=plan, check_plan=plan is None)
    return A, None


def _profile(log_dir: Optional[str]):
    """A JAX profile into ``log_dir`` while the block runs (a no-op for
    None). Python's call tracer stays off: it would slow the host and
    bury the program's spans."""
    if log_dir is None:
        return contextlib.nullcontext()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return jax.profiler.trace(log_dir, profiler_options=opts)


def _print_formats(A, hier):
    if hier is not None:
        for rec in hier.formats():
            bnd = f" boundary={rec['boundary']}" if "boundary" in rec else ""
            print(f"  level {rec['level']} {rec['dims']}: "
                  f"local={rec['local']}{bnd} remote={rec['remote']}")
        return
    from repro.core import DEFAULT_CANDIDATES
    names = [f.name for f in DEFAULT_CANDIDATES]
    label = "interior" if A.split else "local"
    print(f"  per-shard {label} formats: ",
          [names[i] for i in np.asarray(A.local.active_id)])
    if A.split:
        print("  per-shard boundary formats:",
              [names[i] for i in np.asarray(A.boundary.active_id)])
    print("  per-shard remote formats:",
          [names[i] for i in np.asarray(A.remote.active_id)])


def main(argv=None) -> HPCGRun:
    p = argparse.ArgumentParser()
    p.add_argument("--grid", type=int, nargs=3, default=[16, 16, 32])
    p.add_argument("--mode", choices=["uniform", "multiformat"], default="uniform")
    p.add_argument("--tune", default="ml",
                   choices=["ml", "cached", "analytic", "profile"],
                   help="per-shard selection policy in multiformat mode "
                        "(repro.tuning.FormatPolicy)")
    p.add_argument("--local", default="DIA", choices=[f.name for f in Format])
    p.add_argument("--remote", default="COO", choices=[f.name for f in Format])
    p.add_argument("--backend", default="auto",
                   choices=["auto", "ref", "pallas"],
                   help="SpMV kernel routing: auto = Pallas where a tuned "
                        "kernel config measured faster than the jnp "
                        "reference, the reference otherwise")
    p.add_argument("--tol", type=float, default=1e-7)
    p.add_argument("--maxiter", type=int, default=500)
    p.add_argument("--precond", nargs="?", const="jacobi", default="none",
                   choices=["none", "jacobi", "mg"],
                   help="preconditioner: 'mg' = geometric multigrid V-cycle "
                        "with the multicolored SymGS smoother (repro.mg — "
                        "HPCG's real preconditioner, made vector-parallel "
                        "by the 8-coloring; per-level slab DistPlans), "
                        "'jacobi' = diag(A) fallback. Bare --precond keeps "
                        "the historical Jacobi behaviour.")
    p.add_argument("--mg-levels", type=int, default=None,
                   help="cap the MG hierarchy depth (default: coarsen while "
                        "dims stay even and slabs divide the mesh)")
    p.add_argument("--devices", type=int, default=None,
                   help="solve on the first N of jax.devices() "
                        "(default: all)")
    p.add_argument("--no-warmup", action="store_true",
                   help="skip the untimed warm-up solve: the compiled solve "
                        "runs once, and its time includes the first run's "
                        "one-off costs (for smoke runs that time nothing)")
    p.add_argument("--profile", metavar="DIR", default=None,
                   help="write a JAX profile of the timed solve to DIR, "
                        "with the program's spans on the device's clock "
                        "when REPRO_TRACE is on, and the compiled solve's "
                        "text (DIR/solve.hlo.txt), whose op_name metadata "
                        "names each op's layer (solver.*, mg.l<k>.*, "
                        "dist.*)")
    p.add_argument("--verbose", action="store_true",
                   help="print the per-iteration convergence curve "
                        "(||r_k|| from the solver's residual history)")
    args = p.parse_args(argv)

    devices = jax.devices()[:args.devices]
    ndev = len(devices)
    mesh = make_mesh((ndev,), ("rows",), devices=devices)
    print(f"devices: {ndev}, grid: {args.grid}")

    # --- 1. problem setup ---------------------------------------------------
    t0 = time.perf_counter()
    with trace.span("build.problem", grid="x".join(map(str, args.grid))):
        prob = hpcg.generate_problem(*args.grid)
    setup_s = time.perf_counter() - t0
    print(f"setup: n={prob.shape[0]} nnz={len(prob.val)} ({setup_s:.2f}s)")

    # --- 2. problem optimization (Morpheus: partition + format selection) ---
    # Optimization computes on the host's CPU device and places the finished
    # containers on the mesh: its sort and scatter programs take 17-40 s
    # each to compile for a TPU at 104^3 (about 20 distinct ones per MG
    # level), a second or two for the CPU. --tune profile times candidate
    # kernels, so it stays on the devices that solve, as does everything
    # when JAX_PLATFORMS leaves the CPU backend out.
    setup_dev = devices[0]
    if args.tune != "profile":
        try:
            setup_dev = jax.local_devices(backend="cpu")[0]
        except RuntimeError:
            pass
    t0 = time.perf_counter()
    with trace.span("build.optimize", mode=args.mode, precond=args.precond,
                    device=setup_dev.platform), jax.default_device(setup_dev):
        A, hier = _optimize(args, prob, mesh, ndev)
    optimize_s = time.perf_counter() - t0
    print(f"optimization on {setup_dev.platform}: "
          f"{A if hier is None else hier} "
          f"({optimize_s:.2f}s)")
    if args.mode == "multiformat":
        _print_formats(A, hier)
    b = distribute_vector(hpcg.rhs_for_ones(prob), mesh, "rows")

    # --- 3. optimized timing -------------------------------------------------
    operands = (A, b)
    if args.precond == "mg":
        # the hierarchy is an argument, not a closure: closed-over arrays
        # would be compiled into the executable as constants
        operands = (A, b, hier)
        solve = jax.jit(lambda a, bb, h: pcg(
            operator(a, mesh, backend=args.backend), bb, tol=args.tol,
            maxiter=args.maxiter, apply_M=h.apply_M()))
    elif args.precond == "jacobi":
        diag = jnp.asarray(
            np.full(prob.shape[0], 26.0, np.float32))  # HPCG diagonal
        operands = (A, b, diag)
        solve = jax.jit(lambda a, bb, d: pcg(
            operator(a, mesh, backend=args.backend), bb, d, tol=args.tol,
            maxiter=args.maxiter))
    else:
        solve = jax.jit(lambda a, bb: cg(
            operator(a, mesh, backend=args.backend), bb, tol=args.tol,
            maxiter=args.maxiter))
    with trace.span("solver.compile", precond=args.precond):
        t0 = time.perf_counter()
        solve = solve.lower(*operands).compile()
        compile_s = time.perf_counter() - t0
        if not args.no_warmup:
            # waited for in every trace mode: a warm-up still running
            # would be timed with the solve
            jax.block_until_ready(solve(*operands))
    if args.profile:
        # the compiled text holds each op's op_name, and with it its layer
        os.makedirs(args.profile, exist_ok=True)
        with open(os.path.join(args.profile, "solve.hlo.txt"), "w") as f:
            f.write(solve.as_text())
    with _profile(args.profile):
        t0 = time.perf_counter()
        with trace.span("solver.solve", precond=args.precond) as sp:
            res = solve(*operands)
            sp.sync(res)
        res = jax.block_until_ready(res)
        dt = time.perf_counter() - t0
    iters = int(res.iters)
    # HPCG's figure of merit: ~ (2 * nnz) flops per SpMV, 1 SpMV per iter
    gflops = 2 * len(prob.val) * iters / dt / 1e9

    # --- 4. validation --------------------------------------------------------
    err = float(np.abs(np.asarray(res.x) - 1.0).max())
    print(f"solve: {iters} iters, {dt * 1e3:.1f} ms, ||r||={float(res.resnorm):.2e}, "
          f"SpMV-rate ~{gflops:.2f} GFLOP/s")
    if args.verbose and res.history is not None:
        hist = np.asarray(res.history)
        hist = hist[~np.isnan(hist)]
        print("convergence (||r_k||, relative to ||r_0||):")
        r0 = hist[0] if hist.size and hist[0] > 0 else 1.0
        for k, rn in enumerate(hist):
            print(f"  iter {k:4d}: {rn:.3e}  rel={rn / r0:.3e}")
    print(f"validation: max|x - 1| = {err:.2e} -> {'PASS' if err < 1e-3 else 'FAIL'}")

    if trace.enabled():
        print("\n# trace summary (REPRO_TRACE=" + trace.mode() + ")")
        print(trace.summary())
        if trace.mode() == "full":
            out = os.environ.get("REPRO_TRACE_EXPORT", "trace.json")
            print(f"trace exported: {trace.export_chrome(out)} "
                  f"(render: python -m repro.obs.report {out})")
    return HPCGRun(code=0 if err < 1e-3 else 1, grid=tuple(args.grid),
                   n=prob.shape[0], nnz=len(prob.val), setup_s=setup_s,
                   optimize_s=optimize_s, compile_s=compile_s, solve_s=dt,
                   err=err, result=res, hlo=solve.as_text(), A=A, hier=hier)


if __name__ == "__main__":
    sys.exit(main().code)
