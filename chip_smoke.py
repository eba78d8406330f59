"""Chip smoke test: HPCG (CG and MG-PCG) at its 104^3 local grid on a TPU.

Drives ``examples/hpcg_solve.py``'s ``main`` in this one process (a chip
belongs to one process, so no phase runs in a child):

  a. CG, ``--backend ref --local DIA``: the jnp reference SpMV;
  b. the same with ``--backend pallas``: the DIA Pallas kernel, which must
     appear as a ``tpu_custom_call`` in the compiled solve; (a) and (b)
     must agree to within one iteration and 1e-4 in x;
  c. MG-PCG, ``--precond mg --mode multiformat --tune ml``.

``--chips 4`` runs only the distributed path instead: CG (DIA local
blocks, COO halo coupling) weak-scaled to 104x104x416 (one 104^3 z-slab
per chip) on a 4-chip mesh, then the same global problem on one chip. Both must validate and agree to within one
iteration; on the mesh, x and every stacked part of the operator must
span all four chips, and no chip may hold over twice the mean bytes.

Each phase prints one ``[phase] {...}`` line (sizes, host seconds,
convergence, ``kernel.route.*`` counters, custom-call count, peak device
bytes; no speed claim). The last line is the device record
``{"ok": true, "device": {...}}``, printed only when every phase passed.
With no TPU the script exits 2 before any phase; any phase failure raises.

Run:  python chip_smoke.py [--chips 4]
"""
import argparse
import gc
import importlib.util
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
GRID = (104, 104, 104)  # HPCG's reference local grid per process


def _prepare_env():
    """Compile cache and a fresh selection cache under the checkout, set
    before jax is imported: no record from an earlier or interpreted run
    can steer this one."""
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro import env

    env.apply()
    # HPCG's set-up computes on the host's CPU device (examples/hpcg_solve.py)
    platforms = os.environ.get("JAX_PLATFORMS")
    if platforms and "cpu" not in platforms.split(","):
        os.environ["JAX_PLATFORMS"] = platforms + ",cpu"
    state = os.path.join(ROOT, ".smoke")
    shutil.rmtree(state, ignore_errors=True)
    os.makedirs(state)
    os.environ["REPRO_TUNING_CACHE"] = os.path.join(state, "selections.json")


def _load_hpcg_solve():
    path = os.path.join(ROOT, "examples", "hpcg_solve.py")
    spec = importlib.util.spec_from_file_location("hpcg_solve", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _grid_args(grid):
    return ["--grid", *map(str, grid)]


def _devices(run):
    """The chips a run solved on, in id order."""
    return sorted(run.result.x.sharding.device_set, key=lambda d: d.id)


def _phase(hpcg_solve, name, argv):
    """One ``hpcg_solve.main`` run (one solve: the smoke times nothing);
    prints its record, raises on failure."""
    from repro.obs import metrics

    argv = argv + ["--no-warmup"]
    t0 = time.perf_counter()
    with metrics.scope() as s:
        run = hpcg_solve.main(argv)
    res = run.result
    rec = {
        "argv": " ".join(argv), "grid": list(run.grid), "n": run.n,
        "nnz": run.nnz, "setup_s": run.setup_s,
        "optimize_s": run.optimize_s, "compile_s": run.compile_s,
        "wall_s": time.perf_counter() - t0, "iters": int(res.iters),
        "resnorm": float(res.resnorm), "max_err": run.err,
        "kernel_route": {k: v for k, v in s.deltas().items()
                         if k.startswith("kernel.route.")},
        "tpu_custom_calls": run.hlo.count(
            'custom_call_target="tpu_custom_call"'),
        "peak_bytes_in_use": [d.memory_stats().get("peak_bytes_in_use")
                              for d in _devices(run)],
    }
    if run.hier is not None:
        rec["level_formats"] = [
            {k: v for k, v in lev.items() if k != "colors"}
            for lev in run.hier.formats()]
    print(f"[{name}] " + json.dumps(rec), flush=True)
    if run.code != 0:
        raise RuntimeError(f"phase {name}: validation failed, "
                           f"max|x - 1| = {run.err}")
    return run, rec


def _same_solution(name, ra, rb, tol=1e-4):
    """Iteration counts within one and max|x_a - x_b| <= tol."""
    import numpy as np

    ia, ib = int(ra.result.iters), int(rb.result.iters)
    dx = float(np.abs(np.asarray(ra.result.x) - np.asarray(rb.result.x)).max())
    print(f"[{name}] " + json.dumps({"iters": [ia, ib], "max_dx": dx}),
          flush=True)
    if abs(ia - ib) > 1 or not dx <= tol:
        raise RuntimeError(f"{name}: iterations {ia} vs {ib}, "
                           f"max|dx| = {dx} (limit 1 and {tol})")


def one_chip(hpcg_solve):
    cg = _grid_args(GRID) + ["--devices", "1", "--local", "DIA"]
    ra, _ = _phase(hpcg_solve, "a:cg-ref", cg + ["--backend", "ref"])
    rb, rec_b = _phase(hpcg_solve, "b:cg-pallas", cg + ["--backend", "pallas"])
    if rec_b["tpu_custom_calls"] < 1:
        raise RuntimeError("phase b: no tpu_custom_call in the compiled "
                           "solve; the DIA kernel did not run on the chip")
    _same_solution("a-vs-b", ra, rb)
    del ra, rb
    gc.collect()
    _phase(hpcg_solve, "c:mg-pcg", _grid_args(GRID) + [
        "--devices", "1", "--precond", "mg", "--mode", "multiformat",
        "--tune", "ml"])


def _check_placement(run, ndev):
    """x and every stacked operator part are split over ``ndev`` chips,
    and no chip holds over twice the mean of live bytes."""
    import jax

    A = run.A
    leaves = [("x", run.result.x)] + [
        (f"{part}[{i}]", leaf)
        for part in ("local", "remote", "boundary")
        for i, leaf in enumerate(jax.tree.leaves(getattr(A, part)))]
    bad = [name for name, a in leaves
           if len(a.sharding.device_set) != ndev
           or a.sharding.is_fully_replicated]
    used = [d.memory_stats()["bytes_in_use"] for d in _devices(run)]
    mean = sum(used) / len(used)
    print("[placement] " + json.dumps({
        "arrays": len(leaves), "not_split": bad, "bytes_in_use": used}),
        flush=True)
    if bad:
        raise RuntimeError(f"not split over {ndev} chips: {bad}")
    if max(used) > 2 * mean:
        raise RuntimeError(f"device bytes_in_use {used} exceed 2x the "
                           f"mean {mean:.0f}")


def four_chips(hpcg_solve, ndev=4):
    import numpy as np

    grid = (GRID[0], GRID[1], GRID[2] * ndev)  # one 104^3 z-slab per chip
    cg = _grid_args(grid) + ["--local", "DIA"]
    r4, _ = _phase(hpcg_solve, f"cg-{ndev}chips",
                   cg + ["--devices", str(ndev)])
    _check_placement(r4, ndev)
    iters4, x4 = int(r4.result.iters), np.asarray(r4.result.x)
    del r4
    gc.collect()
    r1, _ = _phase(hpcg_solve, "cg-1chip", cg + ["--devices", "1"])
    i1 = int(r1.result.iters)
    dx = float(np.abs(np.asarray(r1.result.x) - x4).max())
    print(f"[{ndev}-vs-1] " + json.dumps({"iters": [iters4, i1],
                                          "max_dx": dx}), flush=True)
    if abs(iters4 - i1) > 1:
        raise RuntimeError(f"{ndev} chips took {iters4} iterations, one "
                           f"chip {i1}")


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--chips", type=int, choices=(1, 4), default=1,
                   help="4: run only the 4-chip distributed CG and its "
                        "one-chip comparison")
    args = p.parse_args(argv)

    _prepare_env()
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < args.chips:
        print(f"chip_smoke: needs {args.chips} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    hpcg_solve = _load_hpcg_solve()
    if args.chips == 1:
        one_chip(hpcg_solve)
    else:
        four_chips(hpcg_solve, args.chips)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
