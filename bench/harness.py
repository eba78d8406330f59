"""One run of one cell: set-up, warm-up, the measured window, the check.

The traffic is a closed loop with one caller: it calls the cell's
compiled solve, waits for it, and calls again, in rounds over a pool of
right-hand sides, until the window's seconds have passed; the round in
progress at the deadline ends the window, which counts its true length.
Each exact solution ``x_s`` is drawn from a seed; ``b = A x_s`` comes
from the plain reference in float64, and the chip gets it in float32.
What decides ``correct`` is in :func:`check`.
"""
from __future__ import annotations

import dataclasses
import json
import os
import shutil
import sys
import tempfile
import time
import types
from typing import Callable, List, NamedTuple, Optional

import numpy as np

from bench import reference, spec, tracing, work

SRC = os.path.join(spec.ROOT, "src")


class Answer(NamedTuple):
    """A control solve's result, shaped like the program's."""
    x: object
    iters: object


class NoChip(RuntimeError):
    """No TPU, or fewer chips than the cell asks for."""


def prepare() -> str:
    """Environment for the program, set before JAX is imported: the
    checkout's compile cache (``repro.env``, every program cached), the
    CPU backend beside the TPU for the host-side optimize, and a fresh
    selection cache, so that no earlier record steers this run. Returns
    the run's scratch directory."""
    if SRC not in sys.path:
        sys.path.insert(0, SRC)
    from repro import env

    env.apply()
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    platforms = os.environ.get("JAX_PLATFORMS")
    if platforms and "cpu" not in platforms.split(","):
        os.environ["JAX_PLATFORMS"] = platforms + ",cpu"
    state = tempfile.mkdtemp(prefix="bench-")
    os.environ["REPRO_TUNING_CACHE"] = os.path.join(state, "selections.json")
    return state


def find_chips(n: int):
    """The first ``n`` TPU chips; no CPU fallback."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devices[0].platform} devices")
    if len(devices) < n:
        raise NoChip(f"the cell needs {n} chips, JAX found {len(devices)}")
    return devices[:n]


@dataclasses.dataclass
class Problems:
    """The traffic's pool of right-hand sides, in float64, shaped as the
    grid (nz, ny, nx), and the order the window visits them in."""
    x_s: List[np.ndarray]   # exact solutions
    b: List[np.ndarray]     # b = A x_s, rounded to the float32 served
    order: List[int]
    reference_s: float      # host seconds the plain reference took for b


def make_problems(config: dict, traffic: dict, seed: int) -> Problems:
    """``pool`` exact solutions, uniform on [low, high). Drawn from the
    traffic's ``pool_seed`` where it gives one, so that every run solves
    the same set (CG's iteration count varies with b by some 10%), else
    from the run's seed. The run's seed orders the pool."""
    spec_ = traffic["x_s"]
    shape = reference.grid_shape(config["grid"])
    base = spec_["pool_seed"] if spec_["pool_seed"] is not None else seed
    x_s, bs, ref_s = [], [], 0.0
    for i in range(int(spec_["pool"])):
        x = np.random.default_rng([base, i]).uniform(
            spec_["low"], spec_["high"], size=shape)
        x_s.append(x)
        t0 = time.perf_counter()
        bs.append(reference.apply_A(np, x).astype(np.float32)
                  .astype(np.float64))
        ref_s += time.perf_counter() - t0
    order = np.random.default_rng([seed, 2]).permutation(len(bs)).tolist()
    return Problems(x_s, bs, order, ref_s)


def solve_limits(config: dict, traffic: dict):
    """``(tol, maxiter)`` the traffic's solves run with."""
    if traffic["stop"] == "tolerance":
        return config["tol"], config["maxiter"]
    if traffic["stop"] == "iterations":
        return 0.0, config["timed_set_iters"]
    raise ValueError(f"unknown stop rule {traffic['stop']!r}")


@dataclasses.dataclass
class Window:
    seconds: float          # wall time of the whole window
    solve_s: List[float]    # each solve, dispatch to ready
    iters: List[int]        # each solve's iteration count
    answers: List[tuple]    # sampled (pool index, solution on the host)


def run_window(solve: Callable, bs: list, order: List[int], seconds: float,
               keep: int, rng: np.random.Generator) -> Window:
    """Closed loop: solve, wait, solve the next, in rounds over the pool
    in ``order``, until ``seconds`` have passed and the round in progress
    has ended. Keeps a reservoir sample of ``keep`` answers drawn by
    ``rng``."""
    import jax
    from jax.profiler import TraceAnnotation

    kept: list = []
    solve_s, iters = [], []
    t_begin = time.perf_counter()
    while True:
        for i in order:
            t0 = time.perf_counter()
            with TraceAnnotation("bench.dispatch"):
                res = solve(bs[i])
            with TraceAnnotation("bench.wait"):
                res = jax.block_until_ready(res)
            t1 = time.perf_counter()
            with TraceAnnotation("bench.record"):
                solve_s.append(t1 - t0)
                iters.append(int(res.iters))
                n = len(iters)
                if len(kept) < keep:
                    kept.append((i, res.x))
                else:
                    j = int(rng.integers(0, n))
                    if j < keep:
                        kept[j] = (i, res.x)
        if time.perf_counter() - t_begin >= seconds:
            break
    wall = time.perf_counter() - t_begin
    answers = [(i, np.asarray(x, np.float64)) for i, x in kept]
    return Window(wall, solve_s, iters, answers)


def check(config: dict, traffic: dict, problems: Problems,
          window: Window) -> dict:
    """Each number compared, ``{name: (value, limit)}``, the worst over
    the sampled answers, each against its own right-hand side.

    CG to a tolerance: the true relative residual ``||b - A x|| / ||b||``
    and the error ``max|x - x_s| / max|x_s|``, both against the plain
    float64 stencil. MG-PCG sets: the gap to the float64 reference's own
    iterate after the same iterations, ``max|x - x_ref| / max|x_ref|``,
    and of the true relative residual to the reference's, ``| ||b - A x||
    - ||b - A x_ref|| | / ||b||``, which shows the V-cycle's effect.
    """
    shape = reference.grid_shape(config["grid"])
    _, k = solve_limits(config, traffic)
    refs: dict = {}
    worst: dict = {}
    for i, x in window.answers:
        x, b, x_s = x.reshape(shape), problems.b[i], problems.x_s[i]
        bnorm = float(np.linalg.norm(b))

        def res(v):
            return float(np.linalg.norm(b - reference.apply_A(np, v))) / bnorm

        if config["solver"] == "cg":
            vals = {"true_res": res(x),
                    "err": float(np.abs(x - x_s).max() / np.abs(x_s).max())}
        else:
            if i not in refs:
                refs[i] = reference.solve(np, config, b, k)[0]
            x_ref = refs[i]
            vals = {"x_gap": float(np.abs(x - x_ref).max()
                                   / np.abs(x_ref).max()),
                    "res_gap": abs(res(x) - res(x_ref))}
        for name, v in vals.items():
            worst[name] = max(worst.get(name, v), v)
    return {name: (v, config["limits"][name]) for name, v in worst.items()}


def failures(config: dict, traffic: dict, window: Window) -> int:
    """Solves that did not end as the traffic asks: short of the
    tolerance at ``maxiter``, or not exactly the set's iterations."""
    _, maxiter = solve_limits(config, traffic)
    if traffic["stop"] == "tolerance":
        return sum(1 for k in window.iters if k >= maxiter)
    return sum(1 for k in window.iters if k != maxiter)


def control_solve(config: dict, traffic: dict, dtype):
    """The plain reference in the program's place, computed in ``dtype``
    on the default device: ``b -> (x, iters)``."""
    import jax.numpy as jnp

    shape = reference.grid_shape(config["grid"])
    _, k = solve_limits(config, traffic)

    def solve(b):
        x, k_run = reference.solve(jnp, config,
                                   jnp.asarray(b, dtype).reshape(shape), k)
        return Answer(x.astype(jnp.float32).reshape(-1), jnp.asarray(k_run))
    return solve


@dataclasses.dataclass
class Served:
    """A cell's system, built, with the solve the window calls."""
    system: object
    solve: Callable
    compile_s: float


def serve(cell: spec.Cell, devices,
          solve_override: Optional[Callable] = None) -> Served:
    """Build the cell's system on ``devices`` and compile its solve.
    ``solve_override`` puts another solve (the control) in the program's
    place."""
    from bench import system

    config, traffic = cell.config, cell.traffic
    if traffic["loop"] != "closed" or traffic["clients"] != 1:
        raise ValueError("the traffic generator drives one closed-loop "
                         f"caller, not {traffic['loop']} x "
                         f"{traffic['clients']}")
    sysm = system.build(config, devices)
    n = int(np.prod(config["grid"]))
    b0 = system.place(sysm, np.zeros(n, np.float32))
    tol, maxiter = solve_limits(config, traffic)
    t0 = time.perf_counter()
    solve = solve_override or system.compile_solve(config, sysm, b0, tol,
                                                   maxiter)
    return Served(sysm, solve, time.perf_counter() - t0)


@dataclasses.dataclass
class Measured:
    window: Window
    reduction: Optional[tracing.Reduction]
    problems: Problems
    t_window: float       # perf_counter at the window's start


def measure(cell: spec.Cell, served: Served, seed: int, seconds: float,
            trace: bool) -> Measured:
    """The seed's problems on the chip, one warm solve, then the window
    (with the profiler on, for ``trace``, and at most the traffic's
    ``trace_seconds`` long)."""
    import jax

    from bench import system

    problems = make_problems(cell.config, cell.traffic, seed)
    bs = [system.place(served.system, b.astype(np.float32).reshape(-1))
          for b in problems.b]
    jax.block_until_ready(served.solve(bs[problems.order[0]]))
    rng = np.random.default_rng([seed, 1])
    keep = int(cell.traffic["answers_checked"])
    t_window = time.perf_counter()
    if not trace:
        window = run_window(served.solve, bs, problems.order, seconds, keep,
                            rng)
        return Measured(window, None, problems, t_window)
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        # host TraceMe annotations on, the Python call tracer off
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        try:
            window = run_window(served.solve, bs, problems.order,
                                min(seconds, cell.traffic["trace_seconds"]),
                                keep, rng)
        finally:
            jax.profiler.stop_trace()
        reduction = tracing.load(trace_dir, window.seconds)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    return Measured(window, reduction, problems, t_window)


def verdict(cell: spec.Cell, m: Measured):
    """``(correct, failed, numbers)`` of a measured window."""
    numbers = check(cell.config, cell.traffic, m.problems, m.window)
    failed = failures(cell.config, cell.traffic, m.window)
    correct = failed == 0 and all(v <= lim for v, lim in numbers.values())
    return correct, failed, numbers


def run(cell: spec.Cell, seed: int, seconds: float, trace: bool,
        t_start: float, devices, solve_override: Optional[Callable] = None,
        peaks: Optional[dict] = None) -> dict:
    """One run of ``cell`` on ``devices``: the result object."""
    served = serve(cell, devices, solve_override)
    m = measure(cell, served, seed, seconds, trace)
    # the plain reference's seconds are not set-up of the system
    setup = {"setup_s": m.t_window - t_start - m.problems.reference_s,
             "build_s": served.system.build_s,
             "optimize_s": served.system.optimize_s,
             "compile_s": served.compile_s}
    stats = [d.memory_stats() or {} for d in devices]
    peak = max(s.get("peak_bytes_in_use", 0) for s in stats)
    del served
    correct, failed, numbers = verdict(cell, m)

    ctx = types.SimpleNamespace(cell=cell.name, config=cell.config,
                                traffic=cell.traffic, setup=setup,
                                window=m.window, trace=m.reduction,
                                peaks=peaks, work=work)
    metrics = spec.read_metrics(cell.per_layer if trace else cell.end_to_end,
                                ctx)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": int(peak)}
    result = {"correct": bool(correct), "attempted": len(m.window.iters),
              "failed": failed, "metrics": metrics, "device": device}
    if m.reduction is not None:
        device["busy_s"] = m.reduction.busy_s
        device["window_s"] = m.reduction.window_s
        result["breakdown"] = m.reduction.breakdown()
    result["check"] = {k: {"value": v, "limit": lim}
                       for k, (v, lim) in numbers.items()}
    return result


def main(args, t_start: float) -> int:
    """CLI body: prepare, find the chips, run, print. Exits 2 with no
    result where there is no TPU or too few chips."""
    cell = spec.load_cell(args.workload)
    state = prepare()
    try:
        try:
            devices = find_chips(cell.chips)
            peaks = spec.peaks_for(devices[0].device_kind)
        except (NoChip, KeyError) as e:
            print(f"bench: {e}", file=sys.stderr)
            return 2
        result = run(cell, args.seed, args.seconds, bool(args.trace),
                     t_start, devices, peaks=peaks)
    finally:
        shutil.rmtree(state, ignore_errors=True)
    for k, c in result["check"].items():
        print(f"check {k} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0
