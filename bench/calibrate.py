"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 bench/calibrate.py --workload <cell> --seeds 1 2 ... \\
        --control-seeds 101 102 103 [--seconds 0]

Builds the cell once, then for each seed runs the benchmark's own warm
solve, a short window and the comparison, and prints one JSON line per
seed with every number compared. Then puts the control in the program's
place, the plain reference computed in bfloat16 (the precision below
the float32 the configurations state), and reads the same numbers on
the control seeds. The benchmark's own runs never run this.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import harness, spec  # noqa: E402


def readings(cell, served, seeds, seconds, label):
    for seed in seeds:
        m = harness.measure(cell, served, seed, seconds, False)
        correct, failed, numbers = harness.verdict(cell, m)
        w = m.window
        print(json.dumps({
            "run": label, "seed": seed, "correct": correct,
            "attempted": len(w.iters), "failed": failed,
            "iters": sorted(set(w.iters)),
            "solve_ms": [1e3 * t for t in w.solve_s],
            "check": {k: v for k, (v, _) in numbers.items()}}), flush=True)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, nargs="*", default=[])
    p.add_argument("--control-seeds", type=int, nargs="*", default=[])
    p.add_argument("--seconds", type=float, default=0.0)
    args = p.parse_args(argv)
    cell = spec.load_cell(args.workload)
    state = harness.prepare()
    try:
        try:
            devices = harness.find_chips(cell.chips)
        except harness.NoChip as e:
            print(f"calibrate: {e}", file=sys.stderr)
            return 2
        import jax.numpy as jnp

        served = harness.serve(cell, devices)
        print(json.dumps({"setup_s": time.perf_counter() - T_START,
                          "compile_s": served.compile_s}), flush=True)
        readings(cell, served, args.seeds, args.seconds, "program")
        control = harness.control_solve(cell.config, cell.traffic,
                                        jnp.bfloat16)
        readings(cell, dataclasses.replace(served, solve=control),
                 args.control_seeds, args.seconds, "control")
    finally:
        shutil.rmtree(state, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
