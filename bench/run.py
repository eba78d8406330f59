"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout, on a machine that holds the chips the
cell asks for (``BENCHMARK.json``). With ``--trace 0`` the last line of
standard output carries the cell's end-to-end metrics, with ``--trace 1``
its per-layer metrics and the device trace's breakdown. The numbers
that decide ``correct`` close standard error, each beside its limit.
Exits 2, printing no result, where JAX finds no TPU or too few chips.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import harness  # noqa: E402


def parse(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=[0, 1], default=0)
    return p.parse_args(argv)


if __name__ == "__main__":
    sys.exit(harness.main(parse(), T_START))
