"""A benchmark cell cut to a size a CPU test run can hold, and a run of
it that skips the harness's look for a chip."""
import dataclasses
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import harness, spec  # noqa: E402


def small_cell(name, grid, levels=None, backend="ref"):
    """``name`` with its grid, level count and backend replaced; the
    limits stay the cell's own."""
    cell = spec.load_cell(name)
    cfg = dict(cell.config, grid=[grid] * 3, backend=backend)
    if levels is not None:
        cfg["mg"] = dict(cfg["mg"], levels=levels)
    return dataclasses.replace(cell, config=cfg)


def run(cell, seed=2**33 + 7, solve_override=None, trace=False):
    """A whole run on the CPU, with a window of a quarter second."""
    import jax

    return harness.run(cell, seed, 0.25, trace, time.perf_counter(),
                       jax.devices()[:1], solve_override=solve_override)
