"""The HPCG example's entry point, called in-process as chip_smoke.py
calls it: ``main(argv)`` returns the run record, not just an exit code."""
import importlib.util
import os

import pytest

EXAMPLE = os.path.join(os.path.dirname(__file__), "..", "examples",
                       "hpcg_solve.py")


@pytest.fixture(scope="module")
def hpcg_solve():
    spec = importlib.util.spec_from_file_location("hpcg_solve", EXAMPLE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("extra", [
    ["--backend", "ref"],
    ["--backend", "pallas"],
    ["--precond", "mg", "--mode", "multiformat", "--tune", "ml"],
])
def test_main_returns_run_record(hpcg_solve, extra):
    run = hpcg_solve.main(["--grid", "8", "8", "8", "--devices", "1"] + extra)
    assert run.code == 0 and run.err < 1e-3
    assert (run.n, run.nnz) == (512, 10648)
    assert int(run.result.iters) > 0
    assert run.result.x.shape == (512,)
    assert "HloModule" in run.hlo
    assert min(run.setup_s, run.optimize_s, run.compile_s) >= 0
    assert (run.hier is not None) == ("mg" in extra)


def test_profile_holds_the_solve_and_its_layers(hpcg_solve, tmp_path):
    """``--profile DIR``: a JAX profile of the timed solve, the program's
    span on the host's line, and the compiled text that places each op
    in its layer."""
    import glob
    import sys

    import jax

    from repro.obs import trace

    sys.path.insert(0, os.path.join(os.path.dirname(EXAMPLE), ".."))
    from bench import scopes

    with trace.tracing("summary"):
        run = hpcg_solve.main(["--grid", "8", "8", "8", "--devices", "1",
                               "--backend", "ref",
                               "--profile", str(tmp_path)])
    assert run.code == 0
    [pb] = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    host = [p for p in jax.profiler.ProfileData.from_file(pb).planes
            if p.name == "/host:CPU"][0]
    assert [ev.name for line in host.lines for ev in line.events
            if ev.name.startswith("solver.")] == ["solver.solve"]
    with open(tmp_path / "solve.hlo.txt") as f:
        table = scopes.instruction_scopes(f.read())
    assert {"solver.spmv", "solver.vector"} <= set(table.values())
