"""The HPCG example's entry point, called in-process as chip_smoke.py
calls it: ``main(argv)`` returns the run record, not just an exit code.
Its CLI runs on 8 forced host devices in a subprocess (the device count
is fixed when jax starts, and conftest keeps this session at one)."""
import importlib.util
import os
import subprocess
import sys

import pytest

from repro.obs import report

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


def _run_cli(args, cwd, **env):
    """``HPCG_DEVICES=8 python examples/hpcg_solve.py ARGS`` in ``cwd``,
    with its selection cache there too; returns its stdout."""
    full_env = dict(os.environ, HPCG_DEVICES="8",
                    REPRO_TUNING_CACHE=str(cwd / "selections.json"), **env)
    res = subprocess.run([sys.executable, os.path.abspath(EXAMPLE)] + args,
                         capture_output=True, text=True, timeout=600,
                         env=full_env, cwd=cwd)
    assert res.returncode == 0, f"stdout:\n{res.stdout}\nstderr:\n{res.stderr}"
    return res.stdout


@pytest.fixture(scope="module")
def mg_traced(tmp_path_factory):
    """MG-PCG with per-level selection on 8 host devices, under
    ``REPRO_TRACE=full``: its stdout and the directory it ran in."""
    cwd = tmp_path_factory.mktemp("mg")
    out = _run_cli(["--grid", "16", "16", "16", "--mode", "multiformat",
                    "--tune", "cached", "--precond", "mg", "--tol", "1e-8",
                    "--verbose"], cwd, REPRO_TRACE="full")
    return out, cwd


def test_cli_cg_multiformat_cached_8_devices(tmp_path):
    out = _run_cli(["--grid", "8", "8", "16", "--mode", "multiformat",
                    "--tune", "cached"], tmp_path)
    assert "-> PASS" in out, out
    assert "per-shard remote formats:" in out, out


def test_cli_mg_pcg_selects_per_level_8_devices(mg_traced):
    out, _ = mg_traced
    assert "-> PASS" in out, out
    assert "  level 0 " in out and "  level 1 " in out, out


def test_cli_mg_pcg_trace_renders_by_phase(mg_traced, capsys):
    """The exported ``trace.json`` renders a row for each host phase the
    MG-PCG build and solve pass through."""
    out, cwd = mg_traced
    assert "trace exported: trace.json" in out, out
    assert report.main([str(cwd / "trace.json")]) == 0
    rows = {line.split()[0] for line in capsys.readouterr().out.splitlines()
            if line.strip()}
    assert {"build", "plan", "convert", "select", "solver"} <= rows, rows
