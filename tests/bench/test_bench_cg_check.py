"""What decides `correct` in the CG cell, at a size a test run holds:
the program passes, the control (the plain reference in bfloat16 in the
program's place) fails, and so does a run with its timed path broken."""
import jax.numpy as jnp
import pytest

import small_cells
from bench import harness, system

GRID = 16


@pytest.fixture
def cell(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_TUNING_CACHE", str(tmp_path / "sel.json"))
    return small_cells.small_cell("hpcg104-cg", GRID)


def test_program_is_correct(cell):
    r = small_cells.run(cell)
    assert r["correct"], r["check"]
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert set(r["metrics"]) == {"solve_ms", "solve_p95_ms", "setup_s"}
    assert list(r)[-1] == "check"


def test_traced_run_reads_the_per_layer_metrics_it_finds(cell):
    r = small_cells.run(cell, trace=True)
    assert r["correct"], r["check"]
    # the CPU has no TPU plane and no peak: those readers find nothing
    assert set(r["metrics"]) == {"setup.build_s", "setup.optimize_s",
                                 "cg.iters"}
    assert r["device"]["window_s"] > 0 and r["device"]["busy_s"] == 0
    assert set(r["breakdown"]) == {"device_ops", "idle_gaps"}
    assert list(r)[-1] == "check"


def test_bfloat16_control_is_not_correct(cell):
    control = harness.control_solve(cell.config, cell.traffic, jnp.bfloat16)
    r = small_cells.run(cell, solve_override=control)
    assert not r["correct"]
    assert r["check"]["true_res"]["value"] > 10 * r["check"]["true_res"]["limit"]


def test_step_that_returns_its_state_unchanged_is_caught(cell, monkeypatch):
    from repro.core import solvers

    monkeypatch.setattr(solvers, "_cg_step", lambda apply_A, state: state)
    r = small_cells.run(cell)
    assert not r["correct"]
    assert r["failed"] == r["attempted"]


def test_answer_altered_where_it_is_produced_is_caught(cell, monkeypatch):
    cg = system.cg

    def altered(*a, **k):
        res = cg(*a, **k)
        return res._replace(x=res.x.at[GRID].add(0.01))

    monkeypatch.setattr(system, "cg", altered)
    r = small_cells.run(cell)
    assert not r["correct"]
    assert r["check"]["err"]["value"] > r["check"]["err"]["limit"]
