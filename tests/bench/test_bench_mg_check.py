"""What decides `correct` in the MG-PCG cell, at a size a test run
holds: the program passes, the control (the plain reference in bfloat16
in the program's place) fails, and so does a run with its timed path
broken."""
import jax.numpy as jnp
import pytest

import small_cells
from bench import harness, system

GRID, LEVELS = 16, 3


@pytest.fixture
def cell(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_TUNING_CACHE", str(tmp_path / "sel.json"))
    return small_cells.small_cell("hpcg104-mgpcg", GRID, LEVELS)


def test_program_is_correct(cell):
    r = small_cells.run(cell)
    assert r["correct"], r["check"]
    assert r["failed"] == 0
    assert set(r["metrics"]) == {"iter_ms", "setup_s"}


def test_bfloat16_control_is_not_correct(cell):
    control = harness.control_solve(cell.config, cell.traffic, jnp.bfloat16)
    r = small_cells.run(cell, solve_override=control)
    assert not r["correct"]
    assert r["check"]["x_gap"]["value"] > 10 * r["check"]["x_gap"]["limit"]


def test_smoother_that_returns_its_state_unchanged_is_caught(cell,
                                                             monkeypatch):
    from repro.mg import dist

    def unchanged(hier, lev, b, x, sweeps, x_is_zero):
        return jnp.zeros_like(b) if x is None else x

    monkeypatch.setattr(dist, "_dist_smooth", unchanged)
    r = small_cells.run(cell)
    assert not r["correct"]


def test_answer_altered_where_it_is_produced_is_caught(cell, monkeypatch):
    pcg = system.pcg

    def altered(*a, **k):
        res = pcg(*a, **k)
        return res._replace(x=res.x.at[GRID].add(0.01))

    monkeypatch.setattr(system, "pcg", altered)
    r = small_cells.run(cell)
    assert not r["correct"]
    assert r["check"]["x_gap"]["value"] > r["check"]["x_gap"]["limit"]
