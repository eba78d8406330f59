"""Least bytes at small grids against hand counts, and the plain
reference against the program's own matrix."""
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import reference, work  # noqa: E402

CG = {"grid": [4, 4, 4], "solver": "cg"}
MG = {"grid": [8, 8, 8], "solver": "mg-pcg",
      "mg": {"levels": 2, "pre": 1, "post": 1, "coarse_sweeps": 4}}


@pytest.mark.parametrize("grid,nnz", [((4, 4, 4), 1000), ((8, 8, 8), 10648),
                                      ((104, 104, 104), 29791000),
                                      ((2, 3, 5), 4 * 7 * 13)])
def test_stencil_nnz_is_3n_minus_2_cubed(grid, nnz):
    assert work.stencil_nnz(*grid) == nnz


def test_stencil_nnz_counts_the_generated_problem():
    from repro.core import hpcg

    assert work.stencil_nnz(4, 5, 6) == len(hpcg.generate_problem(4, 5, 6).val)


def test_cg_counts_values_once_per_spmv():
    # 1000 values x 4 B per SpMV; 3 iterations plus the initial residual
    assert work.spmv_bytes(1000) == 4000
    assert work.solve_bytes(CG, 3) == 4 * 4000


def test_mg_counts_by_hand():
    # levels 8^3 (22^3 = 10648 values) and 4^3 (1000 values)
    fine = 2 * (2 * 4 * 10648) + 4 * 10648   # pre + post sweeps, residual
    coarse = 4 * (2 * 4 * 1000)              # 4 sweeps on the coarsest
    assert work.level_nnz([8, 8, 8], 2) == [10648, 1000]
    assert work.vcycle_bytes([10648, 1000], 1, 1, 4) == fine + coarse
    step = 4 * 10648 + fine + coarse
    assert work.solve_bytes(MG, 5) == 6 * step


def test_hbm_share_and_missing_peak():
    class Ctx:
        config = CG
        window = type("W", (), {"iters": [3, 3], "seconds": 1e-6})
        peaks = {"hbm_bytes_per_s": 64e9}

    assert work.window_hbm_share(Ctx) == pytest.approx(
        100 * 2 * 16000 / (64e9 * 1e-6))
    Ctx.peaks = None
    assert work.window_hbm_share(Ctx) is None


def test_reference_stencil_matches_the_programs_matrix():
    from repro.core import hpcg

    grid = (5, 4, 3)
    prob = hpcg.generate_problem(*grid)
    x = np.random.default_rng(0).uniform(-1, 1, prob.shape[0])
    y = np.zeros_like(x)
    np.add.at(y, prob.row, prob.val.astype(np.float64) * x[prob.col])
    shape = reference.grid_shape(grid)
    got = reference.apply_A(np, x.reshape(shape)).reshape(-1)
    np.testing.assert_allclose(got, y, rtol=0, atol=1e-12)


def test_reference_cg_and_pcg_converge():
    shape = reference.grid_shape((8, 8, 8))
    x_s = np.random.default_rng(1).uniform(0, 2, shape)
    b = reference.apply_A(np, x_s)
    x, k = reference.cg(np, b, 1e-10, 200)
    assert 0 < k < 200 and np.abs(x - x_s).max() < 1e-8
    x5 = reference.pcg(np, b, 5, 2, 1, 1, 4)
    x10 = reference.pcg(np, b, 10, 2, 1, 1, 4)
    e5, e10 = np.abs(x5 - x_s).max(), np.abs(x10 - x_s).max()
    assert e10 < e5 < 0.1
