"""repro.mg: coarsening oracles, colored SymGS vs sequential GS, V-cycle
symmetry/PD, MG-PCG iteration counts, distributed MG-PCG (subprocess)."""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import Format, convert, hpcg, spmv, to_dense_np
from repro.core.solvers import cg, pcg
from repro.obs import metrics
from repro.mg import (build_colored, build_hierarchy, check_coloring,
                      coarsen_execute, color_grid, galerkin_coarse,
                      plan_coarsen, prolong, restrict, stencil27_coo,
                      symgs, symgs_reference_np)

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


# ---------------------------------------------------------------------------
# Coarsening: restriction / prolongation vs dense oracles
# ---------------------------------------------------------------------------


def _dense_injection_np(nxc, nyc, nzc, nxf, nyf, nzf):
    """Independent dense R (nc x nf): coarse (x,y,z) <- fine (2x,2y,2z)."""
    nc, nf = nxc * nyc * nzc, nxf * nyf * nzf
    R = np.zeros((nc, nf))
    for zc in range(nzc):
        for yc in range(nyc):
            for xc in range(nxc):
                i = xc + nxc * (yc + nyc * zc)
                j = 2 * xc + nxf * (2 * yc + nyf * 2 * zc)
                R[i, j] = 1.0
    return R


def _dense_trilinear_np(nxc, nyc, nzc, nxf, nyf, nzf):
    """Independent dense P (nf x nc): per-axis weight 1 (even) / 0.5 (odd),
    out-of-grid corners dropped (Dirichlet-0 ghost)."""
    nc, nf = nxc * nyc * nzc, nxf * nyf * nzf
    P = np.zeros((nf, nc))
    for zf in range(nzf):
        for yf in range(nyf):
            for xf in range(nxf):
                i = xf + nxf * (yf + nyf * zf)
                axes = []
                for cf, ncdim in ((xf, nxc), (yf, nyc), (zf, nzc)):
                    if cf % 2 == 0:
                        axes.append([(cf // 2, 1.0)])
                    else:
                        opts = [(cf // 2, 0.5)]
                        if cf // 2 + 1 < ncdim:
                            opts.append((cf // 2 + 1, 0.5))
                        axes.append(opts)
                for xc, wx in axes[0]:
                    for yc, wy in axes[1]:
                        for zc, wz in axes[2]:
                            P[i, xc + nxc * (yc + nyc * zc)] += wx * wy * wz
    return P


def test_injection_restrict_prolong_vs_dense_oracle():
    plan = plan_coarsen(4, 4, 4)
    c = coarsen_execute(plan)
    R = _dense_injection_np(2, 2, 2, 4, 4, 4)
    rng = np.random.default_rng(0)
    rf = rng.standard_normal(plan.nf).astype(np.float32)
    xc = rng.standard_normal(plan.nc).astype(np.float32)
    np.testing.assert_allclose(np.asarray(restrict(c, jnp.asarray(rf))),
                               R @ rf, rtol=1e-6, atol=1e-6)
    # injection pairing: P = R^T exactly (V-cycle symmetry requirement)
    np.testing.assert_allclose(np.asarray(prolong(c, jnp.asarray(xc))),
                               R.T @ xc, rtol=1e-6, atol=1e-6)


def test_trilinear_restrict_prolong_vs_dense_oracle():
    plan = plan_coarsen(4, 6, 4, prolong="trilinear")
    c = coarsen_execute(plan)
    P = _dense_trilinear_np(2, 3, 2, 4, 6, 4)
    rng = np.random.default_rng(1)
    rf = rng.standard_normal(plan.nf).astype(np.float32)
    xc = rng.standard_normal(plan.nc).astype(np.float32)
    np.testing.assert_allclose(np.asarray(prolong(c, jnp.asarray(xc))),
                               P @ xc, rtol=1e-5, atol=1e-5)
    # full weighting: R = P^T / 8
    np.testing.assert_allclose(np.asarray(restrict(c, jnp.asarray(rf))),
                               P.T @ rf / 8.0, rtol=1e-5, atol=1e-5)


def test_stencil27_matches_generate_problem():
    prob = hpcg.generate_problem(3, 4, 2)
    D_ref = to_dense_np(hpcg.to_coo(prob))
    D_dev = to_dense_np(stencil27_coo(3, 4, 2))
    np.testing.assert_allclose(D_dev, D_ref, rtol=0, atol=0)


def test_galerkin_coarse_symmetric_and_coarsens():
    prob = hpcg.generate_problem(4, 4, 4)
    plan = plan_coarsen(4, 4, 4, prolong="trilinear", coarse_op="galerkin")
    Ac = galerkin_coarse(hpcg.to_coo(prob), plan)
    D = to_dense_np(Ac)
    assert D.shape == (8, 8)
    np.testing.assert_allclose(D, D.T, rtol=1e-6, atol=1e-6)
    assert np.all(np.linalg.eigvalsh(D.astype(np.float64)) > 0)


def test_plan_coarsen_validation():
    with pytest.raises(ValueError):
        plan_coarsen(3, 4, 4)  # odd dim
    with pytest.raises(ValueError):
        plan_coarsen(4, 4, 4, coarse_op="galerkin")  # degenerate pairing


# ---------------------------------------------------------------------------
# Colored SymGS vs the sequential NumPy oracle
# ---------------------------------------------------------------------------


# Grids with every dim even take the color-major layout; an odd dim the
# natural one, with its gathers and scatter.
LAYOUT = {(4, 4, 4): "color_major", (5, 3, 4): "gather",
          (8, 8, 2): "color_major"}


@pytest.mark.parametrize("dims", [(4, 4, 4), (5, 3, 4), (8, 8, 2)])
def test_colored_symgs_matches_sequential_gs(dims):
    prob = hpcg.generate_problem(*dims)
    C = hpcg.to_coo(prob)
    colors = color_grid(*dims)
    with metrics.scope() as m:
        cs = build_colored(C, dims=dims, fmt=Format.CSR, check=True)
    assert m.delta(f"mg.smoother.{LAYOUT[dims]}") == 1
    assert (m.delta("mg.smoother.color_major")
            + m.delta("mg.smoother.gather")) == 1
    assert (cs.dims is not None) == (LAYOUT[dims] == "color_major")
    rng = np.random.default_rng(0)
    n = prob.shape[0]
    b = rng.standard_normal(n).astype(np.float32)
    x0 = rng.standard_normal(n).astype(np.float32)
    got = symgs(cs, jnp.asarray(b), jnp.asarray(x0), sweeps=2, backend="ref")
    want = symgs_reference_np(prob.row, prob.col, prob.val, colors, b, x0,
                              sweeps=2)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-5)


def test_colored_blocks_any_format_agree():
    dims = (4, 4, 4)
    prob = hpcg.generate_problem(*dims)
    C = hpcg.to_coo(prob)
    b = jnp.asarray(hpcg.rhs_for_ones(prob))
    base = symgs(build_colored(C, dims=dims, fmt=Format.CSR), b, backend="ref")
    for fmt in (Format.ELL, Format.DIA, Format.COO, Format.SELL, Format.HYB):
        cs = build_colored(C, dims=dims, fmt=fmt)
        assert set(cs.formats) == {fmt}
        assert cs.dims == dims  # the color-major layout, in every format
        got = symgs(cs, b, backend="ref")
        np.testing.assert_allclose(np.asarray(got), np.asarray(base),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dims", [(4, 4, 4), (8, 8, 2), (6, 4, 8), (2, 2, 2)])
def test_color_major_moves_and_block_offsets(dims):
    """The moves are the color-major permutation (color, then x-fastest
    rank within the color), and each color block's diagonals lie on the
    offsets the sweep takes as static: all 27 where every dim is at
    least 4."""
    from repro.core.convert import plan_switch
    from repro.mg.smoothers import (color_block_offsets, color_major_index,
                                    from_color_major, to_color_major)

    n = int(np.prod(dims))
    v = np.arange(n, dtype=np.float32)
    cm = np.asarray(to_color_major(jnp.asarray(v), dims))
    pos = color_major_index(dims)
    np.testing.assert_array_equal(cm[pos], v)
    np.testing.assert_array_equal(
        np.asarray(from_color_major(jnp.asarray(cm), dims)), v)
    colors = color_grid(*dims)
    np.testing.assert_array_equal(np.sort(pos[colors == 3]),
                                  3 * (n // 8) + np.arange(n // 8))

    cs = build_colored(hpcg.to_coo(hpcg.generate_problem(*dims)), dims=dims,
                       fmt=Format.COO)
    for c, blk in enumerate(cs.blocks):
        live = plan_switch(blk, Format.DIA).dia_offsets
        offs = color_block_offsets(dims, c)
        assert live == offs
        # three shifts per axis, two along a dim of 2 (the sub-grid is 1
        # wide there, so a shift off it leaves the grid)
        assert len(offs) == np.prod([3 if d >= 4 else 2 for d in dims])


def test_check_coloring_rejects_improper():
    prob = hpcg.generate_problem(4, 4, 4)
    with pytest.raises(ValueError, match="improper coloring"):
        check_coloring(hpcg.to_coo(prob),
                       np.zeros(prob.shape[0], np.int32))


# ---------------------------------------------------------------------------
# V-cycle: symmetry + positive definiteness (PCG's requirements)
# ---------------------------------------------------------------------------


def test_vcycle_apply_M_symmetric_positive_definite():
    prob = hpcg.generate_problem(4, 4, 4)
    hier = build_hierarchy(prob, backend="ref")
    n = prob.shape[0]
    M = np.asarray(jax.jit(jax.vmap(hier.apply_M()))(jnp.eye(n, dtype=jnp.float32))).T
    sym_err = np.abs(M - M.T).max() / np.abs(M).max()
    assert sym_err < 1e-5, sym_err
    w = np.linalg.eigvalsh(((M + M.T) / 2).astype(np.float64))
    assert w.min() > 0, w.min()


# ---------------------------------------------------------------------------
# MG-PCG convergence (the acceptance criterion)
# ---------------------------------------------------------------------------


def test_mg_pcg_beats_cg_16cubed():
    prob = hpcg.generate_problem(16, 16, 16)
    A = convert(hpcg.to_coo(prob), Format.CSR)
    b = jnp.asarray(hpcg.rhs_for_ones(prob))
    apply_A = lambda v: spmv(A, v)  # noqa: E731
    hier = build_hierarchy(prob, backend="ref")
    r_cg = jax.jit(lambda bb: cg(apply_A, bb, tol=1e-8, maxiter=500))(b)
    r_mg = jax.jit(lambda bb: pcg(apply_A, bb, tol=1e-8, maxiter=500,
                                  apply_M=hier.apply_M()))(b)
    assert int(r_mg.iters) < int(r_cg.iters), (int(r_mg.iters),
                                               int(r_cg.iters))
    assert int(r_cg.iters) < 500  # both actually converged
    np.testing.assert_allclose(np.asarray(r_mg.x), 1.0, rtol=1e-3, atol=1e-3)


def test_mg_pcg_trilinear_galerkin_converges():
    prob = hpcg.generate_problem(8, 8, 8)
    A = convert(hpcg.to_coo(prob), Format.CSR)
    b = jnp.asarray(hpcg.rhs_for_ones(prob))
    apply_A = lambda v: spmv(A, v)  # noqa: E731
    hier = build_hierarchy(prob, prolong="trilinear", coarse_op="galerkin",
                           backend="ref")
    res = pcg(apply_A, b, tol=1e-8, maxiter=200, apply_M=hier.apply_M())
    assert int(res.iters) < 200
    np.testing.assert_allclose(np.asarray(res.x), 1.0, rtol=1e-3, atol=1e-3)


def test_hierarchy_per_level_format_selection():
    from repro.tuning import FormatPolicy

    prob = hpcg.generate_problem(8, 8, 8)
    policy = FormatPolicy("analytic")
    hier = build_hierarchy(prob, policy=policy, backend="ref")
    fmts = hier.formats()
    assert len(fmts) >= 2
    for rec in fmts:
        assert rec["A"] in [f.name for f in policy.candidates]
        assert rec["colors"] is not None and len(rec["colors"]) == 8
    # the selection is real: solve still converges with the chosen formats
    A = convert(hpcg.to_coo(prob), Format.CSR)
    b = jnp.asarray(hpcg.rhs_for_ones(prob))
    res = pcg(lambda v: spmv(A, v), b, tol=1e-8, maxiter=100,
              apply_M=hier.apply_M())
    assert int(res.iters) < 100


def test_jacobi_smoother_hierarchy_converges():
    prob = hpcg.generate_problem(8, 8, 8)
    A = convert(hpcg.to_coo(prob), Format.CSR)
    b = jnp.asarray(hpcg.rhs_for_ones(prob))
    hier = build_hierarchy(prob, smoother="jacobi", pre=2, post=2,
                           backend="ref")
    res = pcg(lambda v: spmv(A, v), b, tol=1e-8, maxiter=200,
              apply_M=hier.apply_M())
    assert int(res.iters) < 200
    np.testing.assert_allclose(np.asarray(res.x), 1.0, rtol=1e-3, atol=1e-3)


# ---------------------------------------------------------------------------
# Distributed MG-PCG (8 forced host devices, subprocess)
# ---------------------------------------------------------------------------


def _run_subprocess(body: str):
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys
        sys.path.insert(0, %r)
        import numpy as np
        import jax, jax.numpy as jnp
        from repro.core import hpcg, Format
        from repro.core.distributed import distribute_vector
        from repro.core.solvers import cg, pcg, operator
        from repro.mg import build_dist_hierarchy
        from repro.launch.mesh import make_mesh
    """ % os.path.abspath(SRC)) + textwrap.dedent(body)
    res = subprocess.run([sys.executable, "-c", script], capture_output=True,
                         text=True, timeout=600)
    assert res.returncode == 0, f"stdout:\n{res.stdout}\nstderr:\n{res.stderr}"
    return res.stdout


def test_dist_mg_pcg_beats_cg_8shards():
    out = _run_subprocess("""
        mesh = make_mesh((8,), ("rows",))
        prob = hpcg.generate_problem(16, 16, 16)
        hier = build_dist_hierarchy(prob, mesh, "rows", mode="multiformat",
                                    tune="analytic")
        assert hier.nlevels >= 2, hier
        fmts = hier.formats()
        for rec in fmts:  # per-level per-shard selection ran
            assert len(rec["local"]) == 8, rec
        A = hier.levels[0].A
        b = distribute_vector(hpcg.rhs_for_ones(prob), mesh, "rows")
        apply_A = operator(A, mesh, backend="ref")
        r_cg = jax.jit(lambda bb: cg(apply_A, bb, tol=1e-8, maxiter=500))(b)
        r_mg = jax.jit(lambda bb: pcg(apply_A, bb, tol=1e-8, maxiter=500,
                                      apply_M=hier.apply_M()))(b)
        assert int(r_mg.iters) < int(r_cg.iters), (int(r_mg.iters),
                                                   int(r_cg.iters))
        assert int(r_cg.iters) < 500
        err = float(np.abs(np.asarray(r_mg.x) - 1.0).max())
        assert err < 1e-3, err
        print("DIST_MG_OK", int(r_mg.iters), int(r_cg.iters))
    """)
    assert "DIST_MG_OK" in out


def test_dist_hierarchy_is_a_jit_argument():
    """The distributed hierarchy is a pytree: a solve takes it as a jit
    argument, so its arrays are inputs of the compiled V-cycle rather
    than constants compiled into it, and the result is unchanged."""
    from repro.core.distributed import distribute_vector
    from repro.launch.mesh import make_mesh
    from repro.mg import build_dist_hierarchy

    mesh = make_mesh((1,), ("rows",))
    prob = hpcg.generate_problem(8, 8, 8)
    hier = build_dist_hierarchy(prob, mesh, "rows", tune="analytic")
    r = distribute_vector(hpcg.rhs_for_ones(prob), mesh, "rows")
    leaves = jax.tree.leaves(hier)
    assert len(leaves) > 3 * hier.nlevels
    f = jax.jit(lambda h, v: h.apply_M()(v))
    assert len(jax.tree.leaves(f.lower(hier, r).args_info)) == len(leaves) + 1
    closed = jax.jit(hier.apply_M())
    np.testing.assert_allclose(np.asarray(f(hier, r)), np.asarray(closed(r)),
                               rtol=1e-6, atol=1e-6)


# (dims, smoother_format) -> layout of each of the two levels built
DIST_SMOOTHERS = {((8, 8, 8), None): ("color_major", "color_major"),
                  ((8, 8, 8), Format.ELL): ("color_major", "color_major"),
                  ((6, 6, 6), None): ("color_major", "gather")}


@pytest.mark.parametrize("dims, smoother_format", list(DIST_SMOOTHERS),
                         ids=["8-default", "8-ell", "6-default"])
def test_dist_smoother_matches_sequential_gs_one_shard(dims, smoother_format):
    """Each level's distributed smoother on one shard is the sequential
    color-ordered Gauss-Seidel, in the color-major layout (default DIA
    blocks, or the format asked for) and in the natural fallback."""
    from repro.core.distributed import distribute_vector
    from repro.launch.mesh import make_mesh
    from repro.mg import build_dist_hierarchy
    from repro.mg.dist import _dist_smooth

    layouts = DIST_SMOOTHERS[(dims, smoother_format)]
    mesh = make_mesh((1,), ("rows",))
    with metrics.scope() as m:
        hier = build_dist_hierarchy(hpcg.generate_problem(*dims), mesh,
                                    "rows", nlevels=2,
                                    smoother_format=smoother_format)
    for layout in ("color_major", "gather"):
        assert m.delta(f"mg.smoother.{layout}") == layouts.count(layout)
    smooth = jax.jit(lambda h, k, bb, xx: _dist_smooth(
        h, h.levels[k], bb, xx, 2, False), static_argnums=1)
    rng = np.random.default_rng(1)
    for k, (lev, layout) in enumerate(zip(hier.levels, layouts)):
        fmt = (smoother_format or
               (Format.DIA if layout == "color_major" else Format.ELL))
        assert lev.colored.formats == [fmt.name] * 8
        assert (lev.colored.rows is None) == (layout == "color_major")
        prob = hpcg.generate_problem(*lev.dims)
        n = prob.shape[0]
        b = rng.standard_normal(n).astype(np.float32)
        x0 = rng.standard_normal(n).astype(np.float32)
        got = smooth(hier, k, distribute_vector(b, mesh, "rows"),
                     distribute_vector(x0, mesh, "rows"))
        want = symgs_reference_np(prob.row, prob.col, prob.val,
                                  color_grid(*lev.dims), b, x0, sweeps=2)
        np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5,
                                   atol=2e-5)


def test_dist_mg_smoothing_is_gather_free():
    """The mechanism: in the compiled MG-PCG solve, the even levels'
    smoothing holds no gather and no scatter (the natural path's per-color
    takes and scatter-add are gone, and the DIA blocks are read as static
    slices), and level 0's color blocks are DIA tables of 27 diagonals."""
    import re

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from bench import scopes
    from repro.core.distributed import distribute_vector
    from repro.core.formats import DIA
    from repro.core.solvers import operator
    from repro.launch.mesh import make_mesh
    from repro.mg import build_dist_hierarchy
    from repro.mg.smoothers import color_block_offsets

    grid = (16, 16, 16)
    mesh = make_mesh((1,), ("rows",))
    prob = hpcg.generate_problem(*grid)
    hier = build_dist_hierarchy(prob, mesh, "rows", nlevels=4)
    for c, blk in enumerate(hier.levels[0].colored.blocks):
        assert isinstance(blk, DIA)
        offs = np.asarray(blk.offsets)
        assert offs.shape == (1, 27)
        assert tuple(offs[0]) == color_block_offsets(grid, c)
    b = distribute_vector(hpcg.rhs_for_ones(prob), mesh, "rows")
    fn = jax.jit(lambda a, bb, h: pcg(operator(a, mesh), bb, tol=0.0,
                                      maxiter=2, apply_M=h.apply_M()))
    text = fn.lower(hier.levels[0].A, b, hier).compile().as_text()
    table = scopes.instruction_scopes(text)
    found = {}
    for line in text.split("\n"):
        if not line.startswith("  ") or " = " not in line:
            continue
        op = re.search(r"\s(gather|scatter)\(", line.split(" = ", 1)[1])
        if op:
            scope = table[scopes.instruction(line)]
            found.setdefault(scope, []).append(op.group(1))
    assert {"mg.l0.smooth", "mg.l1.smooth"} <= set(table.values())
    assert "mg.l0.smooth" not in found and "mg.l1.smooth" not in found
    assert "gather" in found["mg.l0.restrict"]  # the search finds them
