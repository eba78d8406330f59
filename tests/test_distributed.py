"""Distributed sparse runtime tests.

Multi-device tests run in a subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` so the main pytest
process keeps its single-device view (dry-run isolation, see dryrun.py).
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import Format, hpcg, random_coo, to_dense_np
from repro.core.convert import (convert_execute_batch, planned_pulls_scope,
                                plan_switch_batch)
from repro.core.distributed import (DistPlan, build_dist_matrix, dist_spmv,
                                    distribute_vector, group_ranks,
                                    partition_coo, partition_execute_jit,
                                    plan_partition)
from repro.core.formats import COO
from repro.core.solvers import cg, cg_fixed_iters
from repro.launch.mesh import make_mesh

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run_subprocess(body: str, env=None):
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys
        sys.path.insert(0, %r)
        import numpy as np
        import jax, jax.numpy as jnp
        from repro.core import hpcg, Format
        from repro.core.distributed import (activate_dist, build_dist_matrix,
                                            dist_spmv, distribute_vector)
        from repro.core.solvers import cg, operator
        from repro.launch.mesh import make_mesh
    """ % os.path.abspath(SRC)) + textwrap.dedent(body)
    full_env = dict(os.environ, **(env or {}))
    res = subprocess.run([sys.executable, "-c", script], capture_output=True,
                         text=True, timeout=600, env=full_env)
    assert res.returncode == 0, f"stdout:\n{res.stdout}\nstderr:\n{res.stderr}"
    return res.stdout


def _dense(shape, row, col, val):
    D = np.zeros(shape)
    np.add.at(D, (np.asarray(row), np.asarray(col)), np.asarray(val))
    return D


# ---------------------------------------------------------------------------
# Partitioner (host logic — no devices needed)
# ---------------------------------------------------------------------------

def test_partition_local_remote_split():
    prob = hpcg.generate_problem(4, 4, 8)
    pc = partition_coo(prob.row, prob.col, prob.val, prob.shape, 4)
    assert pc.halo_mode == "neighbor"
    assert pc.hw == 16  # one plane = nx*ny
    # all entries accounted for
    total = sum(len(t[0]) for t in pc.local) + sum(len(t[0]) for t in pc.remote)
    assert total == len(prob.row)
    # local columns are in-range
    for (r, c, v) in pc.local:
        assert (c >= 0).all() and (c < pc.mp).all()
    for (r, c, v) in pc.remote:
        assert (c >= 0).all() and (c < 2 * pc.hw).all()


def test_partition_requires_divisible():
    with pytest.raises(ValueError):
        partition_coo([0], [0], [1.0], (10, 10), 3)


def test_partition_irregular_falls_back_to_gather():
    A = random_coo(0, (64, 64), density=0.2)
    pc = partition_coo(np.asarray(A.row), np.asarray(A.col), np.asarray(A.data),
                       (64, 64), 8)
    assert pc.halo_mode == "gather"


def test_partition_block_diagonal_marks_remote_empty():
    """Satellite fix: reach == 0 must not force hw=1 and a pointless
    exchange — the remote part is statically empty."""
    row = col = np.arange(64)
    val = np.ones(64, np.float32)
    plan = plan_partition(row, col, val, (64, 64), 8)
    assert plan.remote_empty and plan.hw == 0
    assert plan.halo_mode == "neighbor"  # collapsed auto branch
    pc = partition_coo(row, col, val, (64, 64), 8)
    assert pc.remote_empty and pc.hw == 0
    assert all(len(t[0]) == 0 for t in pc.remote)


# ---------------------------------------------------------------------------
# Batched device partitioner (plan_partition + partition_execute)
# ---------------------------------------------------------------------------


def _stacked_parts(prob, nshards):
    plan = plan_partition(prob.row, prob.col, prob.val, prob.shape, nshards)
    local, remote = partition_execute_jit(prob.row, prob.col, prob.val,
                                          plan=plan)
    return local, remote, plan


def test_partition_execute_matches_host_partitioner():
    prob = hpcg.generate_problem(4, 4, 8)
    local, remote, plan = _stacked_parts(prob, 4)
    pc = partition_coo(prob.row, prob.col, prob.val, prob.shape, 4)
    assert (plan.mp, plan.hw, plan.halo_mode) == (pc.mp, pc.hw, pc.halo_mode)
    for p in range(4):
        for part, stacked in ((pc.local, local), (pc.remote, remote)):
            want = _dense(stacked.shape, *part[p])
            got = _dense(stacked.shape, stacked.row[p], stacked.col[p],
                         stacked.data[p])
            np.testing.assert_allclose(got, want, atol=1e-6)


def test_partition_execute_gather_mode_random():
    A = random_coo(3, (64, 64), density=0.15)
    r, c, v = np.asarray(A.row), np.asarray(A.col), np.asarray(A.data)
    plan = plan_partition(r, c, v, (64, 64), 8)
    assert plan.halo_mode == "gather"
    local, remote = partition_execute_jit(r, c, v, plan=plan)
    D = _dense((64, 64), r, c, v)
    # reassemble: local blocks on the diagonal, remote with global columns
    got = np.zeros((64, 64))
    for p in range(8):
        got[p * 8:(p + 1) * 8, p * 8:(p + 1) * 8] += _dense(
            (8, 8), local.row[p], local.col[p], local.data[p])
        got[p * 8:(p + 1) * 8, :] += _dense(
            (8, 64), remote.row[p], remote.col[p], remote.data[p])
    np.testing.assert_allclose(got, D, atol=1e-6)


@pytest.mark.parametrize("nkeys", [1, 3, 9])
def test_group_ranks_is_position_within_key(nkeys):
    """Each entry's rank is its position among the earlier entries with
    its key, in input order; a key outside [0, nkeys) ranks 0."""
    key = np.random.default_rng(nkeys).integers(0, nkeys + 1, 500)
    want = np.zeros_like(key)
    for k in range(nkeys):
        idx = np.flatnonzero(key == k)
        want[idx] = np.arange(len(idx))
    got = np.asarray(group_ranks(jnp.asarray(key, jnp.int32), nkeys))
    np.testing.assert_array_equal(got, want)


def test_batched_build_constant_planned_pulls():
    """Acceptance: the batched build pipeline performs no per-shard host
    transfers — the planned-pull count is independent of shard count, and
    nothing else crosses device->host (transfer guard disallows it)."""
    from repro.tuning.cache import SelectionCache
    from repro.tuning.policy import FormatPolicy

    prob = hpcg.generate_problem(4, 4, 8)
    candidates = (Format.COO, Format.CSR, Format.DIA, Format.ELL)
    pulls = {}
    for nshards in (2, 8):
        import tempfile
        cache = SelectionCache(os.path.join(tempfile.mkdtemp(), "sel.json"))
        policy = FormatPolicy("cached", candidates=candidates, cache=cache)
        plan = plan_partition(prob.row, prob.col, prob.val, prob.shape, nshards)
        # planned_pulls_scope: order-independent count of the pulls this
        # block performs, regardless of what ran earlier in the suite
        with planned_pulls_scope() as scope, \
                jax.transfer_guard_device_to_host("disallow"):
            local, remote = partition_execute_jit(prob.row, prob.col,
                                                  prob.val, plan=plan)
            for part in (local, remote):
                ids = policy.select_batch(part)
                assert ids.shape == (nshards,)
                for fmt in candidates:
                    sp = plan_switch_batch(part, fmt)
                    out = convert_execute_batch(part, sp)
                    jax.block_until_ready(jax.tree_util.tree_leaves(out))
        pulls[nshards] = scope.count
    assert pulls[2] == pulls[8], pulls


# ---------------------------------------------------------------------------
# Batched symbolic phase (shared plans across shards)
# ---------------------------------------------------------------------------


def _stack_coos(mats):
    cap = max(m.capacity for m in mats)
    def pad(a):
        return np.pad(np.asarray(a), (0, cap - a.shape[0]))
    return COO(jnp.asarray(np.stack([pad(m.row) for m in mats])),
               jnp.asarray(np.stack([pad(m.col) for m in mats])),
               jnp.asarray(np.stack([pad(m.data) for m in mats])),
               mats[0].shape, cap)


def test_batch_dia_plan_unions_and_dedupes_offsets():
    """Satellite regression: heterogeneous per-shard diagonal sets used to
    be padded with a duplicated live offset; the shared batch plan is the
    deduped union, and every shard converts exactly."""
    from repro.core.formats import banded_coo

    a = banded_coo((32, 32), [0])              # 1 diagonal
    b = banded_coo((32, 32), [-3, 0, 5])       # 3 diagonals
    stacked = _stack_coos([a, b])
    plan = plan_switch_batch(stacked, Format.DIA)
    assert plan.dia_offsets == (-3, 0, 5)
    assert len(set(plan.dia_offsets)) == len(plan.dia_offsets)
    out = convert_execute_batch(stacked, plan)
    for i, src in enumerate((a, b)):
        part = jax.tree.map(lambda x, i=i: x[i], out)
        np.testing.assert_allclose(to_dense_np(part), to_dense_np(src),
                                   atol=1e-6)
    # explicit duplicate offsets hints are deduped too (single + batch)
    from repro.core import plan_switch
    assert plan_switch(a, Format.DIA, offsets=[0, 0, 5]).dia_offsets == (0, 5)
    assert plan_switch_batch(stacked, Format.DIA,
                             offsets=[5, 0, 0, -3]).dia_offsets == (-3, 0, 5)


def test_stale_plan_raises_instead_of_dropping():
    """Review fix: a reused DistPlan whose capacities or halo width no
    longer fit the triplets must fail loudly, not silently drop entries in
    the guard-slot scatter."""
    prob = hpcg.generate_problem(4, 4, 8)
    mesh = make_mesh((1,), ("rows",))
    plan = plan_partition(prob.row, prob.col, prob.val, prob.shape, 1)
    # denser matrix than the plan was made for -> capacity overflow
    import dataclasses
    small = dataclasses.replace(plan, local_cap=7)
    with pytest.raises(ValueError, match="stale DistPlan"):
        build_dist_matrix(prob.row, prob.col, prob.val, prob.shape, mesh,
                          "rows", plan=small)
    # wrong P still raises the original mismatch error
    with pytest.raises(ValueError, match="plan is for"):
        build_dist_matrix(prob.row, prob.col, prob.val, prob.shape, mesh,
                          "rows", plan=dataclasses.replace(plan, nshards=2))


def test_hpcg_partition_problem_matches_general_path():
    """slab-aware fast path == general plan_partition + partition_execute."""
    prob = hpcg.generate_problem(4, 4, 8)
    l_gen, r_gen, plan_gen = _stacked_parts(prob, 4)
    l_slab, r_slab, plan_slab = hpcg.partition_problem(prob, 4)
    assert (plan_slab.mp, plan_slab.hw, plan_slab.halo_mode) == \
           (plan_gen.mp, plan_gen.hw, plan_gen.halo_mode)
    assert (plan_slab.local_cap, plan_slab.remote_cap) == \
           (plan_gen.local_cap, plan_gen.remote_cap)
    for a, b in ((l_gen, l_slab), (r_gen, r_slab)):
        for p in range(4):
            np.testing.assert_allclose(
                _dense(a.shape, a.row[p], a.col[p], a.data[p]),
                _dense(b.shape, b.row[p], b.col[p], b.data[p]), atol=1e-6)
    with pytest.raises(ValueError, match="nz % P"):
        hpcg.slab_plan(prob, 3)


def test_reused_plan_replans_on_live_pattern_change():
    """Review fix: memoised format plans are fingerprinted against the live
    pattern — a numeric update that turns zeros live must re-plan, not
    silently convert with stale DIA offsets / ELL widths."""
    mesh = make_mesh((1,), ("rows",))
    row = np.arange(16).repeat(2)
    col = np.concatenate([np.stack([np.arange(16),
                                    (np.arange(16) + 1) % 16]).T.ravel()])
    val = np.where(np.arange(32) % 2 == 0, 1.0, 0.0).astype(np.float32)
    A = build_dist_matrix(row, col, val, (16, 16), mesh, "rows",
                          mode="multiformat", tune="analytic")
    assert A.plan.pattern_sig is not None
    # same pattern, same values -> memoised plans reused, result correct
    A2 = build_dist_matrix(row, col, val, (16, 16), mesh, "rows",
                          mode="multiformat", tune="analytic", plan=A.plan)
    # off-diagonal entries become live: plan fingerprint mismatch -> re-plan
    val2 = np.ones(32, np.float32)
    A3 = build_dist_matrix(row, col, val2, (16, 16), mesh, "rows",
                           mode="multiformat", tune="analytic", plan=A.plan)
    x = distribute_vector(np.ones(16, np.float32), mesh, "rows")
    D = _dense((16, 16), row, col, val2)
    for part in ("local", "remote"):
        ids = np.asarray(getattr(A3, part).active_id)
        assert ids.shape == (1,)
    y = np.asarray(dist_spmv(A3, x, mesh))
    np.testing.assert_allclose(y, D @ np.ones(16), atol=1e-5)
    # and every resident variant is correct, not just the active one
    for fmt in (Format.COO, Format.CSR, Format.DIA, Format.ELL):
        from repro.core.distributed import activate_dist
        Af = activate_dist(activate_dist(A3, "local", fmt), "remote", fmt)
        yf = np.asarray(dist_spmv(Af, x, mesh))
        np.testing.assert_allclose(yf, D @ np.ones(16), atol=1e-5, err_msg=fmt.name)


def test_plan_switch_batch_ell_overflow_raises():
    """Review fix: an explicit undersized k must raise (parity with
    plan_switch), not silently drop row overflow."""
    A = random_coo(6, (32, 32), density=0.3)
    stacked = _stack_coos([A, A])
    with pytest.raises(ValueError, match="overflow"):
        plan_switch_batch(stacked, Format.ELL, k=2)
    assert plan_switch_batch(stacked, Format.ELL, k=2, check=False).ell_k == 2


def test_batch_plans_match_per_shard_unions():
    prob = hpcg.generate_problem(4, 4, 8)
    local, _, _ = _stacked_parts(prob, 4)
    kplan = plan_switch_batch(local, Format.ELL)
    per_shard_k = []
    for p in range(4):
        rows = np.asarray(local.row[p])[np.asarray(local.data[p]) != 0]
        per_shard_k.append(np.bincount(rows, minlength=local.shape[0]).max())
    assert kplan.ell_k == max(per_shard_k)
    hplan = plan_switch_batch(local, Format.HYB)
    assert hplan.ell_k >= 1 and hplan.hyb_coo_capacity >= 1
    out = convert_execute_batch(local, hplan)
    for p in range(4):
        part = jax.tree.map(lambda x, p=p: x[p], out)
        want = _dense(local.shape, local.row[p], local.col[p], local.data[p])
        np.testing.assert_allclose(to_dense_np(part), want, atol=1e-5)


def test_select_batch_matches_per_shard_select():
    from repro.tuning.policy import FormatPolicy

    prob = hpcg.generate_problem(4, 4, 8)
    local, remote, _ = _stacked_parts(prob, 4)
    for mode in ("ml", "analytic"):
        policy = FormatPolicy(mode)
        for part in (local, remote):
            ids = policy.select_batch(part)
            single = [policy.select(jax.tree.map(lambda a, p=p: a[p], part)).best
                      for p in range(4)]
            assert [policy.candidates[i] for i in ids] == single, mode


def test_select_batch_cached_warm_hits(tmp_path):
    from repro.tuning.cache import SelectionCache
    from repro.tuning.policy import FormatPolicy

    prob = hpcg.generate_problem(4, 4, 8)
    local, _, _ = _stacked_parts(prob, 4)
    cache = SelectionCache(str(tmp_path / "sel.json"))
    policy = FormatPolicy("cached", cache=cache)
    ids = policy.select_batch(local)
    assert len(cache) >= 1
    ids2 = FormatPolicy("cached", cache=SelectionCache(str(tmp_path / "sel.json"))
                        ).select_batch(local)
    np.testing.assert_array_equal(ids, ids2)


def test_batch_features_match_host_featuriser():
    from repro.tuning.features import PatternFeatures, batch_features

    prob = hpcg.generate_problem(4, 4, 8)
    local, remote, _ = _stacked_parts(prob, 4)
    for part in (local, remote):
        feats = batch_features(part)
        for p, f in enumerate(feats):
            ref = PatternFeatures.from_coo(
                COO(part.row[p], part.col[p], part.data[p], part.shape,
                    int(part.row.shape[1])))
            np.testing.assert_allclose(f.vector(), ref.vector(),
                                       rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# Single-device mesh (in-process)
# ---------------------------------------------------------------------------

def test_dist_spmv_single_shard():
    mesh = make_mesh((1,), ("rows",))
    prob = hpcg.generate_problem(4, 4, 4)
    A = build_dist_matrix(prob.row, prob.col, prob.val, prob.shape, mesh, "rows",
                          local_format=Format.DIA, remote_format=Format.COO)
    x = distribute_vector(np.ones(prob.shape[0], np.float32), mesh, "rows")
    y = dist_spmv(A, x, mesh)
    D = np.zeros(prob.shape)
    np.add.at(D, (prob.row, prob.col), prob.val)
    np.testing.assert_allclose(np.asarray(y), D @ np.ones(prob.shape[0]),
                               rtol=1e-5, atol=1e-5)


def test_cg_single_device():
    prob = hpcg.generate_problem(6, 6, 6)
    from repro.core import convert, to_coo
    A = convert(hpcg.to_coo(prob), Format.CSR)
    b = jnp.asarray(hpcg.rhs_for_ones(prob))
    from repro.core import spmv
    res = cg(lambda v: spmv(A, v), b, tol=1e-7, maxiter=300)
    np.testing.assert_allclose(np.asarray(res.x), 1.0, rtol=1e-3, atol=1e-3)


def test_cg_fixed_iters_runs():
    prob = hpcg.generate_problem(4, 4, 4)
    from repro.core import convert, spmv
    A = convert(hpcg.to_coo(prob), Format.ELL)
    b = jnp.asarray(hpcg.rhs_for_ones(prob))
    res = cg_fixed_iters(lambda v: spmv(A, v), b, iters=30)
    assert np.isfinite(float(res.resnorm))


# ---------------------------------------------------------------------------
# 8-shard SPMD (subprocess)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode,lf,rf", [
    ("uniform", "CSR", "CSR"),
    ("uniform", "DIA", "COO"),
    ("multiformat", "CSR", "CSR"),
])
def test_dist_spmv_8shards(mode, lf, rf):
    out = _run_subprocess(f"""
        mesh = make_mesh((8,), ("rows",))
        prob = hpcg.generate_problem(8, 8, 16)
        D = np.zeros(prob.shape); np.add.at(D, (prob.row, prob.col), prob.val)
        x_np = np.random.default_rng(0).standard_normal(prob.shape[0]).astype(np.float32)
        A = build_dist_matrix(prob.row, prob.col, prob.val, prob.shape, mesh,
                              "rows", local_format=Format.{lf},
                              remote_format=Format.{rf}, mode="{mode}")
        x = distribute_vector(x_np, mesh, "rows")
        y = jax.jit(lambda a, v: dist_spmv(a, v, mesh))(A, x)
        err = abs(np.asarray(y) - D @ x_np).max() / abs(D @ x_np).max()
        assert err < 1e-5, err
        print("OK", err)
    """)
    assert "OK" in out


@pytest.mark.parametrize("nshards", [1, 2, 4])
def test_dist_row_sharded_dia_matches_reference(nshards):
    """A uniform DIA/COO build stores its parts row-sharded: one lane-tiled
    table over every shard's rows (slabs of 288, 144 or 72 rows, padded to
    whole lanes), offsets and COO arrays end to end. SpMV on the reference
    and Pallas paths, and CG, match the dense reference, and every kernel
    call takes its table as stored."""
    out = _run_subprocess(f"""
        from repro.obs import metrics
        P = {nshards}
        mesh = make_mesh((P,), ("rows",), devices=jax.devices()[:P])
        prob = hpcg.generate_problem(6, 6, 8)
        D = np.zeros(prob.shape); np.add.at(D, (prob.row, prob.col), prob.val)
        A = build_dist_matrix(prob.row, prob.col, prob.val, prob.shape, mesh,
                              "rows", local_format=Format.DIA,
                              remote_format=Format.COO)
        tables = [A.local] + ([A.boundary] if A.split else [])
        assert A.split == (P > 1), A
        for t in tables:
            assert t.data.shape == (27, P * -(-A.mp // 128), 128), t.data.shape
            assert t.offsets.shape == (P * 27,)
        assert A.remote.row.ndim == 1 and A.remote.row.shape[0] % P == 0
        x_np = np.random.default_rng(3).standard_normal(prob.shape[0]).astype(np.float32)
        x = distribute_vector(x_np, mesh, "rows")
        for backend in ("ref", "pallas"):
            with metrics.scope() as s:
                y = jax.jit(lambda a, v: dist_spmv(a, v, mesh, backend=backend))(A, x)
            err = abs(np.asarray(y) - D @ x_np).max() / abs(D @ x_np).max()
            assert err < 1e-5, (backend, err)
            tiled = len(tables) if backend == "pallas" else 0
            assert s.delta("kernel.dia.table_tiled") == tiled
            assert s.delta("kernel.dia.table_relaid") == 0
        b = distribute_vector(hpcg.rhs_for_ones(prob), mesh, "rows")
        res = jax.jit(lambda a, bb: cg(operator(a, mesh, backend="pallas"), bb,
                                       tol=1e-7, maxiter=300))(A, b)
        err = abs(np.asarray(res.x) - 1.0).max()
        assert err < 1e-3, err
        print("OK", int(res.iters), err)
    """)
    assert "OK" in out


def test_dist_cg_8shards_converges_to_ones():
    out = _run_subprocess("""
        mesh = make_mesh((8,), ("rows",))
        prob = hpcg.generate_problem(8, 8, 16)
        A = build_dist_matrix(prob.row, prob.col, prob.val, prob.shape, mesh,
                              "rows", local_format=Format.DIA,
                              remote_format=Format.COO)
        b = distribute_vector(hpcg.rhs_for_ones(prob), mesh, "rows")
        res = jax.jit(lambda a, bb: cg(lambda v: dist_spmv(a, v, mesh), bb,
                                       tol=1e-7, maxiter=300))(A, b)
        err = abs(np.asarray(res.x) - 1.0).max()
        assert err < 1e-3, err
        print("OK", int(res.iters), err)
    """)
    assert "OK" in out


def test_dist_matches_single_device_result():
    """Invariant: distribution must not change the math."""
    out = _run_subprocess("""
        from repro.core import convert, spmv
        mesh = make_mesh((8,), ("rows",))
        prob = hpcg.generate_problem(6, 6, 8)
        x_np = np.random.default_rng(1).standard_normal(prob.shape[0]).astype(np.float32)
        A1 = convert(hpcg.to_coo(prob), Format.CSR)
        y1 = np.asarray(spmv(A1, jnp.asarray(x_np)))
        A8 = build_dist_matrix(prob.row, prob.col, prob.val, prob.shape, mesh,
                               "rows", mode="multiformat")
        y8 = np.asarray(dist_spmv(A8, distribute_vector(x_np, mesh, "rows"), mesh))
        err = abs(y1 - y8).max() / abs(y1).max()
        assert err < 1e-5, err
        print("OK", err)
    """)
    assert "OK" in out


@pytest.mark.parametrize("tune", ["cached", "ml"])
def test_dist_multiformat_policy_8shards(tune, tmp_path):
    """Multiformat build with the batched cached/ml policies: correct SpMV
    vs the dense oracle, and the whole build runs with device->host
    transfers disallowed (zero unplanned pulls, full stack)."""
    out = _run_subprocess(f"""
        mesh = make_mesh((8,), ("rows",))
        prob = hpcg.generate_problem(8, 8, 16)
        D = np.zeros(prob.shape); np.add.at(D, (prob.row, prob.col), prob.val)
        x_np = np.random.default_rng(2).standard_normal(prob.shape[0]).astype(np.float32)
        with jax.transfer_guard_device_to_host("disallow"):
            A = build_dist_matrix(prob.row, prob.col, prob.val, prob.shape,
                                  mesh, "rows", mode="multiformat",
                                  tune="{tune}")
        y = np.asarray(dist_spmv(A, distribute_vector(x_np, mesh, "rows"), mesh))
        err = abs(y - D @ x_np).max() / abs(D @ x_np).max()
        assert err < 1e-5, err
        print("OK", err)
    """, env={"REPRO_TUNING_CACHE": str(tmp_path / "selections.json")})
    assert "OK" in out


def test_dist_activate_roundtrip_8shards():
    out = _run_subprocess("""
        mesh = make_mesh((8,), ("rows",))
        prob = hpcg.generate_problem(8, 8, 16)
        D = np.zeros(prob.shape); np.add.at(D, (prob.row, prob.col), prob.val)
        x_np = np.random.default_rng(3).standard_normal(prob.shape[0]).astype(np.float32)
        x = distribute_vector(x_np, mesh, "rows")
        ref = D @ x_np
        A = build_dist_matrix(prob.row, prob.col, prob.val, prob.shape, mesh,
                              "rows", mode="multiformat", tune="analytic")
        orig = np.asarray(A.local.active_id)
        check = lambda a: abs(np.asarray(dist_spmv(a, x, mesh)) - ref).max() / abs(ref).max()
        assert check(A) < 1e-5
        A2 = activate_dist(A, "local", Format.CSR)       # uniform switch
        assert (np.asarray(A2.local.active_id) == 1).all()
        assert check(A2) < 1e-5
        A3 = activate_dist(A2, "local", orig)            # per-shard ids back
        assert (np.asarray(A3.local.active_id) == orig).all()
        assert check(A3) < 1e-5
        A4 = activate_dist(A3, "remote", Format.COO)
        assert check(A4) < 1e-5
        print("OK")
    """)
    assert "OK" in out


def test_dist_overlapped_spmv_random_gather_8shards():
    """Overlap refactor must hold for the all_gather (irregular) path."""
    out = _run_subprocess("""
        from repro.core import random_coo
        mesh = make_mesh((8,), ("rows",))
        A0 = random_coo(7, (256, 256), density=0.08)
        r, c, v = np.asarray(A0.row), np.asarray(A0.col), np.asarray(A0.data)
        D = np.zeros((256, 256)); np.add.at(D, (r, c), v)
        x_np = np.random.default_rng(4).standard_normal(256).astype(np.float32)
        A = build_dist_matrix(r, c, v, (256, 256), mesh, "rows",
                              mode="multiformat", tune="analytic")
        assert A.halo_mode == "gather", A
        y = np.asarray(dist_spmv(A, distribute_vector(x_np, mesh, "rows"), mesh))
        err = abs(y - D @ x_np).max() / abs(D @ x_np).max()
        assert err < 1e-5, err
        print("OK", err)
    """)
    assert "OK" in out


def test_dist_block_diagonal_skips_exchange_8shards():
    out = _run_subprocess("""
        mesh = make_mesh((8,), ("rows",))
        row = col = np.arange(64); val = np.arange(1, 65, dtype=np.float32)
        A = build_dist_matrix(row, col, val, (64, 64), mesh, "rows")
        assert A.remote_empty and A.hw == 0, A
        x_np = np.ones(64, np.float32)
        y = np.asarray(dist_spmv(A, distribute_vector(x_np, mesh, "rows"), mesh))
        np.testing.assert_allclose(y, val)
        print("OK")
    """)
    assert "OK" in out


def test_dist_cg_slab_plan_auto_backend_8shards():
    """HPCG end-to-end on the slab-aware fast path with operator(auto)."""
    out = _run_subprocess("""
        mesh = make_mesh((8,), ("rows",))
        prob = hpcg.generate_problem(8, 8, 16)
        plan = hpcg.slab_plan(prob, 8)
        A = build_dist_matrix(prob.row, prob.col, prob.val, prob.shape, mesh,
                              "rows", local_format=Format.DIA,
                              remote_format=Format.CSR, plan=plan)
        b = distribute_vector(hpcg.rhs_for_ones(prob), mesh, "rows")
        res = jax.jit(lambda a, bb: cg(operator(a, mesh), bb,
                                       tol=1e-7, maxiter=300))(A, b)
        err = abs(np.asarray(res.x) - 1.0).max()
        assert err < 1e-3, err
        print("OK", int(res.iters), err)
    """)
    assert "OK" in out
