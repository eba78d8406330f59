"""Interior/boundary overlap split, persistent DistPlans, and repro.env.

The split parity oracle is the unsplit path: ``split_local_execute`` must
partition every live local entry into exactly one of interior/boundary
(dense sums match per shard), with interior rows having no live remote
entry — so the interior SpMV is provably independent of the halo.
Multi-device behaviour (the overlapped ``dist_spmv`` itself, per-split
multiformat selection) runs in an 8-forced-host-device subprocess, same
harness as ``test_distributed``.
"""
import os
import subprocess
import sys
import textwrap
import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import Format, hpcg
from repro.core.convert import (SwitchPlan, convert_execute_batch,
                                planned_pulls_scope, plan_switch_batch)
from repro.core.distributed import (DistPlan, _split_caps, partition_coo,
                                    partition_execute_jit, plan_partition,
                                    split_local_execute_jit)
from repro.obs import metrics

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run_subprocess(body: str, env=None, ndev: int = 8):
    script = textwrap.dedent("""
        import sys
        sys.path.insert(0, %r)
        from repro import env
        env.apply(host_devices=%d)
        import os
        import numpy as np
        import jax, jax.numpy as jnp
        from repro.core import hpcg, Format
        from repro.core.distributed import (activate_dist, build_dist_matrix,
                                            dist_spmv, dist_spmv_phase,
                                            distribute_vector)
        from repro.launch.mesh import make_mesh
    """ % (os.path.abspath(SRC), ndev)) + textwrap.dedent(body)
    full_env = dict(os.environ, **(env or {}))
    res = subprocess.run([sys.executable, "-c", script], capture_output=True,
                         text=True, timeout=600, env=full_env)
    assert res.returncode == 0, f"stdout:\n{res.stdout}\nstderr:\n{res.stderr}"
    return res.stdout


def _dense(shape, row, col, val):
    D = np.zeros(shape)
    np.add.at(D, (np.asarray(row), np.asarray(col)), np.asarray(val))
    return D


def _random_triplets(seed, n, m, band=None):
    rng = np.random.default_rng(seed)
    row = rng.integers(0, n, m)
    if band is None:
        col = rng.integers(0, n, m)
    else:
        col = np.clip(row + rng.integers(-band, band + 1, m), 0, n - 1)
    val = rng.standard_normal(m).astype(np.float32)
    return row, col, val


def _split_and_check(row, col, val, shape, nshards, force_split=False):
    """Run the split scatter and assert the structural invariants; returns
    (plan, interior, boundary, local, remote)."""
    plan = plan_partition(row, col, val, shape, nshards)
    icap, bcap = _split_caps(row, col, val, plan.mp, nshards)
    local, remote = partition_execute_jit(row, col, val, plan=plan)
    interior, boundary = split_local_execute_jit(local, remote, mp=plan.mp,
                                                 icap=icap, bcap=bcap)
    mp = plan.mp
    for p in range(nshards):
        dl = _dense((mp, mp), local.row[p], local.col[p], local.data[p])
        di = _dense((mp, mp), interior.row[p], interior.col[p],
                    interior.data[p])
        db = _dense((mp, mp), boundary.row[p], boundary.col[p],
                    boundary.data[p])
        # the split is a partition of the local block: nothing lost, nothing
        # duplicated
        np.testing.assert_allclose(di + db, dl, rtol=1e-6, atol=1e-6)
        # interior rows have no live remote entry (their SpMV never waits
        # on the halo) and no live boundary entry (the halves are disjoint)
        rrow = np.asarray(remote.row[p])
        rlive = np.asarray(remote.data[p]) != 0
        brows = np.zeros(mp, bool)
        brows[rrow[rlive]] = True
        ilive = np.asarray(interior.data[p]) != 0
        assert not brows[np.asarray(interior.row[p])[ilive]].any()
        blive = np.asarray(boundary.data[p]) != 0
        assert brows[np.asarray(boundary.row[p])[blive]].all()
    return plan, interior, boundary, local, remote


# ---------------------------------------------------------------------------
# Split scatter invariants (host+device, single-device view)
# ---------------------------------------------------------------------------


def test_split_parity_stencil():
    prob = hpcg.generate_problem(4, 4, 8)
    _split_and_check(prob.row, prob.col, prob.val, prob.shape, 4)


def test_split_parity_random_gather():
    row, col, val = _random_triplets(0, 64, 700)  # random -> gather mode
    plan = plan_partition(row, col, val, (64, 64), 4)
    assert plan.halo_mode == "gather"
    _split_and_check(row, col, val, (64, 64), 4)


def test_split_parity_banded_neighbor():
    row, col, val = _random_triplets(1, 64, 900, band=10)
    plan = plan_partition(row, col, val, (64, 64), 4)
    assert plan.halo_mode == "neighbor"
    _split_and_check(row, col, val, (64, 64), 4)


def test_split_block_diagonal_hw0_all_interior():
    """A statically-empty remote part (hw=0) has no boundary rows: a forced
    split must put every live entry in the interior container."""
    n = 32
    row = col = np.arange(n)
    val = np.ones(n, np.float32)
    plan = plan_partition(row, col, val, (n, n), 4)
    assert plan.remote_empty and plan.hw == 0
    icap, bcap = _split_caps(row, col, val, plan.mp, 4)
    assert bcap == 1  # floor capacity, no real boundary entries
    local, remote = partition_execute_jit(row, col, val, plan=plan)
    interior, boundary = split_local_execute_jit(local, remote, mp=plan.mp,
                                                 icap=icap, bcap=bcap)
    assert int((np.asarray(boundary.data) != 0).sum()) == 0
    assert int((np.asarray(interior.data) != 0).sum()) == n


def test_split_caps_count_live_entries_only():
    """Dead (val == 0) entries are dropped by the device split, so the cap
    scan must not count them either — or caps (and ELL widths downstream)
    would be inflated by padding."""
    prob = hpcg.generate_problem(4, 4, 4)
    icap, bcap = _split_caps(prob.row, prob.col, prob.val, prob.shape[0] // 2, 2)
    val0 = prob.val.copy()
    val0[::2] = 0.0
    icap0, bcap0 = _split_caps(prob.row, prob.col, val0, prob.shape[0] // 2, 2)
    assert icap0 < icap and bcap0 <= bcap


def test_stale_split_caps_raise():
    """Reusing a plan whose split caps are too small for denser triplets
    must fail loudly, not silently drop entries (same contract as the
    partition caps)."""
    from repro.core.distributed import _check_plan_fits

    prob = hpcg.generate_problem(4, 4, 8)
    plan = plan_partition(prob.row, prob.col, prob.val, prob.shape, 4)
    icap, bcap = _split_caps(prob.row, prob.col, prob.val, plan.mp, 4)
    import dataclasses
    stale = dataclasses.replace(plan, interior_cap=max(1, icap // 2),
                                boundary_cap=bcap)
    with pytest.raises(ValueError, match="stale DistPlan"):
        _check_plan_fits(prob.row, prob.col, stale, val=prob.val)
    ok = dataclasses.replace(plan, interior_cap=icap, boundary_cap=bcap)
    _check_plan_fits(prob.row, prob.col, ok, val=prob.val)  # no raise


def test_slab_plan_carries_split_caps():
    """The analytic z-slab plan precomputes the overlap caps (boundary =
    the slab's first/last x-y planes), so a split build does no extra
    host scan."""
    prob = hpcg.generate_problem(4, 4, 8)
    plan = hpcg.slab_plan(prob, 4)
    icap, bcap = _split_caps(prob.row, prob.col, prob.val, plan.mp, 4)
    assert (plan.interior_cap, plan.boundary_cap) == (icap, bcap)
    p1 = hpcg.slab_plan(prob, 1)
    assert p1.interior_cap is None and p1.remote_empty


# ---------------------------------------------------------------------------
# Transfer discipline: the 3-way pipeline stays device-resident
# ---------------------------------------------------------------------------


def test_three_way_split_constant_planned_pulls():
    """The split scatter plus per-split batched selection/conversion adds
    no per-shard host transfers: the planned-pull count is independent of
    the shard count, and nothing else crosses device->host."""
    import tempfile

    from repro.tuning.cache import SelectionCache
    from repro.tuning.policy import FormatPolicy

    prob = hpcg.generate_problem(4, 4, 8)
    candidates = (Format.COO, Format.CSR, Format.DIA, Format.ELL)
    pulls = {}
    for nshards in (2, 8):
        cache = SelectionCache(os.path.join(tempfile.mkdtemp(), "sel.json"))
        policy = FormatPolicy("cached", candidates=candidates, cache=cache)
        plan = plan_partition(prob.row, prob.col, prob.val, prob.shape,
                              nshards)
        icap, bcap = _split_caps(prob.row, prob.col, prob.val, plan.mp,
                                 nshards)
        with planned_pulls_scope() as scope, \
                jax.transfer_guard_device_to_host("disallow"):
            local, remote = partition_execute_jit(prob.row, prob.col,
                                                  prob.val, plan=plan)
            interior, boundary = split_local_execute_jit(
                local, remote, mp=plan.mp, icap=icap, bcap=bcap)
            for part in (interior, boundary, remote):
                ids = policy.select_batch(part)
                assert ids.shape == (nshards,)
                for fmt in candidates:
                    sp = plan_switch_batch(part, fmt)
                    out = convert_execute_batch(part, sp)
                    jax.block_until_ready(jax.tree_util.tree_leaves(out))
        pulls[nshards] = scope.count
    assert pulls[2] == pulls[8], pulls


# ---------------------------------------------------------------------------
# DistPlan persistence
# ---------------------------------------------------------------------------


def test_dist_plan_json_roundtrip_bare():
    prob = hpcg.generate_problem(4, 4, 8)
    plan = plan_partition(prob.row, prob.col, prob.val, prob.shape, 4)
    assert DistPlan.from_json(plan.to_json()) == plan


def test_dist_plan_json_roundtrip_enriched():
    """Round-trip with everything a production plan carries: split caps,
    per-candidate SwitchPlans for all three parts, pattern fingerprint."""
    import dataclasses

    prob = hpcg.generate_problem(4, 4, 8)
    from repro.core.distributed import plan_dist_formats

    plan = plan_partition(prob.row, prob.col, prob.val, prob.shape, 4)
    icap, bcap = _split_caps(prob.row, prob.col, prob.val, plan.mp, 4)
    plan = dataclasses.replace(plan, interior_cap=icap, boundary_cap=bcap,
                               pattern_sig="deadbeef")
    local, remote = partition_execute_jit(prob.row, prob.col, prob.val,
                                          plan=plan)
    interior, boundary = split_local_execute_jit(local, remote, mp=plan.mp,
                                                 icap=icap, bcap=bcap)
    plan = plan_dist_formats(interior, remote, plan,
                             (Format.COO, Format.CSR, Format.DIA, Format.ELL),
                             boundary=boundary)
    rt = DistPlan.from_json(plan.to_json())
    assert rt == plan
    assert rt.interior_plans is not None and rt.boundary_plans is not None
    assert all(isinstance(p, SwitchPlan) for p in rt.interior_plans)


def test_switch_plan_json_roundtrip():
    sp = SwitchPlan(target=Format.DIA, dia_offsets=(-4, -1, 0, 1, 4))
    assert SwitchPlan.from_json(sp.to_json()) == sp
    sp2 = SwitchPlan(target=Format.ELL, ell_k=7)
    assert SwitchPlan.from_json(sp2.to_json()) == sp2


def test_plan_cache_restart_skips_planning(tmp_path):
    """A fresh SelectionCache instance over the same store (the restart)
    must hit the persisted plan: distplan.cache_hit increments, the loaded
    plan carries the memoised format plans, and the build still matches
    the from-scratch result."""
    body = """
    import tempfile, json
    from repro.tuning.cache import SelectionCache
    from repro.obs import metrics

    mesh = make_mesh((8,), ("rows",))
    prob = hpcg.generate_problem(4, 4, 8)
    x = distribute_vector(np.ones(prob.shape[0], np.float32), mesh, "rows")
    path = os.environ["PLAN_CACHE_PATH"]
    kw = dict(mode="multiformat", tune="analytic")

    with metrics.scope() as s:
        A = build_dist_matrix(prob.row, prob.col, prob.val, prob.shape, mesh,
                              "rows", plan_cache=SelectionCache(path), **kw)
        assert s.delta("distplan.cache_miss") == 1, metrics.snapshot()
        assert s.delta("distplan.cache_hit") == 0
    y0 = np.asarray(dist_spmv(A, x, mesh))

    # the "restart": a fresh cache object over the same on-disk store
    with metrics.scope() as s:
        B = build_dist_matrix(prob.row, prob.col, prob.val, prob.shape, mesh,
                              "rows", plan_cache=SelectionCache(path), **kw)
        assert s.delta("distplan.cache_hit") == 1, metrics.snapshot()
        assert s.delta("distplan.cache_miss") == 0
    assert B.plan.interior_plans is not None  # planning was skipped, not redone
    assert B.plan.pattern_sig == A.plan.pattern_sig
    y1 = np.asarray(dist_spmv(B, x, mesh))
    np.testing.assert_allclose(y1, y0, rtol=1e-5, atol=1e-5)
    print("OK")
    """
    out = _run_subprocess(
        body, env={"PLAN_CACHE_PATH": str(tmp_path / "plans.json")})
    assert "OK" in out


# ---------------------------------------------------------------------------
# Overlapped dist_spmv (8 forced host devices, subprocess)
# ---------------------------------------------------------------------------


# Split vs unsplit vs dense oracle, plus the phase decomposition:
# interior + boundary == local, and the production result is identical
# either way.
_SPLIT_PARITY = """
    mesh = make_mesh((%(ndev)d,), ("rows",))
    prob = hpcg.generate_problem(*%(grid)r)
    n = prob.shape[0]
    D = np.zeros((n, n))
    np.add.at(D, (prob.row, prob.col), prob.val)
    xh = np.arange(n, dtype=np.float32) / n
    x = distribute_vector(xh, mesh, "rows")
    ref = D @ xh

    A = build_dist_matrix(prob.row, prob.col, prob.val, prob.shape, mesh,
                          "rows", local_format=Format.CSR,
                          remote_format=Format.COO)
    assert A.split, A
    B = build_dist_matrix(prob.row, prob.col, prob.val, prob.shape, mesh,
                          "rows", local_format=Format.CSR,
                          remote_format=Format.COO, split=False)
    assert not B.split, B
    ya = np.asarray(dist_spmv(A, x, mesh))
    yb = np.asarray(dist_spmv(B, x, mesh))
    np.testing.assert_allclose(ya, ref, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(ya, yb, rtol=1e-5, atol=1e-5)

    loc = np.asarray(dist_spmv_phase(A, x, mesh, phase="local"))
    intr = np.asarray(dist_spmv_phase(A, x, mesh, phase="interior"))
    bnd = np.asarray(dist_spmv_phase(A, x, mesh, phase="boundary"))
    exc = np.asarray(dist_spmv_phase(A, x, mesh, phase="exchange"))
    np.testing.assert_allclose(intr + bnd, loc, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(loc + exc, ya, rtol=1e-4, atol=1e-4)
    try:
        dist_spmv_phase(B, x, mesh, phase="interior")
    except ValueError as e:
        assert "split" in str(e)
    else:
        raise AssertionError("interior phase on unsplit matrix must raise")
    print("OK")
    """


def test_dist_split_spmv_parity_8shards():
    body = _SPLIT_PARITY % {"ndev": 8, "grid": (4, 4, 8)}
    assert "OK" in _run_subprocess(body)


def test_dist_split_spmv_parity_16shards():
    """One z-plane per shard, so every row reaches a neighbouring shard."""
    body = _SPLIT_PARITY % {"ndev": 16, "grid": (8, 8, 16)}
    assert "OK" in _run_subprocess(body, ndev=16)


def test_dist_split_multiformat_and_boundary_activate_8shards():
    """Per-split multiformat selection: three independent SwitchDynamic
    parts, runtime activate() of the boundary part preserves results."""
    body = """
    from repro.core.dynamic import SwitchDynamicMatrix

    mesh = make_mesh((8,), ("rows",))
    prob = hpcg.generate_problem(4, 4, 8)
    n = prob.shape[0]
    xh = np.ones(n, np.float32)
    x = distribute_vector(xh, mesh, "rows")
    A = build_dist_matrix(prob.row, prob.col, prob.val, prob.shape, mesh,
                          "rows", mode="multiformat", tune="analytic")
    assert A.split
    assert isinstance(A.boundary, SwitchDynamicMatrix)
    assert A.plan.interior_plans is not None
    assert A.plan.boundary_plans is not None
    y0 = np.asarray(dist_spmv(A, x, mesh))
    D = np.zeros((n, n))
    np.add.at(D, (prob.row, prob.col), prob.val)
    np.testing.assert_allclose(y0, D @ xh, rtol=1e-4, atol=1e-4)
    for fmt in (Format.COO, Format.CSR, Format.ELL):
        A2 = activate_dist(A, "boundary", fmt)
        y2 = np.asarray(dist_spmv(A2, x, mesh))
        np.testing.assert_allclose(y2, y0, rtol=1e-5, atol=1e-5)
    try:
        activate_dist(build_dist_matrix(prob.row, prob.col, prob.val,
                                        prob.shape, mesh, "rows",
                                        mode="multiformat", tune="analytic",
                                        split=False), "boundary", Format.COO)
    except ValueError as e:
        assert "boundary" in str(e)
    else:
        raise AssertionError("boundary activate on unsplit matrix must raise")
    print("OK")
    """
    assert "OK" in _run_subprocess(body)


def test_dist_split_gather_mode_8shards():
    """Random pattern -> gather halo; the split schedule must agree with
    the dense oracle there too."""
    body = """
    mesh = make_mesh((8,), ("rows",))
    rng = np.random.default_rng(7)
    n, m = 128, 2000
    row = rng.integers(0, n, m)
    col = rng.integers(0, n, m)
    val = rng.standard_normal(m).astype(np.float32)
    D = np.zeros((n, n))
    np.add.at(D, (row, col), val)
    xh = rng.standard_normal(n).astype(np.float32)
    x = distribute_vector(xh, mesh, "rows")
    A = build_dist_matrix(row, col, val, (n, n), mesh, "rows")
    assert A.halo_mode == "gather" and A.split
    y = np.asarray(dist_spmv(A, x, mesh))
    np.testing.assert_allclose(y, D @ xh, rtol=2e-4, atol=2e-4)
    print("OK")
    """
    assert "OK" in _run_subprocess(body)


def test_dist_parts_scoped_and_counted_4shards():
    """Each part of a split distributed SpMV runs under a scope of its own
    in the compiled text (``bench.scopes`` reads them), and the build
    counts what each part stores against its live entries: a DIA
    boundary part stores 27 full diagonals over every row of each slab."""
    body = """
    sys.path.insert(0, %r)
    from collections import Counter
    from bench import scopes
    from repro.obs import metrics

    mesh = make_mesh((4,), ("rows",), devices=jax.devices()[:4])
    prob = hpcg.generate_problem(8, 8, 32)
    A = build_dist_matrix(prob.row, prob.col, prob.val, prob.shape, mesh,
                          "rows", local_format=Format.DIA,
                          remote_format=Format.COO,
                          plan=hpcg.slab_plan(prob, 4), check_plan=False)
    assert A.split and A.mp == 8 * 8 * 8, A
    parts = ("interior", "boundary", "remote")
    assert set(A.counts) == {f"dist.{k}.{p}" for k in ("stored", "entries")
                             for p in parts}
    assert A.counts["dist.stored.boundary"] == 27 * A.mp * 4
    assert A.counts["dist.stored.interior"] == 27 * A.mp * 4
    assert sum(A.counts[f"dist.entries.{p}"] for p in parts) == len(prob.val)
    # the two end slabs exchange one plane, the others two: 9 remote
    # entries per row of a plane, fewer at its edges
    assert A.counts["dist.entries.remote"] == 6 * (3 * 8 - 2) ** 2
    assert all(metrics.value(k) == v for k, v in A.counts.items())

    x = distribute_vector(np.ones(prob.shape[0], np.float32), mesh, "rows")

    def placed(fn):
        text = jax.jit(fn).lower(A, x).compile().as_text()
        return Counter(scopes.instruction_scopes(text).values())

    full = placed(lambda a, v: dist_spmv(a, v, mesh, backend="ref"))
    assert all(full[s] > 0 for s in ("dist.interior", "dist.boundary",
                                     "dist.halo", "dist.remote")), full
    local = placed(lambda a, v: dist_spmv_phase(a, v, mesh, phase="local",
                                                backend="ref"))
    assert local["dist.halo"] == local["dist.remote"] == 0, local
    assert local["dist.interior"] > 0 and local["dist.boundary"] > 0, local
    print("OK")
    """ % os.path.dirname(os.path.abspath(SRC))
    assert "OK" in _run_subprocess(body)


# ---------------------------------------------------------------------------
# repro.env (no jax involvement by construction)
# ---------------------------------------------------------------------------


def test_env_resolve_backend(monkeypatch):
    from repro import env

    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.delenv("JAX_PLATFORM_NAME", raising=False)
    monkeypatch.delenv("REPRO_BACKEND", raising=False)
    assert env.resolve_backend() == "cpu"
    assert env.resolve_backend("GPU") == "gpu"
    monkeypatch.setenv("JAX_PLATFORMS", "cuda,cpu")
    assert env.resolve_backend() == "cuda"


def test_env_apply_backend_gated(monkeypatch):
    """CPU gets only the device-count flag; GPU adds the async-collective
    set; a caller's unrelated XLA_FLAGS survive the merge."""
    from repro import env

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/tmp/cc")
    monkeypatch.setenv("XLA_FLAGS", "--xla_dump_to=/tmp/d")
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")  # jax already imported in pytest
        info = env.apply(backend="cpu", host_devices=16)
    assert "--xla_force_host_platform_device_count=16" in info["xla_flags"]
    assert "--xla_dump_to=/tmp/d" in info["xla_flags"]
    assert "async_collectives" not in info["xla_flags"]
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        info = env.apply(backend="gpu", host_devices=4)
    assert "--xla_gpu_enable_async_collectives=true" in info["xla_flags"]
    assert "--xla_gpu_enable_latency_hiding_scheduler=true" in info["xla_flags"]
    assert "--xla_force_host_platform_device_count=4" in info["xla_flags"]
    # managed flags were replaced, not duplicated
    assert info["xla_flags"].count("device_count") == 1
    assert env.describe()["backend"] == "gpu"


def test_env_apply_warns_after_jax_import(monkeypatch):
    from repro import env

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/tmp/cc")
    monkeypatch.setenv("XLA_FLAGS", "")
    with pytest.warns(RuntimeWarning, match="after jax"):
        env.apply(backend="cpu", host_devices=2)


def test_env_apply_compile_cache_dir(monkeypatch):
    """Unset, the cache dir defaults to <checkout>/.jax_cache; a caller's
    JAX_COMPILATION_CACHE_DIR is left exactly as it was."""
    from repro import env

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "x")
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        info = env.apply(backend="cpu")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert info["compile_cache_dir"] == os.path.join(root, ".jax_cache")
    assert os.environ["JAX_COMPILATION_CACHE_DIR"] == info["compile_cache_dir"]
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/tmp/mine")
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        assert env.apply(backend="cpu")["compile_cache_dir"] == "/tmp/mine"
    assert os.environ["JAX_COMPILATION_CACHE_DIR"] == "/tmp/mine"
