"""repro.obs: tracer, metrics, ledger, explain, report, solver history."""
import collections
import contextlib
import io
import json
import os
import sys
import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import Format, coo_from_arrays, hpcg
from repro.core.convert import convert, planned_pulls_scope
from repro.core.ops import spmv
from repro.core.solvers import cg, cg_fixed_iters, pcg
from repro.launch.mesh import make_mesh
from repro.obs import explain, ledger, metrics, trace
from repro.obs import report


@pytest.fixture(autouse=True)
def _clean_trace():
    """Each test starts from an empty trace in the mode the env dictates."""
    trace.clear()
    yield
    trace.clear()


# ---------------------------------------------------------------------------
# Tracer core
# ---------------------------------------------------------------------------


def test_spans_nest_and_record_parentage():
    with trace.tracing("full"):
        with trace.span("build.outer", kind="t") as outer:
            with trace.span("plan.inner") as inner:
                pass
            trace.event("kernel.route", route="ref")
    evs = {e["name"]: e for e in trace.events()}
    assert set(evs) == {"build.outer", "plan.inner", "kernel.route"}
    assert evs["build.outer"]["parent"] is None
    assert evs["plan.inner"]["parent"] == evs["build.outer"]["id"]
    # the event fired while build.outer was still open -> it is a child too
    assert evs["kernel.route"]["parent"] == evs["build.outer"]["id"]
    assert inner.id != outer.id
    # durations: the parent covers the child
    assert evs["build.outer"]["dur"] >= evs["plan.inner"]["dur"]


def test_summary_mode_aggregates_without_ring():
    with trace.tracing("summary"):
        for _ in range(3):
            with trace.span("select.policy"):
                pass
    assert trace.events() == []  # no per-event storage in summary mode
    agg = trace.aggregate()
    assert agg["select.policy"]["count"] == 3
    assert "select.policy" in trace.summary()


def test_off_mode_emits_nothing_and_never_touches_jax(monkeypatch):
    """The REPRO_TRACE=off hot path must not record, sync, or import-touch
    jax: sp.sync() on the null span is a pure no-op."""
    def _boom(*a, **k):  # any block_until_ready call would be a sync leak
        raise AssertionError("block_until_ready called on the off path")

    monkeypatch.setattr(jax, "block_until_ready", _boom)
    trace.set_mode("off")
    y = jnp.arange(4.0)
    with jax.transfer_guard("disallow"):
        with trace.span("kernel.anything", x=1) as sp:
            sp.sync(y)
            sp.set(a=2)
        trace.event("kernel.evt")
    assert trace.events() == []
    assert trace.aggregate() == {}
    # the off span is one shared singleton — no allocation per call
    assert trace.span("a") is trace.span("b")


def test_summary_span_shows_on_the_profilers_host_plane(tmp_path):
    """An active span is a JAX profiler annotation too: a profile taken on
    the CPU holds it on the ``/host:CPU`` plane, with its attributes, on
    the clock of the ops it covers."""
    import glob

    f = jax.jit(lambda v: (v * 2.0).sum())
    x = jnp.ones(256)
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with trace.tracing("summary"):
        with jax.profiler.trace(str(tmp_path), profiler_options=opts):
            with trace.span("solver.solve", precond="mg") as sp:
                sp.set(iters=5)
                f(x).block_until_ready()
    assert trace.aggregate()["solver.solve"]["count"] == 1
    [pb] = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    planes = {p.name: p for p in
              jax.profiler.ProfileData.from_file(pb).planes}
    spans = [ev for line in planes["/host:CPU"].lines
             for ev in line.events if ev.name == "solver.solve"]
    assert len(spans) == 1
    assert dict(spans[0].stats) == {"precond": "mg", "iters": 5}
    dispatch = [ev for line in planes["/host:CPU"].lines
                for ev in line.events if ev.name.startswith("PjitFunction")]
    assert any(spans[0].start_ns <= ev.start_ns <= spans[0].end_ns
               for ev in dispatch)


def test_tracing_scope_restores_mode():
    trace.set_mode("off")
    with trace.tracing("full"):
        assert trace.mode() == "full"
        with trace.tracing("summary"):
            assert trace.mode() == "summary"
        assert trace.mode() == "full"
    assert trace.mode() == "off"


def test_export_chrome_roundtrip(tmp_path):
    with trace.tracing("full"):
        with trace.span("solver.solve", precond="mg") as sp:
            with trace.span("plan.partition"):
                pass
    path = trace.export_chrome(str(tmp_path / "trace.json"))
    with open(path) as f:
        doc = json.load(f)
    assert all(e["ph"] == "X" for e in doc["traceEvents"])
    evs = report.load_trace(path)
    assert {e["name"] for e in evs} == {"solver.solve", "plan.partition"}
    child = next(e for e in evs if e["name"] == "plan.partition")
    parent = next(e for e in evs if e["name"] == "solver.solve")
    assert child["parent"] == parent["id"]
    assert parent["args"]["precond"] == "mg"  # ids popped out of args


# ---------------------------------------------------------------------------
# Metrics
# ---------------------------------------------------------------------------


def test_metrics_snapshot_reset_roundtrip():
    metrics.reset(["t.a", "t.b", "t.h"])
    metrics.inc("t.a")
    metrics.inc("t.a", 2)
    metrics.inc("t.b", 5)
    metrics.observe("t.h", 0.25)
    metrics.observe("t.h", 0.75)
    snap = metrics.snapshot()
    assert snap["counters"]["t.a"] == 3
    assert snap["counters"]["t.b"] == 5
    h = snap["histograms"]["t.h"]
    assert (h["count"], h["sum"], h["min"], h["max"]) == (2, 1.0, 0.25, 0.75)
    assert h["mean"] == 0.5
    json.dumps(snap)  # JSON-ready
    metrics.reset(["t.a"])
    assert metrics.value("t.a") == 0
    assert metrics.value("t.b") == 5  # scoped reset leaves others alone
    metrics.reset(["t.b", "t.h"])


def test_metrics_scope_is_order_independent():
    metrics.inc("t.scope", 100)  # unrelated earlier activity
    with metrics.scope() as s:
        metrics.inc("t.scope", 3)
        assert s.delta("t.scope") == 3
    # a second scope sees only its own window, not the 103 before it
    with metrics.scope() as s2:
        assert s2.delta("t.scope") == 0
        metrics.inc("t.scope")
        assert s2.deltas() == {"t.scope": 1}
    metrics.reset(["t.scope"])


def test_quantile_vs_numpy_oracle():
    """Bucket-estimated p50/p95/p99 must land within the 1-2-5 series'
    resolution (~±25%) of numpy's exact quantiles on a skewed sample."""
    metrics.reset(["t.q"])
    rng = np.random.default_rng(0)
    vals = rng.lognormal(mean=5.0, sigma=1.5, size=4000)
    for v in vals:
        metrics.observe("t.q", v)
    for q in (0.5, 0.95, 0.99):
        est = metrics.quantile("t.q", q)
        ref = float(np.quantile(vals, q))
        assert abs(est - ref) / ref < 0.25, (q, est, ref)
    qs = metrics.quantiles("t.q")
    assert set(qs) == {"p50", "p95", "p99"}
    assert qs["p50"] <= qs["p95"] <= qs["p99"]
    metrics.reset(["t.q"])


def test_quantile_edge_cases():
    metrics.reset(["t.single", "t.empty"])
    assert metrics.quantile("t.empty", 0.5) is None  # never observed
    metrics.observe("t.single", 42.0)
    # single observation: min==max clamping makes every quantile exact
    for q in (0.0, 0.5, 0.99, 1.0):
        assert metrics.quantile("t.single", q) == pytest.approx(42.0)
    with pytest.raises(ValueError):
        metrics.quantile("t.single", 1.5)
    metrics.reset(["t.single"])


def test_define_histogram_and_gauges():
    metrics.reset(["t.custom", "t.gauge"])
    metrics.define_histogram("t.custom", [1.0, 2.0, 4.0])
    for v in (0.5, 1.5, 3.0, 8.0):
        metrics.observe("t.custom", v)
    assert metrics.quantile("t.custom", 0.5) == pytest.approx(1.75, rel=0.3)
    with pytest.raises(ValueError):  # re-binning live counts is impossible
        metrics.define_histogram("t.custom", [10.0])
    metrics.set_gauge("t.gauge", 3)
    metrics.set_gauge("t.gauge", 7)  # last write wins
    assert metrics.gauge("t.gauge") == 7
    snap = metrics.snapshot()
    assert snap["gauges"]["t.gauge"] == 7
    json.dumps(snap)
    metrics.reset(["t.custom", "t.gauge"])
    assert metrics.gauge("t.gauge", default=-1) == -1


def test_trace_ring_drop_counter_and_warn_once(tmp_path, monkeypatch):
    """A wrapped full-mode ring counts drops in trace.dropped_events and
    export_chrome warns exactly once per collection."""
    monkeypatch.setattr(trace, "RING_CAPACITY", 8)
    with metrics.scope() as s:
        with trace.tracing("full"):
            trace.clear()
            for i in range(12):
                trace.event("kernel.route", i=i)
            assert trace.dropped() == 4
            assert s.delta("trace.dropped_events") == 4
            assert len(trace.events()) == 8
            # newest events win: the first 4 are gone
            assert [e["args"]["i"] for e in trace.events()] == list(range(4, 12))
            with pytest.warns(RuntimeWarning, match="truncated"):
                trace.export_chrome(str(tmp_path / "t1.json"))
            with warnings.catch_warnings():
                warnings.simplefilter("error")  # second export: silent
                trace.export_chrome(str(tmp_path / "t2.json"))
            doc = json.load(open(tmp_path / "t1.json"))
            assert doc["otherData"]["dropped_events"] == 4
            trace.clear()  # re-arms the warning
            trace.event("kernel.route", i=0)
            with warnings.catch_warnings():
                warnings.simplefilter("error")  # no drops -> no warning
                trace.export_chrome(str(tmp_path / "t3.json"))


def test_planned_pulls_scope_counts_only_inside():
    A = jnp.zeros((4, 4)).at[0, 0].set(1.0)
    from repro.core.formats import Dense
    D = Dense(A, (4, 4), 16)
    convert(D, Format.COO)  # pulls before the scope must not count
    with planned_pulls_scope() as s:
        before = s.count
        convert(D, Format.COO)
        assert s.count > before
    first = s.count
    convert(D, Format.COO)  # pulls after the scope must not count either
    assert s.count == first


# ---------------------------------------------------------------------------
# Instrumented layers, end to end
# ---------------------------------------------------------------------------


def test_hpcg_trace_contains_phases_with_sane_parentage():
    """An hpcg build + multiformat selection + auto-routed solve leaves
    select/plan/convert/kernel spans in the trace, with every recorded
    parent id belonging to a recorded span."""
    from repro.core.distributed import build_dist_matrix, distribute_vector
    from repro.core.solvers import operator

    with trace.tracing("full"):
        trace.clear()
        prob = hpcg.generate_problem(4, 4, 4)
        mesh = make_mesh((1,), ("rows",))
        A = build_dist_matrix(prob.row, prob.col, prob.val, prob.shape,
                              mesh, "rows", mode="multiformat",
                              tune="analytic")
        b = distribute_vector(hpcg.rhs_for_ones(prob), mesh, "rows")
        res = jax.block_until_ready(
            cg(operator(A, mesh, backend="auto"), b, tol=1e-6, maxiter=50))
        evs = trace.events()
    assert float(res.resnorm) < 1e-3
    phases = {report.phase_of(e["name"]) for e in evs}
    assert {"select", "plan", "convert", "kernel", "build"} <= phases, phases
    ids = {e["id"] for e in evs}
    by_id = {e["id"]: e for e in evs}
    for e in evs:
        if e["parent"] is not None and e["parent"] in ids:
            parent = by_id[e["parent"]]
            # a child span starts no earlier than its parent
            assert e["ts"] >= parent["ts"] - 1e-3, (e, parent)
    # the build.dist span must be an ancestor of at least one plan span
    build_ids = {e["id"] for e in evs if e["name"] == "build.dist"}
    assert any(e["parent"] in build_ids for e in evs
               if e["name"].startswith(("plan.", "select.", "convert.")))


def test_selection_cache_counters(tmp_path):
    from repro.tuning.cache import SelectionCache
    from repro.tuning.policy import FormatPolicy
    from repro.core import random_coo

    C = random_coo(0, (32, 32), 0.1)
    cache = SelectionCache(str(tmp_path / "sel.json"))
    policy = FormatPolicy("cached", cache=cache)
    with metrics.scope() as s:
        policy.select(C)
        assert s.delta("selection.cache_miss") == 1
        policy.select(C)
        assert s.delta("selection.cache_hit") == 1


def test_kernel_route_counters():
    from repro.core.ops import kernel_route
    from repro.core import random_coo

    A = convert(random_coo(1, (64, 64), 0.1), Format.CSR)
    with metrics.scope() as s:
        route, cfg = kernel_route(A)  # empty cache: unmeasured -> ref
        assert route in ("ref", "pallas")
        deltas = s.deltas()
    assert any(k.startswith("kernel.route.") for k in deltas), deltas


def test_padding_waste_histograms():
    from repro.core import random_coo

    metrics.reset(["ell.padding_waste", "hyb.padding_waste"])
    C = random_coo(3, (64, 64), 0.05)
    convert(C, Format.ELL)
    convert(C, Format.HYB)
    snap = metrics.snapshot()["histograms"]
    assert snap["ell.padding_waste"]["count"] == 1
    assert 0.0 <= snap["ell.padding_waste"]["max"] <= 1.0
    assert snap["hyb.padding_waste"]["count"] == 1


# ---------------------------------------------------------------------------
# Decision ledger + explain
# ---------------------------------------------------------------------------


@pytest.fixture
def _ledger_on():
    ledger.set_enabled(True)
    ledger.clear()
    yield
    ledger.clear()


def test_ledger_ring_drops_and_dump_roundtrip(tmp_path, monkeypatch, _ledger_on):
    monkeypatch.setattr(ledger, "CAPACITY", 4)
    monkeypatch.setattr(ledger, "_RING", collections.deque(maxlen=4))
    for i in range(6):
        ledger.record("kernel.route", i=i)
    recs = ledger.records()
    assert len(recs) == 4
    assert [r["i"] for r in recs] == [2, 3, 4, 5]  # newest win
    assert ledger.dropped() == 2
    seqs = [r["seq"] for r in recs]
    assert seqs == sorted(seqs)
    path = ledger.dump_json(str(tmp_path / "led.json"))
    doc = ledger.load_json(path)
    assert len(doc["records"]) == 4 and doc["dropped"] == 2
    # seq stays monotonic across clear(): dumps never alias
    ledger.clear()
    ledger.record("kernel.route", i=99)
    assert ledger.records()[0]["seq"] > seqs[-1]
    with open(tmp_path / "bad.json", "w") as f:
        json.dump({"nope": 1}, f)
    with pytest.raises(ValueError):
        ledger.load_json(str(tmp_path / "bad.json"))


def test_ledger_disabled_records_nothing(_ledger_on):
    ledger.set_enabled(False)
    ledger.record("format.select", chosen="CSR")
    assert ledger.records() == []
    ledger.set_enabled(True)


def test_policy_select_emits_explainable_records(tmp_path, _ledger_on):
    """A cached-mode selection leaves a format.select record carrying the
    feature vector, the CART path (or analytic scores), the cache
    hit/miss, and the kernel veto reason; the second select is a hit."""
    from repro.core import random_coo
    from repro.tuning.cache import SelectionCache
    from repro.tuning.policy import FormatPolicy

    C = random_coo(0, (64, 64), 0.1)
    policy = FormatPolicy("cached", cache=SelectionCache(
        str(tmp_path / "sel.json")))
    policy.select(C)
    policy.select(C)
    recs = ledger.records(kind="format.select")
    assert len(recs) == 2
    miss, hit = recs
    assert miss["cache"] == "miss" and hit["cache"] == "hit"
    assert miss["chosen"] in Format.__members__
    assert set(miss["features"]) >= {"log_m", "row_cv", "ell_efficiency"}
    assert "tree_path" in miss or "scores" in miss
    if "tree_path" in miss:
        leaf = miss["tree_path"][-1]
        assert leaf["leaf"] and leaf["predict_name"] in Format.__members__
    # empty kernel cache: the pin must carry its veto reason
    assert "no tuned kernel record" in miss["kernel_veto"]
    text = explain.render(recs)
    assert "cache: miss" in text and "cache: hit" in text
    if "tree_path" in miss:
        assert "CART path" in text and "leaf[" in text


def _powerlaw_coo(seed, n, shape_a=1.3, scale=4.0):
    """Power-law row lengths (pareto counts): the irregular-row family."""
    rng = np.random.default_rng(seed)
    counts = np.minimum(1 + (rng.pareto(shape_a, n) * scale).astype(np.int64),
                        n)
    rows = np.repeat(np.arange(n, dtype=np.int64), counts)
    cols = np.concatenate([rng.choice(n, k, replace=False) for k in counts])
    vals = rng.standard_normal(len(rows)).astype(np.float32)
    vals = np.where(np.abs(vals) < 1e-3, 1e-3, vals)
    return coo_from_arrays(rows, cols, vals, (n, n))


def test_plan_for_records_sell_geometry_source(tmp_path, _ledger_on):
    from repro.tuning import kernel_tune
    from repro.tuning.cache import SelectionCache
    from repro.tuning.policy import FormatPolicy

    C = _powerlaw_coo(3, 512)
    cache = SelectionCache(str(tmp_path / "k.json"))
    A = convert(C, Format.SELL)
    kernel_tune.tune_kernel(A, cache=cache,
                            grid=kernel_tune.default_grid(A, smoke=True),
                            iters=1, inner=1)
    policy = FormatPolicy("cached", cache=cache)
    ledger.clear()
    policy.plan_for(C, fmt=Format.SELL)
    recs = ledger.records(kind="plan.switch")
    assert len(recs) == 1
    assert recs[0]["fmt"] == "SELL"
    # the tuned record's (c, sigma) seeded the plan and said so
    assert recs[0]["geometry_source"] == "tuned kernel record"
    assert "c" in recs[0]["hints"] and "sigma" in recs[0]["hints"]
    assert "SELL" in explain.render(recs)


def test_kernel_route_ledger_reasons(tmp_path, _ledger_on):
    from repro.core import random_coo
    from repro.core.ops import kernel_route
    from repro.tuning.cache import SelectionCache

    A = convert(random_coo(1, (64, 64), 0.1), Format.CSR)
    empty = SelectionCache(str(tmp_path / "empty.json"))
    route, _ = kernel_route(A, cache=empty)
    assert route == "ref"
    recs = ledger.records(kind="kernel.route")
    assert len(recs) == 1
    assert recs[0]["route"] == "ref"
    assert "no tuned record" in recs[0]["reason"]
    assert recs[0]["bucket"].startswith("kernel:")
    text = explain.render(recs)
    assert "reason:" in text and "bucket:" in text


def test_explain_render_kernel_record_with_sell_geometry(_ledger_on):
    rec = {"seq": 1, "ts": 0.0, "kind": "kernel.route", "op": "spmv",
           "fmt": "SELL", "route": "pallas",
           "kernel": {"fmt": "SELL", "op": "spmv",
                      "cfg": {"c": 32, "sigma": 256},
                      "kernel_us": 120.0, "ref_us": 300.0, "speedup": 2.5}}
    text = explain.render_record(rec)
    assert "c=32" in text and "sigma=256" in text
    assert "2.50x" in text


@pytest.fixture(scope="module")
def explain_demo(tmp_path_factory):
    """``python -m repro.obs.explain --family powerlaw --dump PATH``: the
    demo's printed decision trail and the ledger dump it wrote."""
    dump = str(tmp_path_factory.mktemp("explain") / "ledger_dump.json")
    was = ledger.enabled()
    out = io.StringIO()
    try:
        with contextlib.redirect_stdout(out):
            assert explain.main(["--family", "powerlaw", "--dump", dump]) == 0
    finally:
        ledger.clear()
        ledger.set_enabled(was)
    return out.getvalue(), dump


def test_explain_demo_prints_the_decision_trail(explain_demo):
    text, dump = explain_demo
    assert "CART path" in text and "format.select" in text
    assert "veto:" in text or "kernel record:" in text
    assert ledger.load_json(dump)["records"]


def test_explain_replays_its_dump(explain_demo, capsys):
    _, dump = explain_demo
    assert explain.main([dump, "--quiet"]) == 0
    assert "format.select" in capsys.readouterr().out


# ---------------------------------------------------------------------------
# Report
# ---------------------------------------------------------------------------


def test_attribution_self_time():
    evs = [
        {"name": "build.dist", "ts": 0.0, "dur": 100.0, "tid": 1, "id": 1,
         "parent": None, "args": {}},
        {"name": "plan.partition", "ts": 10.0, "dur": 30.0, "tid": 1, "id": 2,
         "parent": 1, "args": {}},
        {"name": "convert.execute", "ts": 50.0, "dur": 50.0, "tid": 1, "id": 3,
         "parent": 1, "args": {}},
    ]
    rows = {r["phase"]: r for r in report.attribution(evs)}
    assert rows["build"]["self_ms"] == pytest.approx(0.020)  # 100-30-50 us
    assert rows["plan"]["self_ms"] == pytest.approx(0.030)
    assert rows["convert"]["self_ms"] == pytest.approx(0.050)
    assert sum(r["share"] for r in rows.values()) == pytest.approx(1.0)
    assert "build" in report.render_attribution(list(rows.values()))


def test_report_cli_renders(tmp_path, capsys):
    with trace.tracing("full"):
        with trace.span("solver.solve"):
            with trace.span("kernel.spmv"):
                pass
    path = str(tmp_path / "t.json")
    trace.export_chrome(path)
    assert report.main([path]) == 0
    out = capsys.readouterr().out
    assert "solver" in out and "kernel" in out


# ---------------------------------------------------------------------------
# Solver history
# ---------------------------------------------------------------------------


def test_cg_history_fixed_size_and_monotone_tail():
    prob = hpcg.generate_problem(4, 4, 4)
    A = convert(hpcg.to_coo(prob), Format.CSR)
    b = hpcg.rhs_for_ones(prob)
    res = jax.block_until_ready(
        cg(lambda v: spmv(A, v), b, tol=1e-8, maxiter=40))
    hist = np.asarray(res.history)
    assert hist.shape == (41,)  # maxiter + 1, regardless of convergence
    k = int(res.iters)
    assert hist[0] > 0
    assert np.isfinite(hist[:k + 1]).all()
    assert np.isnan(hist[k + 1:]).all()  # untouched tail stays NaN
    assert hist[k] == pytest.approx(float(res.resnorm), rel=1e-4)


def test_pcg_and_fixed_iters_history():
    prob = hpcg.generate_problem(4, 4, 4)
    A = convert(hpcg.to_coo(prob), Format.CSR)
    b = hpcg.rhs_for_ones(prob)
    diag = jnp.full((prob.shape[0],), 26.0, jnp.float32)
    res = jax.block_until_ready(
        pcg(lambda v: spmv(A, v), b, diag, tol=1e-8, maxiter=30))
    hist = np.asarray(res.history)
    assert hist.shape == (31,)
    assert hist[int(res.iters)] == pytest.approx(float(res.resnorm), rel=1e-4)

    res = jax.block_until_ready(
        cg_fixed_iters(lambda v: spmv(A, v), b, iters=7))
    hist = np.asarray(res.history)
    assert hist.shape == (8,)
    assert np.isfinite(hist).all()
    assert hist[-1] == pytest.approx(float(res.resnorm), rel=1e-4)
