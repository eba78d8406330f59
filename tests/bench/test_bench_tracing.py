"""The trace reduction gives known answers on a small trace."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import tracing  # noqa: E402

# one chip: a while op holding two body ops with a gap between them (30 to
# 40) and an empty stretch after them (90 to 100), then a lone op after a gap
OPS = [("%while.1 = (f32[8]) while(...)", 0, 100),
       ("%fusion.2 = f32[8] fusion(...)", 10, 30),
       ("%dia_spmv.3 = f32[8,128] custom-call(...)", 40, 90),
       ("%fusion.2 = f32[8] fusion(...)", 130, 150)]
HOST = [("bench.dispatch", 0, 5), ("bench.wait", 5, 120),
        ("bench.record", 110, 128)]


def test_union_busy_and_gaps():
    assert tracing.union([(5, 7), (0, 3), (2, 4), (9, 9)]) == [
        (0, 4), (5, 7), (9, 9)]
    assert tracing.busy_ns(OPS) == 90
    assert tracing.gaps(OPS) == [(30, 40), (90, 130)]


def test_leaves_drop_ops_that_hold_others():
    assert tracing.leaves(OPS) == OPS[1:]
    nested = [("%while.1", 0, 100), ("%while.2", 5, 60), ("%f.3", 10, 20),
              ("%f.4", 60, 70), ("%f.5", 100, 110)]
    assert tracing.leaves(nested) == nested[2:]
    assert tracing.busy_ns(nested) == 30
    assert tracing.leaves(OPS[:1]) == OPS[:1]  # an op alone is a leaf


def test_self_time_takes_nested_ops_out_of_their_parent():
    assert tracing.self_time_by_name(OPS) == {
        "while.1 (f32[8]) while": 30.0, "fusion.2 f32[8] fusion": 40.0,
        "dia_spmv.3 f32[8,128] custom-call": 50.0}


def test_op_name_keeps_name_shape_and_opcode():
    text = ('%dia_spmv.9 = f32[8788,128]{1,0:T(8,128)S(1)} '
            'custom-call(s32[27]{0:T(128)S(1)} %p), custom_call_target="x"')
    assert tracing.op_name(text) == "dia_spmv.9 f32[8788,128] custom-call"
    assert tracing.op_name("bench.wait") == "bench.wait"


def test_reduction_shares_and_breakdown():
    r = tracing.Reduction({"/device:TPU:0": OPS}, HOST, window_s=200e-9)
    assert r.busy_s == pytest.approx(90e-9)
    assert r.idle_share == pytest.approx(0.55)
    assert r.seconds_where(lambda n: "custom-call" in n) == pytest.approx(50e-9)
    bd = r.breakdown()
    assert [n.split()[0] for n, _ in bd["device_ops"]] == [
        "dia_spmv.3", "fusion.2", "while.1"]
    # the gap 90..130 has its middle at 110, inside bench.record; the gap
    # 30..40 inside the while op has its middle at 35, inside bench.wait
    assert bd["idle_gaps"] == [["bench.record", pytest.approx(40e-9)],
                               ["bench.wait", pytest.approx(10e-9)]]


def test_busy_is_averaged_over_chips_and_empty_trace_reads_nothing():
    r = tracing.Reduction({"a": OPS, "b": OPS[:1]}, [], window_s=200e-9)
    assert r.busy_s == pytest.approx((90 + 100) / 2 * 1e-9)
    empty = tracing.Reduction({}, HOST, window_s=1.0)
    assert empty.busy_s == 0.0 and empty.idle_share is None
    assert empty.breakdown() == {"device_ops": [], "idle_gaps": []}


def test_load_reads_a_profiler_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.dispatch"):
        y = f(x)
    y.block_until_ready()
    jax.profiler.stop_trace()
    r = tracing.load(str(tmp_path), window_s=1.0)
    assert "bench.dispatch" in [n for n, _, _ in r.host_spans]
    assert r.device_ops == {}  # the CPU has no TPU plane
