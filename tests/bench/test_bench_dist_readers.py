"""The 4-chip cell's per-layer readers on a hand-made trace of 4 chips:
each counts per chip, and finds nothing where there is no trace or no
peak."""
import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import dist_work, spec, tracing, work  # noqa: E402

CONFIG = {"grid": [4, 4, 16], "solver": "cg", "shards": 4}
NNZ = 10 * 10 * 46      # stencil_nnz(4, 4, 16)
PEAK = 1e9              # bytes/s
ITERS = [3, 5]          # two solves: 4 + 6 = 10 SpMVs


def chip(k):
    """One chip's ops (ns): a while op holding a DIA kernel call, a
    fusion, the halo exchange's start and done, an all-reduce; chip k's
    kernel takes 100 + 20 k ns."""
    return [("%while.1 = (f32[64]) while(%t), body=%body", 0, 1000),
            ("%dia_spmv.4 = f32[1,128] custom-call(%a), "
             'custom_call_target="tpu_custom_call"', 10, 110 + 20 * k),
            ("%fusion.2 = f32[64] fusion(%x), kind=kLoop", 200, 260),
            ("%collective-permute-start.1 = (f32[16], f32[16]) "
             "collective-permute-start(%x), channel_id=1", 300, 310),
            ("%collective-permute-done.1 = f32[16] "
             "collective-permute-done(%c)", 310, 350),
            ("%all-reduce.3 = f32[] all-reduce(%d), channel_id=2", 400, 430)]


def ctx(trace=True, peaks=True):
    planes = {f"/device:TPU:{k}": chip(k) for k in range(4)}
    return types.SimpleNamespace(
        config=CONFIG, window=types.SimpleNamespace(iters=ITERS, seconds=1e-3),
        trace=tracing.Reduction(planes, [], window_s=2e-6) if trace else None,
        peaks={"hbm_bytes_per_s": PEAK} if peaks else None, work=work)


def read(name, c):
    return spec.load_reader(name)(c)


def test_least_bytes_are_one_chips_rows():
    assert work.stencil_nnz(*CONFIG["grid"]) == NNZ
    assert dist_work.chip_spmv_bytes(CONFIG) == 4 * NNZ / 4
    assert dist_work.spmv_calls(ctx().window) == 10


def test_collective_ms_counts_collectives_per_chip_and_iteration():
    # per chip 10 + 40 + 30 ns of collectives, over 8 iterations
    assert read("dist.collective_ms", ctx()) == pytest.approx(1e3 * 80e-9 / 8)
    assert dist_work.is_collective(chip(0)[4][0])
    assert not any(dist_work.is_collective(t) for t, _, _ in chip(0)[:3])


def test_spmv_roofline_divides_by_shards():
    # kernel seconds averaged over chips: 100 + 20 * 1.5 = 130 ns
    least = 10 * 4 * NNZ / 4
    assert read("dist.spmv_roofline", ctx()) == pytest.approx(
        100 * least / (PEAK * 130e-9))


def test_hbm_share_divides_by_shards():
    total = work.solve_bytes(CONFIG, 3) + work.solve_bytes(CONFIG, 5)
    assert total == 10 * 4 * NNZ
    assert read("dist.hbm_share", ctx()) == pytest.approx(
        100 * total / (4 * PEAK * 1e-3))


def test_idle_share_and_iters():
    # per chip leaves: kernel, fusion, permute start and done, all-reduce;
    # busy 100 + 20 k + 60 + 50 + 30 ns, mean 270 ns of a 2000 ns window
    assert read("dist.idle_share", ctx()) == pytest.approx(100 * (1 - 270 / 2000))
    assert read("dist.iters", ctx()) == 4


@pytest.mark.parametrize("name,trace,peaks", [
    ("dist.collective_ms", False, True),
    ("dist.idle_share", False, True),
    ("dist.spmv_roofline", False, True),
    ("dist.spmv_roofline", True, False),
    ("dist.hbm_share", True, False),
])
def test_reader_finds_nothing_without_trace_or_peak(name, trace, peaks):
    assert read(name, ctx(trace=trace, peaks=peaks)) is None


def test_trace_without_device_ops_reads_nothing():
    c = ctx()
    c.trace = tracing.Reduction({}, [], window_s=1.0)
    for name in ("dist.collective_ms", "dist.idle_share",
                 "dist.spmv_roofline"):
        assert read(name, c) is None
