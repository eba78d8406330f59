"""What decides `correct` in the 4-chip CG cell, at a size a test run
holds: its configuration cut to 16x16x64 with the jnp reference SpMV and
the cell's own limits, run by the harness on 4 CPU host devices in a
subprocess (the test session keeps one device). The program passes; the
control (the plain reference in bfloat16) fails, and so do runs with the
halo exchange replaced by zeros and with the boundary rows' part left
out."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import spec  # noqa: E402

CELL = "hpcg104x4-cg"

SCRIPT = textwrap.dedent("""
    import dataclasses, json, os, sys, time
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    sys.path[:0] = [{root!r}, os.path.join({root!r}, "src")]
    import jax, jax.numpy as jnp
    from bench import harness, spec
    from repro.core import distributed

    cell = spec.load_cell({cell!r})
    cell = dataclasses.replace(cell, config=dict(
        cell.config, grid=[16, 16, 64], backend="ref"))
    devices = jax.devices()[:cell.chips]

    def run(on=cell, trace=False, **kw):
        return harness.run(on, 2**33 + 7, 0.25, trace, time.perf_counter(),
                           devices, **kw)

    out = {{"program": run(), "traced": run(trace=True)}}
    # the control never reaches the tolerance, so each of its solves runs
    # maxiter eager iterations: two right-hand sides of the pool do
    x_s = dict(cell.traffic["x_s"], pool=2)
    two = dataclasses.replace(cell, traffic=dict(cell.traffic, x_s=x_s))
    out["control"] = run(two, solve_override=harness.control_solve(
        two.config, two.traffic, jnp.bfloat16))
    exchange = distributed._exchange_halo
    distributed._exchange_halo = lambda x, *a: jnp.zeros_like(exchange(x, *a))
    out["zero_halo"] = run()
    distributed._exchange_halo = exchange
    shard_spmv = distributed._shard_spmv
    distributed._shard_spmv = lambda *a, boundary=None, **k: shard_spmv(*a, **k)
    out["no_boundary"] = run()
    print(json.dumps(out))
""")


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    env = dict(os.environ, REPRO_TUNING_CACHE=str(
        tmp_path_factory.mktemp("sel") / "sel.json"))
    res = subprocess.run(
        [sys.executable, "-c", SCRIPT.format(root=ROOT, cell=CELL)],
        capture_output=True, text=True, timeout=600, env=env)
    assert res.returncode == 0, f"stdout:\n{res.stdout}\nstderr:\n{res.stderr}"
    return json.loads(res.stdout.strip().splitlines()[-1])


def test_config_shards_are_the_cells_chips():
    cell = spec.load_cell(CELL)
    assert cell.chips == 4 and cell.config["shards"] == cell.chips
    assert [m.name for m in cell.end_to_end] == ["solve_ms", "setup_s"]
    assert [m.name for m in cell.per_layer] == [
        "dist.iters", "dist.idle_share", "dist.hbm_share",
        "dist.spmv_roofline", "dist.collective_ms"]


def test_program_is_correct_on_four_devices(runs):
    r = runs["program"]
    assert r["correct"], r["check"]
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert r["device"]["count"] == 4
    assert set(r["metrics"]) == {"solve_ms", "setup_s"}


def test_traced_run_reads_the_per_layer_metrics_it_finds(runs):
    r = runs["traced"]
    assert r["correct"], r["check"]
    # the CPU has no TPU plane and no peak: those readers find nothing
    assert set(r["metrics"]) == {"dist.iters"}


def test_bfloat16_control_is_not_correct(runs):
    r = runs["control"]
    assert not r["correct"]
    assert r["check"]["true_res"]["value"] > 10 * r["check"]["true_res"]["limit"]


@pytest.mark.parametrize("fault", ["zero_halo", "no_boundary"])
def test_broken_distributed_spmv_is_caught(runs, fault):
    r = runs[fault]
    assert not r["correct"]
    # without its boundary part the operator is no longer SPD, and CG may
    # break down to NaN, which no limit admits either
    assert not r["check"]["true_res"]["value"] <= r["check"]["true_res"]["limit"]
