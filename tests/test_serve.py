"""Serving engine: batched jit'd prefill correctness + the smoke CLI.

The load-bearing claims: (1) ONE ``prefill_cache`` forward primes the
decode cache *identically* to the per-token prefill-by-decode loop it
replaced — same greedy continuations, ragged prompt lengths and pow2
row/len padding included; (2) the CLI subprocess completes every request;
(3) runtime ``activate()`` format switches between decode steps are
numerically invisible (the paper's dynamic-format claim, serving-shaped).
"""
import dataclasses
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core import Format
from repro.launch.serve import DecodeEngine, _pow2_at_least, serve
from repro.models import build_model
from repro.models.linear_sparse import LinearSparse, prune_magnitude

RNG = np.random.default_rng(0)


def _f32_model(arch="stablelm_1_6b"):
    # bf16 flash-prefill vs einsum-decode can flip argmax on near-ties;
    # parity tests pin f32 so greedy token ids are deterministic.
    cfg = dataclasses.replace(get_config(arch, smoke=True), dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _ref_greedy(model, params, prompt, max_new, max_len):
    """Single-request greedy decode with per-token prefill-by-decode —
    the behaviour the batched prefill must reproduce exactly."""
    cache = model.init_cache(1, max_len)
    step = jax.jit(model.decode_step)
    logits = None
    for i, t in enumerate(prompt):
        logits, cache = step(params, cache,
                             jnp.asarray([t], jnp.int32),
                             jnp.asarray([i], jnp.int32))
    tok = int(np.argmax(np.asarray(logits)[0]))
    out, pos = [tok], len(prompt)
    while len(out) < max_new:
        logits, cache = step(params, cache,
                             jnp.asarray([tok], jnp.int32),
                             jnp.asarray([pos], jnp.int32))
        tok = int(np.argmax(np.asarray(logits)[0]))
        out.append(tok)
        pos += 1
    return out


def test_pow2_bucket():
    assert [_pow2_at_least(n, 8) for n in (1, 2, 3, 5, 8, 9)] == \
        [1, 2, 4, 8, 8, 8]


def test_batched_prefill_matches_per_token_decode():
    """Ragged prompts through the batched engine == per-request reference.

    Lengths 3/5/6 in a 2-slot engine force: pow2 P padding (to 8), pow2 R
    padding (admission of 1 pending request pads the row axis), slot
    refill between steps, and the duplicate-slot pad-row scatter."""
    cfg, model, params = _f32_model()
    max_new, max_len = 5, 32
    prompts = [RNG.integers(0, cfg.vocab, (n,)).astype(np.int32)
               for n in (3, 5, 6)]
    engine = DecodeEngine(model, params, slots=2, max_len=max_len)
    done, _ = serve(engine, list(enumerate(prompts)), max_new)
    got = dict(done)
    assert sorted(got) == [0, 1, 2]
    for rid, prompt in enumerate(prompts):
        ref = _ref_greedy(model, params, prompt, max_new, max_len)
        assert got[rid] == ref, f"request {rid} diverged"


def test_prefill_by_decode_fallback_families():
    """ssm has no addressable kv cache: the engine must fall back to the
    per-token path and still finish every request."""
    cfg, model, params = _f32_model("mamba2_2_7b")
    assert not model.supports_prefill_cache()
    engine = DecodeEngine(model, params, slots=2, max_len=24)
    prompts = [RNG.integers(0, cfg.vocab, (4,)).astype(np.int32)
               for _ in range(3)]
    done, _ = serve(engine, list(enumerate(prompts)), max_new=3)
    assert sorted(r for r, _ in done) == [0, 1, 2]
    assert all(len(o) == 3 for _, o in done)
    assert engine.prefill_calls == 3  # one per request, not batched


@pytest.mark.slow
def test_serve_smoke_subprocess():
    """The CLI: every request completes, output lists printed, and the
    engine takes the batched prefill, not the per-token fallback. Run in
    a subprocess so serve's env.apply() cannot touch this session's XLA
    flags (conftest asserts the device-count override never leaks)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src" + os.pathsep + env.get("PYTHONPATH", "")
    n = 6
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--arch",
         "stablelm_1_6b", "--smoke", "--requests", str(n), "--slots", "3",
         "--prompt-len", "5", "--max-new", "4"],
        capture_output=True, text=True, timeout=600, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert out.returncode == 0, out.stderr[-2000:]
    assert f"served {n} requests, {n * 4} tokens" in out.stdout, out.stdout
    assert "batched prefills" in out.stdout, out.stdout
    for rid in range(n):
        assert f"req {rid}:" in out.stdout, out.stdout


def test_request_telemetry_spans_and_histograms():
    """Every finished request leaves a complete telemetry span: per-phase
    latencies in request_log (queue + prefill + decode ~ total), the
    serve.* histograms carry p50/p95/p99, and the ledger replays the
    requests. Oversubscribed slots make queue_us real for late requests."""
    from repro.obs import ledger, metrics

    cfg, model, params = _f32_model()
    ledger.set_enabled(True)
    ledger.clear()
    metrics.reset(["serve.requests", "serve.tokens", "serve.latency_us",
                   "serve.queue_us", "serve.prefill_us", "serve.decode_us",
                   "serve.queue_depth", "serve.retune",
                   "serve.format_switch"])
    engine = DecodeEngine(model, params, slots=2, max_len=32)
    n, max_new = 5, 3
    prompts = [(i, RNG.integers(0, cfg.vocab, (4,)).astype(np.int32))
               for i in range(n)]
    done, _ = serve(engine, prompts, max_new)
    assert len(done) == n
    assert len(engine.request_log) == n
    for entry in engine.request_log:
        assert entry["tokens"] == max_new
        assert entry["queue_us"] >= 0 and entry["prefill_us"] > 0
        assert entry["decode_us"] > 0
        # phases compose into the end-to-end span (prefill is the batched
        # call's per-request share, so <= its slice of the total)
        assert entry["total_us"] >= entry["queue_us"] + entry["decode_us"]
    snap = metrics.snapshot()
    assert snap["counters"]["serve.requests"] == n
    assert snap["counters"]["serve.tokens"] == n * max_new
    lat = snap["histograms"]["serve.latency_us"]
    assert lat["count"] == n
    assert lat["p50"] is not None and lat["p50"] <= lat["p99"]
    assert snap["histograms"]["serve.queue_depth"]["max"] >= 1  # real queueing
    recs = ledger.records(kind="serve.request")
    assert sorted(r["rid"] for r in recs) == list(range(n))
    ledger.clear()


def test_retune_counters_track_switch_vs_keep():
    """serve.retune counts every re-selection; serve.format_switch only the
    ones that changed the container."""
    from repro.obs import metrics

    w = prune_magnitude(RNG.standard_normal((48, 48)).astype(np.float32), 0.2)
    layer = LinearSparse.from_dense(w, fmt=Format.COO)
    with metrics.scope() as s:
        retuned = layer.retune(ncols=64, tune="analytic")
        assert s.delta("serve.retune") == 1
        expected = 1 if retuned.format != layer.format else 0
        assert s.delta("serve.format_switch") == expected
        # retuning the retuned layer at the same width is now a no-switch
        again = retuned.retune(ncols=64, tune="analytic")
        assert s.delta("serve.retune") == 2
        assert again.format == retuned.format


def test_format_switch_between_decode_steps_parity():
    """activate() between steps (the serving-loop format switch) is
    numerically invisible: a decode-shaped loop whose sparse layer hops
    CSR -> ELL -> HYB -> COO matches the fixed-format run exactly."""
    w = prune_magnitude(RNG.standard_normal((32, 32)).astype(np.float32), 0.3)
    layer = LinearSparse.from_dense(w, fmt=Format.CSR)
    x0 = jnp.asarray(RNG.standard_normal((1, 32)).astype(np.float32))

    def roll(layers):
        x, outs = x0, []
        for L in layers:
            x = jnp.tanh(L(x))
            outs.append(np.asarray(x))
        return outs

    base = roll([layer] * 4)
    hops = [layer, layer.activate(Format.ELL), layer.activate(Format.HYB),
            layer.activate(Format.COO)]
    for step, (a, b) in enumerate(zip(base, roll(hops))):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5,
                                   err_msg=f"switch at step {step}")


def test_retune_under_decode_parity():
    """retune(ncols) — the width-aware re-selection hook — may switch the
    stored format but never the numbers."""
    w = prune_magnitude(RNG.standard_normal((48, 48)).astype(np.float32), 0.2)
    layer = LinearSparse.from_dense(w, fmt=Format.COO)
    x1 = jnp.asarray(RNG.standard_normal((1, 48)).astype(np.float32))
    x64 = jnp.asarray(RNG.standard_normal((64, 48)).astype(np.float32))
    wide = layer.retune(ncols=64, tune="analytic")
    np.testing.assert_allclose(np.asarray(layer(x1)), np.asarray(wide(x1)),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(layer(x64)), np.asarray(wide(x64)),
                               rtol=1e-5, atol=1e-5)
