"""Kernel-config autotuning: records, cache, routing, policy decisions.

The acceptance-critical assertion lives here: under a *seeded* cache,
``resolve_backend("auto")`` never routes to a kernel config that measured
slower than the reference path — an unmeasured kernel is never presumed
faster, and a measured loser is vetoed.
"""
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import Format, banded_coo, convert, random_coo, spmv
from repro.core import ops as core_ops
from repro.kernels import ops as kops
from repro.tuning import (CACHE_PATH_ENV, FormatPolicy, PatternFeatures,
                          SelectionCache)
from repro.tuning import kernel_tune as kt

RNG = np.random.default_rng(0)


# ---------------------------------------------------------------------------
# records & keys
# ---------------------------------------------------------------------------


def test_kernel_record_json_roundtrip():
    rec = kt.KernelRecord("CSR", "spmv", {"tm": 256, "tk": 2048},
                          kernel_us=123.4, ref_us=456.7)
    back = kt.KernelRecord.from_json(rec.to_json())
    assert back == rec
    assert back.speedup == pytest.approx(456.7 / 123.4)
    # corrupt / foreign-schema values decode to None, never raise
    assert kt.KernelRecord.from_json("{not json") is None
    assert kt.KernelRecord.from_json(json.dumps({"v": 999})) is None


def test_shape_bucket_quantizes():
    # same power-of-two bucket: one tuned HPCG slab covers its siblings
    assert kt.shape_bucket(1000, 1000, 27000) == kt.shape_bucket(1024, 1024, 27648)
    assert kt.shape_bucket(512, 512, 13824) != kt.shape_bucket(4096, 4096, 110592)
    # density is part of the bucket: same dims, very different row fill
    assert kt.shape_bucket(1024, 1024, 4096) != kt.shape_bucket(1024, 1024, 262144)


def test_backend_tag_tracks_interpret_mode(monkeypatch):
    assert kt.backend_tag() == "cpu-interp"  # the CPU backend interprets
    monkeypatch.setattr(kops, "interpret_mode", lambda: False)
    assert kt.backend_tag().endswith("-native")


# ---------------------------------------------------------------------------
# tuner: persist + round-trip
# ---------------------------------------------------------------------------


def test_tune_kernel_persists_and_roundtrips(tmp_path):
    path = str(tmp_path / "kernels.json")
    A = convert(random_coo(5, (300, 280), density=0.04), Format.CSR)
    rec = kt.tune_kernel(A, cache=SelectionCache(path),
                         grid=kt.default_grid(A, smoke=True),
                         iters=2, inner=1)
    assert rec.fmt == "CSR" and rec.kernel_us > 0 and rec.ref_us > 0
    # a *fresh* cache handle (new process stand-in) sees the same winner
    fresh = kt.best_config(A, cache=SelectionCache(path))
    assert fresh is not None
    assert fresh.cfg == rec.cfg
    assert fresh.kernel_us == pytest.approx(rec.kernel_us)
    # the record rides the kernel: namespace of the shared store
    with open(path) as f:
        raw = json.load(f)
    assert all(k.startswith("kernel:") for k in raw)


def test_tuner_grid_configs_agree_with_ref():
    """Every config the tuner may emit computes the same SpMV as ref."""
    mats = [
        convert(random_coo(7, (97, 83), density=0.08), Format.CSR),
        convert(random_coo(8, (513, 401), density=0.02), Format.ELL),
        convert(banded_coo((300, 300), [-7, 0, 7]), Format.DIA),
        convert(random_coo(9, (200, 160), density=0.06), Format.HYB, k=2),
    ]
    for A in mats:
        x = jnp.asarray(RNG.standard_normal(A.shape[1]).astype(np.float32))
        y_ref = np.asarray(spmv(A, x, backend="ref"), np.float64)
        for cfg in kt.default_grid(A):
            y = np.asarray(spmv(A, x, backend="pallas", cfg=cfg), np.float64)
            np.testing.assert_allclose(
                y, y_ref, rtol=2e-5, atol=2e-5,
                err_msg=f"{type(A).__name__} cfg={cfg}")


# ---------------------------------------------------------------------------
# routing: auto never takes a measured-slower config (seeded cache)
# ---------------------------------------------------------------------------


def _seed(A, kernel_us, ref_us, cfg=None):
    cache = kt.default_kernel_cache()
    rec = kt.KernelRecord(Format(A.format).name, "spmv",
                          cfg or {"tm": 64, "tk": 128}, kernel_us, ref_us)
    cache.put_raw(kt.kernel_key(Format(A.format), A.shape[0], A.shape[1],
                                A.nnz), rec.to_json())
    return rec


def test_auto_routing_seeded_cache(tmp_path, monkeypatch):
    monkeypatch.setenv(CACHE_PATH_ENV, str(tmp_path / "sel.json"))
    A = convert(random_coo(11, (300, 300), density=0.05), Format.CSR)
    x = jnp.asarray(RNG.standard_normal(300).astype(np.float32))

    # 1. no record: never presume the kernel is faster
    assert core_ops.kernel_route(A) == ("ref", None)
    assert core_ops.resolve_backend("auto", A) == "ref"

    # 2. measured slower: vetoed
    _seed(A, kernel_us=100.0, ref_us=50.0)
    assert core_ops.kernel_route(A) == ("ref", None)
    assert core_ops.resolve_backend("auto", A) == "ref"

    # 3. measured faster: routed, with the winning config threaded
    rec = _seed(A, kernel_us=50.0, ref_us=100.0, cfg={"tm": 128, "tk": 512})
    backend, cfg = core_ops.kernel_route(A)
    assert backend == "pallas" and cfg == rec.cfg
    assert core_ops.resolve_backend("auto", A) == "pallas"
    np.testing.assert_allclose(np.asarray(spmv(A, x, backend="auto")),
                               np.asarray(spmv(A, x, backend="ref")),
                               rtol=1e-4, atol=1e-4)

    # 4. explicit backends always pass through untouched
    assert core_ops.resolve_backend("ref", A) == "ref"
    assert core_ops.resolve_backend("pallas", A) == "pallas"


def test_auto_routing_interpret_tag_isolation(tmp_path, monkeypatch):
    """A config tuned under interpret mode never routes native kernels."""
    monkeypatch.setenv(CACHE_PATH_ENV, str(tmp_path / "sel.json"))
    A = convert(random_coo(12, (256, 256), density=0.05), Format.CSR)
    _seed(A, kernel_us=10.0, ref_us=100.0)
    assert core_ops.kernel_route(A)[0] == "pallas"
    monkeypatch.setattr(kops, "interpret_mode", lambda: False)
    # same cache, native tag: the interp-keyed record must not match
    assert core_ops.kernel_route(A) == ("ref", None)


# ---------------------------------------------------------------------------
# policy decisions: (format, backend, cfg) tuples, schema v2 + v1 compat
# ---------------------------------------------------------------------------


def test_decision_v2_schema_roundtrip_and_v1_compat(tmp_path):
    cache = SelectionCache(str(tmp_path / "s.json"))
    cache.put_decision("k2", Format.DIA, "pallas", {"tm": 512}, tag="cpu-interp")
    assert cache.get("k2") == Format.DIA           # legacy reader still works
    assert cache.get_decision("k2") == (Format.DIA, "pallas", {"tm": 512},
                                        "cpu-interp")
    cache.put("k1", Format.ELL)                    # legacy writer
    assert cache.get_decision("k1") == (Format.ELL, None, None, None)
    # the v2 value survives a disk round-trip
    fresh = SelectionCache(cache.path)
    assert fresh.get_decision("k2") == (Format.DIA, "pallas", {"tm": 512},
                                        "cpu-interp")
    # format-only v2 decisions are representable too
    cache.put_decision("k3", Format.CSR)
    assert cache.get_decision("k3") == (Format.CSR, None, None, None)


def test_cached_policy_pins_kernel_decision(tmp_path, monkeypatch):
    monkeypatch.setenv(CACHE_PATH_ENV, str(tmp_path / "sel.json"))
    A = banded_coo((512, 512), [-1, 0, 1])
    fmt = FormatPolicy("ml").select(A).best
    feats = PatternFeatures.from_coo(A)
    # seed a winning kernel record for the picked format's shape bucket
    rec = kt.KernelRecord(fmt.name, "spmv", {"tm": 256}, 10.0, 100.0)
    kt.default_kernel_cache().put_raw(
        kt.kernel_key(fmt, feats.m, feats.n, feats.nnz), rec.to_json())

    policy = FormatPolicy("cached", cache=SelectionCache(str(tmp_path / "sel.json")))
    cold = policy.select(A)
    assert cold.best == fmt
    assert cold.backend == "pallas" and cold.cfg == {"tm": 256}
    warm = policy.select(A)
    assert warm.mode == "cached"
    assert (warm.best, warm.backend, warm.cfg) == (fmt, "pallas", {"tm": 256})


def test_cached_policy_pin_never_replays_across_modes(tmp_path, monkeypatch):
    """A (backend, cfg) pinned under interpret mode must not replay in a
    native-mode process sharing the cache file: the pin is re-derived from
    the current mode's kernel records instead (here: none -> unpinned)."""
    monkeypatch.setenv(CACHE_PATH_ENV, str(tmp_path / "sel.json"))
    A = banded_coo((512, 512), [-1, 0, 1])
    fmt = FormatPolicy("ml").select(A).best
    feats = PatternFeatures.from_coo(A)
    rec = kt.KernelRecord(fmt.name, "spmv", {"tm": 8192}, 10.0, 100.0)
    kt.default_kernel_cache().put_raw(
        kt.kernel_key(fmt, feats.m, feats.n, feats.nnz), rec.to_json())
    cache = SelectionCache(str(tmp_path / "sel.json"))
    cold = FormatPolicy("cached", cache=cache).select(A)
    assert cold.backend == "pallas"  # pinned under the interp tag

    monkeypatch.setattr(kops, "interpret_mode", lambda: False)  # "native"
    native = FormatPolicy("cached", cache=SelectionCache(cache.path)).select(A)
    assert native.mode == "cached"
    assert native.best == fmt        # the format pick itself is reused
    assert native.backend is None    # the interp-tuned pin is NOT replayed


def test_profile_select_over_backends(tmp_path, monkeypatch):
    monkeypatch.setenv(CACHE_PATH_ENV, str(tmp_path / "sel.json"))
    from repro.tuning import profile_select
    A = banded_coo((256, 256), [-4, 0, 4])
    x = jnp.ones((256,), jnp.float32)
    rep = profile_select(A, x, candidates=(Format.CSR, Format.DIA),
                         iters=2, inner=1, backends=("ref", "pallas"))
    assert rep.best in (Format.CSR, Format.DIA)
    assert rep.backend in ("ref", "pallas")  # the decision is now a tuple
    # historical call shape stays format-only
    rep1 = profile_select(A, x, candidates=(Format.DIA,), iters=2, inner=1)
    assert rep1.backend is None and rep1.cfg is None


# ---------------------------------------------------------------------------
# rhs-width bucket: a record tuned at b=1 is never replayed at b=256
# ---------------------------------------------------------------------------


def test_spmm_keys_carry_width_bucket():
    k1 = kt.kernel_key(Format.CSR, 1024, 1024, 4096, op="spmm", ncols=1)
    k256 = kt.kernel_key(Format.CSR, 1024, 1024, 4096, op="spmm", ncols=256)
    assert k1 != k256 and "|b0|" in k1 and "|b8|" in k256
    # ncols=None aliases with the b=1 bucket (read/write consistent)
    assert kt.kernel_key(Format.CSR, 1024, 1024, 4096, op="spmm") == k1
    # spmv keys never grew a width segment (historical records stay valid)
    s = kt.kernel_key(Format.CSR, 1024, 1024, 4096, op="spmv", ncols=256)
    assert s == kt.kernel_key(Format.CSR, 1024, 1024, 4096, op="spmv")
    # widths in one pow2 bucket share a record; different ops never do
    assert kt.kernel_key(Format.CSR, 1024, 1024, 4096, op="spmm", ncols=200) \
        == kt.kernel_key(Format.CSR, 1024, 1024, 4096, op="spmm", ncols=256)
    assert kt.kernel_key(Format.CSR, 1024, 1024, 4096, op="spmm_t", ncols=1) \
        != k1


def test_b1_record_not_consulted_at_b256(tmp_path):
    """The regression the width axis exists to prevent: tune at b=1, then
    look up at b=256 — the narrow record must be invisible."""
    cache = SelectionCache(str(tmp_path / "k.json"))
    A = convert(random_coo(0, (256, 256), 0.05), Format.CSR)
    rec = kt.tune_kernel(A, op="spmm", B_cols=1, cache=cache,
                         grid=[{"tm": 128, "tk": 256, "tn": 1}],
                         iters=1, inner=1)
    assert rec.cfg["tn"] == 1
    assert kt.best_config(A, op="spmm", ncols=1, cache=cache) is not None
    assert kt.best_config(A, op="spmm", ncols=256, cache=cache) is None
    assert kt.best_config(A, op="spmm", cache=cache) is not None  # b0 alias
    # the spmm record is invisible to every other op too
    assert kt.best_config(A, op="spmv", cache=cache) is None
    assert kt.best_config(A, op="spmm_t", ncols=1, cache=cache) is None


def test_auto_route_respects_width_bucket(tmp_path, monkeypatch):
    """spmm(backend="auto") consults the record for ITS width bucket: a
    winner at b=1 routes pallas at b=1 but ref at b=256."""
    monkeypatch.setenv(CACHE_PATH_ENV, str(tmp_path / "sel.json"))
    A = convert(random_coo(1, (128, 128), 0.1), Format.CSR)
    rec = kt.KernelRecord("CSR", "spmm", {"tm": 128, "tk": 256, "tn": 1},
                          kernel_us=1.0, ref_us=100.0)
    kt.default_kernel_cache().put_raw(
        kt.kernel_key(Format.CSR, 128, 128, int(A.nnz), op="spmm", ncols=1),
        rec.to_json())
    assert core_ops.kernel_route(A, op="spmm", ncols=1) == \
        ("pallas", {"tm": 128, "tk": 256, "tn": 1})
    assert core_ops.kernel_route(A, op="spmm", ncols=256) == ("ref", None)
    # and the full op agrees with ref numerics on both routes
    B1 = jnp.ones((128, 1), jnp.float32)
    B256 = jnp.ones((128, 256), jnp.float32)
    for B in (B1, B256):
        np.testing.assert_allclose(
            np.asarray(core_ops.spmm(A, B, backend="auto")),
            np.asarray(core_ops.spmm(A, B, backend="ref")),
            rtol=1e-4, atol=1e-4)


def test_cached_policy_width_buckets_store_distinct_decisions(tmp_path,
                                                              monkeypatch):
    """FormatPolicy("cached") keys spmm_t decisions by width bucket: a
    pallas pin recorded at b=1 must not leak into the b=256 decision."""
    monkeypatch.setenv(CACHE_PATH_ENV, str(tmp_path / "sel.json"))
    A = random_coo(2, (256, 256), 0.05)
    fmt = FormatPolicy("ml").select(A).best
    feats = PatternFeatures.from_coo(A)
    rec = kt.KernelRecord(fmt.name, "spmm_t", {"tm": 128, "tn": 1},
                          kernel_us=1.0, ref_us=100.0)
    kt.default_kernel_cache().put_raw(
        kt.kernel_key(fmt, feats.m, feats.n, feats.nnz, op="spmm_t",
                      ncols=1), rec.to_json())
    cache = SelectionCache(str(tmp_path / "sel.json"))
    narrow = FormatPolicy("cached", cache=cache).select(A, op="spmm_t",
                                                        ncols=1)
    wide = FormatPolicy("cached", cache=cache).select(A, op="spmm_t",
                                                      ncols=256)
    assert narrow.backend == "pallas" and narrow.cfg == {"tm": 128, "tn": 1}
    assert wide.backend is None  # no b=256 measurement -> no pin
    # both are warm on re-read, from distinct cache entries
    warm_n = FormatPolicy("cached", cache=cache).select(A, op="spmm_t",
                                                        ncols=1)
    warm_w = FormatPolicy("cached", cache=cache).select(A, op="spmm_t",
                                                        ncols=256)
    assert warm_n.mode == "cached" and warm_n.backend == "pallas"
    assert warm_w.mode == "cached" and warm_w.backend is None


# ---------------------------------------------------------------------------
# the CLI's smoke tune (``python -m repro.tuning.kernel_tune --smoke``)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def smoke_cache(tmp_path_factory):
    """The cache file ``--smoke --cache PATH`` leaves behind."""
    path = str(tmp_path_factory.mktemp("ktune") / "smoke_kernels.json")
    kt.main(["--smoke", "--cache", path])
    return path


def test_smoke_cache_roundtrips_with_sell_geometry(smoke_cache):
    """Every record reads back through a fresh handle, and the tuned SELL
    record names the container geometry (c, sigma) it was measured on."""
    with open(smoke_cache) as f:
        raw = json.load(f)
    fresh = SelectionCache(smoke_cache)
    assert raw and all(fresh.get_raw(k) == v for k, v in raw.items())
    sell = {k: kt.KernelRecord.from_json(v) for k, v in raw.items()
            if k.startswith("kernel:") and "|SELL|" in k}
    assert sell, sorted(raw)
    for k, rec in sell.items():
        assert "c" in rec.cfg and "sigma" in rec.cfg, (k, rec.cfg)


def test_smoke_cache_auto_never_routes_a_loser(smoke_cache, monkeypatch):
    """With the smoke tune's cache as the process default, ``auto`` takes
    Pallas exactly where the measured record beat the reference path."""
    monkeypatch.setenv(CACHE_PATH_ENV, smoke_cache)
    fresh = SelectionCache(smoke_cache)
    for A in kt._suite(smoke=True):
        rec = kt.best_config(A, cache=fresh)
        assert rec is not None, type(A).__name__
        want = "pallas" if rec.speedup >= 1.0 else "ref"
        assert core_ops.resolve_backend("auto", A) == want, rec
