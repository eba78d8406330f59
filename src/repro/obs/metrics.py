"""Named monotonic counters, gauges, and bucketed histograms.

Counters are always on (one dict update under a lock — nanoseconds, and
only ever on host-side decision paths, never inside jitted device code).
They answer the questions the tracer's spans cannot: *how many* times did
each decision go each way over a whole run?

Standard counter names (incremented by the instrumented layers):

    planned_pulls            sanctioned symbolic-phase d2h transfers
                             (``repro.core.convert._planned_pull``)
    selection.cache_hit/.cache_miss
                             SelectionCache decision lookups
    kernel.route.pallas/.ref/.veto
                             ``kernel_route`` outcomes (veto = a record
                             exists but measured slower than ref)
    kernel.dia.x_resident/.x_streamed
                             DIA kernel calls traced with the whole padded
                             ``x`` in VMEM / with ``x`` windows streamed
                             from HBM (``repro.kernels.ops.dia_spmv``)
    replan.pattern_sig       memoised DistPlan format plans dropped
                             because the live pattern changed
    halo.bytes               bytes a traced ``dist_spmv`` exchanges per
                             call (recorded at trace time)
    trace.dropped_events     full-mode trace ring overwrites (the export
                             is truncated when this is nonzero)
    serve.requests/.tokens/.format_switch/.retune
                             DecodeEngine / LinearSparse serving events

Standard histogram names (``observe``):

    ell.padding_waste        1 - nnz/(m*k) of each planned ELL layout
    hyb.padding_waste        same for the ELL part of each HYB plan
    sell.padding_waste       1 - nnz/capacity of each planned SELL-C-σ
                             slicing (per-slice widths, post σ-sort)
    serve.latency_us         per-request submit→finish wall time
    serve.queue_us/.prefill_us/.decode_us
                             per-request phase latencies (DecodeEngine)
    serve.queue_depth        pending-queue depth sampled at each refill

Histograms carry **fixed bucket boundaries** (a 1-2-5 geometric series
spanning 1e-3 .. 1e9 by default, ~±25% resolution anywhere in range) so
p50/p95/p99 are reportable via :func:`quantile` without storing raw
samples; :func:`define_histogram` overrides the boundaries per name.
Gauges (:func:`set_gauge`) record last-written values (queue depth).

``snapshot()`` returns a plain dict (JSON-ready); ``scope()`` gives tests
an order-independent view: deltas against the values at scope entry, so
assertions stop depending on what ran earlier in the process.
"""
from __future__ import annotations

import bisect
import threading
from typing import Dict, Iterable, Optional, Sequence, Tuple

_LOCK = threading.Lock()
_COUNTERS: Dict[str, float] = {}
# name -> [count, sum, min, max, bucket_counts]; bucket_counts has
# len(boundaries) + 1 slots (the last one is the overflow bucket).
_HISTS: Dict[str, list] = {}
# name -> boundaries tuple (sorted, ascending); set lazily at first observe
# from DEFAULT_BUCKETS unless define_histogram() registered custom ones.
_BOUNDS: Dict[str, Tuple[float, ...]] = {}
_GAUGES: Dict[str, float] = {}


def _geometric_125(lo_exp: int, hi_exp: int) -> Tuple[float, ...]:
    """1-2-5 series boundaries covering 10**lo_exp .. 10**hi_exp."""
    out = []
    for e in range(lo_exp, hi_exp + 1):
        for m in (1.0, 2.0, 5.0):
            out.append(m * 10.0 ** e)
    return tuple(out)


# ~±25% quantile resolution from sub-millisecond fractions to 1e9 (covers
# 0..1 waste ratios, microsecond latencies, and multi-second builds alike).
DEFAULT_BUCKETS = _geometric_125(-3, 8)


def define_histogram(name: str, buckets: Sequence[float]) -> None:
    """Register fixed bucket boundaries for histogram ``name``.

    Must be called before the first ``observe`` for the name (an existing
    histogram keeps the boundaries it was created with — re-binning counts
    is impossible without the raw samples)."""
    b = tuple(sorted(float(v) for v in buckets))
    if not b:
        raise ValueError("buckets must be non-empty")
    with _LOCK:
        if name in _HISTS:
            raise ValueError(f"histogram {name!r} already has observations; "
                             "define buckets before the first observe()")
        _BOUNDS[name] = b


def inc(name: str, n: float = 1) -> None:
    """Increment counter ``name`` by ``n`` (created at 0 on first use)."""
    with _LOCK:
        _COUNTERS[name] = _COUNTERS.get(name, 0) + n


def set_gauge(name: str, value: float) -> None:
    """Set gauge ``name`` to ``value`` (last write wins)."""
    with _LOCK:
        _GAUGES[name] = float(value)


def gauge(name: str, default: float = 0) -> float:
    """Current value of gauge ``name``."""
    with _LOCK:
        return _GAUGES.get(name, default)


def observe(name: str, value: float) -> None:
    """Record ``value`` into histogram ``name`` (count/sum/min/max plus
    its fixed-boundary bucket — quantiles come from the buckets)."""
    v = float(value)
    with _LOCK:
        h = _HISTS.get(name)
        if h is None:
            bounds = _BOUNDS.setdefault(name, DEFAULT_BUCKETS)
            h = _HISTS[name] = [0, 0.0, float("inf"), float("-inf"),
                                [0] * (len(bounds) + 1)]
        h[0] += 1
        h[1] += v
        h[2] = min(h[2], v)
        h[3] = max(h[3], v)
        h[4][bisect.bisect_left(_BOUNDS[name], v)] += 1


def value(name: str, default: float = 0) -> float:
    """Current value of counter ``name``."""
    with _LOCK:
        return _COUNTERS.get(name, default)


def quantile(name: str, q: float) -> Optional[float]:
    """Estimate the ``q``-quantile (0..1) of histogram ``name`` from its
    bucket counts: linear interpolation of rank within the target bucket,
    clamped to the observed [min, max]. None when the histogram is empty.

    Resolution is the bucket width (~±25% with the default 1-2-5 series)
    — the price of never storing raw samples."""
    if not 0.0 <= q <= 1.0:
        raise ValueError(f"quantile {q} outside [0, 1]")
    with _LOCK:
        h = _HISTS.get(name)
        if h is None or h[0] == 0:
            return None
        count, lo, hi = h[0], h[2], h[3]
        counts = list(h[4])
        bounds = _BOUNDS[name]
    rank = q * (count - 1) + 0.5  # mid-rank convention
    seen = 0.0
    for i, c in enumerate(counts):
        if c == 0:
            continue
        if seen + c >= rank:
            # bucket i spans (bounds[i-1], bounds[i]]; the edge buckets
            # are clamped by the observed min/max.
            b_lo = bounds[i - 1] if i > 0 else lo
            b_hi = bounds[i] if i < len(bounds) else hi
            b_lo = max(b_lo, lo)
            b_hi = min(b_hi, hi)
            if b_hi <= b_lo:
                return float(b_lo)
            frac = (rank - seen) / c
            return float(b_lo + frac * (b_hi - b_lo))
        seen += c
    return float(hi)


def quantiles(name: str, qs: Sequence[float] = (0.5, 0.95, 0.99)
              ) -> Dict[str, Optional[float]]:
    """``{"p50": ..., "p95": ..., "p99": ...}`` for the standard cuts."""
    return {f"p{round(q * 100)}": quantile(name, q) for q in qs}


def snapshot() -> dict:
    """JSON-ready snapshot: counters, gauges, and histograms with their
    p50/p95/p99 bucket-estimated quantiles."""
    with _LOCK:
        hist_names = list(_HISTS)
        base = {
            name: {"count": h[0], "sum": h[1], "min": h[2], "max": h[3],
                   "mean": h[1] / max(1, h[0])}
            for name, h in _HISTS.items()}
        counters = dict(_COUNTERS)
        gauges = dict(_GAUGES)
    for name in hist_names:
        base[name].update(quantiles(name))
    return {"counters": counters, "gauges": gauges, "histograms": base}


def reset(names: Optional[Iterable[str]] = None) -> None:
    """Zero counters, gauges, and histograms (all, or just ``names``).
    Custom bucket definitions survive a reset."""
    with _LOCK:
        if names is None:
            _COUNTERS.clear()
            _HISTS.clear()
            _GAUGES.clear()
        else:
            for n in names:
                _COUNTERS.pop(n, None)
                _HISTS.pop(n, None)
                _GAUGES.pop(n, None)


class Scope:
    """Delta view of the counters since scope entry (see :func:`scope`)."""

    def __init__(self):
        with _LOCK:
            self._base = dict(_COUNTERS)

    def delta(self, name: str) -> float:
        """Counter growth since the scope opened."""
        return value(name) - self._base.get(name, 0)

    def deltas(self) -> Dict[str, float]:
        """All counters that moved since the scope opened."""
        with _LOCK:
            cur = dict(_COUNTERS)
        out = {}
        for name, v in cur.items():
            d = v - self._base.get(name, 0)
            if d:
                out[name] = d
        return out

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def scope() -> Scope:
    """``with metrics.scope() as s: ...; s.delta("planned_pulls")``.

    The scope never mutates the global counters, so nested/concurrent
    scopes and unrelated earlier activity cannot perturb each other —
    the fix for order-dependent transfer-count assertions.
    """
    return Scope()
