"""repro.obs — tracing, metrics and decision records.

Small, zero-heavy-dep pieces:

* :mod:`repro.obs.trace`   — ``span()``/``event()`` tracer gated by
  ``REPRO_TRACE=off|summary|full``, Chrome/Perfetto export, ``summary()``;
  active spans also annotate a JAX profile. Its docstring holds the one
  taxonomy of names: host spans (``select``, ``plan``, ``convert``,
  ``build``, ``solver.compile``/``solver.solve``, ``kernel.route``
  events) and the device scopes inside the jitted solves
  (``solver.*``, ``mg.l<k>.*``, ``dist.*``).
* :mod:`repro.obs.metrics` — named monotonic counters, gauges, and
  fixed-bucket histograms (p50/p95/p99 via :func:`metrics.quantile`) with
  ``snapshot()``/``reset()`` and order-independent ``scope()`` deltas.
* :mod:`repro.obs.ledger`  — bounded ring of structured decision records
  (format selections with CART paths, kernel-route vetoes, switch plans,
  serving requests), gated by ``REPRO_LEDGER`` (on by default).
* :mod:`repro.obs.explain` — replays the ledger into a human-readable
  decision trail. CLI: ``python -m repro.obs.explain``.
* :mod:`repro.obs.report`  — per-phase attribution tables
  (select/plan/convert/kernel/solver/build) from a live or exported
  trace. CLI: ``python -m repro.obs.report``.
"""
from repro.obs import ledger
from repro.obs import metrics
from repro.obs import trace
from repro.obs.trace import event, span, tracing

__all__ = ["ledger", "metrics", "trace", "span", "event", "tracing"]
