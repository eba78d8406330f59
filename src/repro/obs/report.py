"""Render traces into per-phase attribution tables.

The span taxonomy (``repro.obs.trace``) prefixes every host span with
its phase: ``select.*``, ``plan.*``, ``convert.*``, ``kernel.*``,
``solver.*``, ``build.*``. This module folds a trace (live buffers or an
exported ``trace.json``) into the question the ROADMAP actually asks:
*where does the host's wall time go* — selection, planning, conversion,
kernel routing, building, or the solve itself? Device time by layer
comes from the named scopes in a JAX profile instead (README,
"Observability").

Attribution uses **self time**: a span's duration minus its children's,
so ``build.dist`` does not double-count the ``plan.*``/``convert.*``
spans it contains.

CLI::

    python -m repro.obs.report trace.json   # phase attribution
    python -m repro.obs.report              # ./trace.json
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Dict, List, Optional

PHASES = ("select", "plan", "convert", "kernel", "solver", "build")


def phase_of(name: str) -> str:
    head = name.split(".", 1)[0]
    return head if head in PHASES else "other"


# ---------------------------------------------------------------------------
# Trace loading
# ---------------------------------------------------------------------------


def load_trace(path: str) -> List[dict]:
    """Read an exported Chrome ``trace.json`` back into event dicts."""
    with open(path) as f:
        doc = json.load(f)
    evs = []
    for e in doc.get("traceEvents", []):
        if e.get("ph") != "X":
            continue
        args = dict(e.get("args", {}))
        evs.append({"name": e["name"], "ts": float(e.get("ts", 0.0)),
                    "dur": float(e.get("dur", 0.0)),
                    "tid": e.get("tid", 0),
                    "id": args.pop("span_id", None),
                    "parent": args.pop("parent_id", None),
                    "args": args})
    return evs


def live_events() -> List[dict]:
    from repro.obs import trace
    return trace.events()


# ---------------------------------------------------------------------------
# Phase attribution
# ---------------------------------------------------------------------------


def attribution(events: List[dict]) -> List[dict]:
    """Fold events into per-phase rows sorted by self time, largest first.

    Returns ``[{"phase", "calls", "total_ms", "self_ms", "share"}]``.
    ``share`` is self time over the summed self time of all phases (the
    trace's attributed wall clock).
    """
    self_us: Dict[Optional[int], float] = {}
    for e in events:
        self_us[e["id"]] = e["dur"]
    for e in events:
        p = e.get("parent")
        if p in self_us:
            self_us[p] -= e["dur"]

    rows: Dict[str, dict] = {}
    for e in events:
        ph = phase_of(e["name"])
        r = rows.setdefault(ph, {"phase": ph, "calls": 0, "total_ms": 0.0,
                                 "self_ms": 0.0})
        r["calls"] += 1
        r["total_ms"] += e["dur"] / 1e3
        r["self_ms"] += max(0.0, self_us.get(e["id"], 0.0)) / 1e3
    wall = sum(r["self_ms"] for r in rows.values()) or 1.0
    out = sorted(rows.values(), key=lambda r: -r["self_ms"])
    for r in out:
        r["share"] = r["self_ms"] / wall
    return out


def render_attribution(rows: List[dict]) -> str:
    if not rows:
        return "(no spans recorded — is REPRO_TRACE set?)"
    out = [f"{'phase':<10} {'calls':>7} {'total_ms':>10} {'self_ms':>10} "
           f"{'share':>7}",
           "-" * 48]
    for r in rows:
        out.append(f"{r['phase']:<10} {r['calls']:>7} {r['total_ms']:>10.2f} "
                   f"{r['self_ms']:>10.2f} {r['share']:>6.1%}")
    return "\n".join(out)


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        description="Render repro.obs traces into per-phase attribution")
    p.add_argument("trace", nargs="?", default=None,
                   help="exported trace.json (default: ./trace.json if present)")
    p.add_argument("--json", action="store_true",
                   help="emit the attribution rows as JSON instead of a table")
    args = p.parse_args(argv)

    trace_path = args.trace or ("trace.json" if os.path.exists("trace.json")
                                else None)
    if not trace_path:
        p.error("nothing to report: no trace.json found (pass its path)")
    evs = load_trace(trace_path)
    rows = attribution(evs)
    if args.json:
        print(json.dumps(rows, indent=1))
    else:
        print(f"# phase attribution ({trace_path}, {len(evs)} spans)")
        print(render_attribution(rows))
    return 0


if __name__ == "__main__":
    sys.exit(main())
