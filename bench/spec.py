"""Find a cell's configuration, traffic mix and metric readers by name.

``BENCHMARK.json`` names the cells; each names a configuration and a
traffic mix. Their files, and one reader module per metric, sit in
directories of their own, so a later cell, configuration or metric is
added by adding files alone.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Callable, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
SPEC_FILE = os.path.join(ROOT, "BENCHMARK.json")
TRAFFIC_DIR = os.path.join(BENCH_DIR, "traffic")
METRICS_DIR = os.path.join(BENCH_DIR, "metrics")
PEAKS_FILE = os.path.join(BENCH_DIR, "peaks.json")


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    better: str
    read: Callable  # ctx -> float | None


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[Metric]
    per_layer: List[Metric]


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_reader(name: str, metrics_dir: str = METRICS_DIR) -> Callable:
    """``read`` of ``<metrics_dir>/<name>.py`` (metric names hold dots, so
    the module is loaded from its path, not imported by name)."""
    path = os.path.join(metrics_dir, name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _metrics_for(entries, cell: str, metrics_dir: str) -> List[Metric]:
    return [Metric(e["name"], e["unit"], e["better"],
                   load_reader(e["name"], metrics_dir))
            for e in entries if cell in e.get("workloads", [cell])]


def load_cell(name: str, spec_file: str = SPEC_FILE,
              traffic_dir: str = TRAFFIC_DIR,
              metrics_dir: str = METRICS_DIR) -> Cell:
    """The cell ``name`` of ``spec_file``, with its files loaded."""
    spec = _load_json(spec_file)
    root = os.path.dirname(os.path.abspath(spec_file))
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in {spec_file}; "
                       f"known: {sorted(cells)}")
    w = cells[name]
    cfg_entry = {c["name"]: c for c in spec["configs"]}[w["config"]]
    config = _load_json(os.path.join(root, cfg_entry["file"]))
    traffic = _load_json(os.path.join(traffic_dir, w["traffic"] + ".json"))
    return Cell(name, int(w["chips"]), config, traffic,
                _metrics_for(spec["end_to_end"], name, metrics_dir),
                _metrics_for(spec["per_layer"], name, metrics_dir))


def peaks_for(kind: str, path: str = PEAKS_FILE) -> Dict[str, float]:
    """The published peaks of a ``device_kind``; an unknown kind is an
    error, never a default."""
    table = _load_json(path)["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} has no entry in {path}; "
                       f"known: {sorted(table)}")
    return table[kind]


def read_metrics(metrics: List[Metric], ctx) -> Dict[str, dict]:
    """Every metric whose reader finds something, as ``{name: {value,
    unit}}``; a reader that finds nothing returns None and is left out."""
    out: Dict[str, dict] = {}
    for m in metrics:
        v: Optional[float] = m.read(ctx)
        if v is not None:
            out[m.name] = {"value": float(v), "unit": m.unit}
    return out
