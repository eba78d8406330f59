"""The benchmark finds its cells, configurations, traffic and metric
readers by name, from files alone, and BENCHMARK.json keeps its form."""
import json
import os
import re
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import spec  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bench_spec():
    with open(spec.SPEC_FILE) as f:
        return json.load(f)


def test_every_cell_loads_with_its_files(bench_spec):
    for w in bench_spec["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.chips == w["chips"]
        assert cell.config["name"] == w["config"]
        assert cell.traffic["stop"] in ("tolerance", "iterations")
        names = [m.name for m in cell.end_to_end]
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer
        assert all(callable(m.read) for m in cell.end_to_end + cell.per_layer)


def test_spec_keeps_the_contract_form(bench_spec):
    s = bench_spec
    assert set(s) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert s["command"][:2] == ["python3", "bench/run.py"]
    assert 1 <= s["run_seconds"] <= 51
    for p in s["paths"]:
        assert os.path.isdir(os.path.join(ROOT, p))
    names = [c["name"] for c in s["configs"]]
    assert len(set(names)) == len(names)
    used = {w["config"] for w in s["workloads"]}
    assert used == set(names)
    for c in s["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and all(NAME.match(k) for k in c["reduced"])
        assert any(c["file"].startswith(p + "/") for p in s["paths"])
        assert 1 <= len(c["why"]) <= 200 and 1 <= len(c["source"]) <= 200
    pairs = [(w["config"], w["traffic"]) for w in s["workloads"]]
    assert len(set(pairs)) == len(pairs)
    for w in s["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    cells = {w["name"] for w in s["workloads"]}
    e2e = {m["name"] for m in s["end_to_end"]}
    for m in s["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in s["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in e2e
        assert set(m["workloads"]) <= cells
        for cell in m["workloads"]:
            moved = [e for e in s["end_to_end"] if e["name"] == m["moves"]][0]
            assert cell in moved.get("workloads", cells)
    for m in s["end_to_end"] + s["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert os.path.isfile(os.path.join(spec.METRICS_DIR,
                                           m["name"] + ".py"))
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"


def test_a_new_cell_config_and_metric_need_new_files_only(tmp_path,
                                                           bench_spec):
    """A later PR adds a cell, a configuration, a traffic mix and a
    per-layer metric by adding files; nothing that is there changes."""
    s = json.loads(json.dumps(bench_spec))
    traffic_dir, metrics_dir = tmp_path / "traffic", tmp_path / "metrics"
    shutil.copytree(spec.TRAFFIC_DIR, traffic_dir)
    shutil.copytree(spec.METRICS_DIR, metrics_dir)
    cfg = json.load(open(os.path.join(ROOT, s["configs"][0]["file"])))
    cfg.update(name="hpcg-cg-104-auto", mode="multiformat", backend="auto")
    (tmp_path / "hpcg-cg-104-auto.json").write_text(json.dumps(cfg))
    s["configs"].append({"name": "hpcg-cg-104-auto", "source": "x",
                         "file": str(tmp_path / "hpcg-cg-104-auto.json"),
                         "reduced": [], "why": "x"})
    (traffic_dir / "to-tol-two.json").write_text(json.dumps(
        dict(json.load(open(traffic_dir / "to-tol.json")),
             answers_checked=2)))
    s["workloads"].append({"name": "hpcg104-cg-auto",
                           "config": "hpcg-cg-104-auto",
                           "traffic": "to-tol-two", "chips": 1, "why": "x"})
    (metrics_dir / "cg.solves.py").write_text(
        "def read(ctx):\n    return len(ctx.window.iters)\n")
    s["per_layer"].append({"name": "cg.solves", "unit": "solves",
                           "better": "higher", "source": "host_clock",
                           "layer": "solver", "moves": "solve_ms",
                           "workloads": ["hpcg104-cg-auto"]})
    s["end_to_end"][0]["workloads"].append("hpcg104-cg-auto")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(s))

    cell = spec.load_cell("hpcg104-cg-auto",
                          spec_file=str(tmp_path / "BENCHMARK.json"),
                          traffic_dir=str(traffic_dir),
                          metrics_dir=str(metrics_dir))
    assert cell.config["backend"] == "auto"
    assert cell.traffic["answers_checked"] == 2
    assert [m.name for m in cell.per_layer] == ["cg.solves"]
    assert [m.name for m in cell.end_to_end] == ["solve_ms", "setup_s"]

    class Ctx:
        window = type("W", (), {"iters": [5, 6, 7]})

    assert spec.read_metrics(cell.per_layer, Ctx) == {
        "cg.solves": {"value": 3.0, "unit": "solves"}}


def test_a_reader_that_finds_nothing_is_left_out(tmp_path):
    (tmp_path / "quiet.py").write_text("def read(ctx):\n    return None\n")
    m = spec.Metric("quiet", "%", "higher",
                    spec.load_reader("quiet", str(tmp_path)))
    assert spec.read_metrics([m], object()) == {}


def test_unknown_cell_is_an_error():
    with pytest.raises(KeyError):
        spec.load_cell("no-such-cell")


def test_peaks_name_their_source_and_refuse_unknown_kinds():
    v5e = spec.peaks_for("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["bf16_flops_per_s"] == 197e12
    assert "TPU v5e" in json.load(open(spec.PEAKS_FILE))["source"]
    with pytest.raises(KeyError):
        spec.peaks_for("cpu")
