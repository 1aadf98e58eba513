"""BENCHMARK.json and every data file load and cross-refer, and adding a
cell, a configuration or a per-layer metric needs new files and one entry."""

import json
import os
import re

import pytest

from benchmarks.lib import manifest

import benchmark_tiny

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
ROOT = manifest.ROOT
BM = manifest.manifest()
CELLS = [w["name"] for w in BM["workloads"]]
E2E = {m["name"]: m for m in BM["end_to_end"]}


def _cells_of(metric):
    return metric.get("workloads", CELLS)


def test_keys_and_names():
    assert set(BM) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BM[k]]
    assert all(NAME.match(n) for n in names)
    for k in ("configs", "workloads"):
        ns = [x["name"] for x in BM[k]]
        assert len(ns) == len(set(ns))
    ms = [x["name"] for x in BM["end_to_end"] + BM["per_layer"]]
    assert len(ms) == len(set(ms))
    assert 1 <= BM["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024


def test_four_chip_cells_are_a_quarter_at_most():
    four = [w for w in BM["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(BM["workloads"]) // 4)
    assert all(w["chips"] in (1, 4) for w in BM["workloads"])


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_load(cell):
    c = manifest.Cell(cell)
    assert c.traffic["kind"] in ("train", "serve")
    assert c.own["limits"] and c.own["trace_seconds"] > 0
    assert len(c.entry["why"]) <= 200
    reported = {m["name"] for m in c.end_to_end()}
    assert "setup_s" in reported and len(reported) >= 2
    assert c.per_layer()


@pytest.mark.parametrize("cfg", [c["name"] for c in BM["configs"]])
def test_config_file(cfg):
    entry = next(c for c in BM["configs"] if c["name"] == cfg)
    assert entry["file"].startswith("benchmarks/")
    data = manifest.load_json(os.path.join(ROOT, entry["file"]))
    assert data["reduced"] == entry["reduced"] == []
    assert data["embedding_dim"] == 128 and len(data["table_sizes"]) == 26
    assert any(w["config"] == cfg for w in BM["workloads"])


@pytest.mark.parametrize("metric", [m["name"] for m in BM["per_layer"]])
def test_per_layer_metric(metric):
    m = next(x for x in BM["per_layer"] if x["name"] == metric)
    assert set(m) <= {"name", "unit", "better", "source", "layer", "moves",
                      "workloads"}
    assert m["source"] in ("device_trace", "program_span", "program_counter",
                           "host_clock")
    moved = E2E[m["moves"]]
    # every cell that reports the metric reports the end-to-end metric it moves
    assert set(_cells_of(m)) <= set(_cells_of(moved))
    spec = manifest.load_json(os.path.join(
        manifest.BENCH, "metrics", metric + ".json"))
    assert os.path.isfile(os.path.join(
        manifest.BENCH, "readers", spec["reader"] + ".py"))
    if metric.endswith("_roofline") or "mfu" in metric:
        assert m["unit"] == "%"


def test_every_end_to_end_bound():
    for m in BM["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    assert E2E["setup_s"]["bound"] == 0.1 and "workloads" not in E2E["setup_s"]


def test_layers_are_named_in_perf_md():
    with open(os.path.join(ROOT, "PERF.md")) as f:
        text = f.read()
    for m in BM["per_layer"]:
        assert m["layer"] in text, m["layer"]


def test_adding_a_cell_a_config_and_a_metric_edits_no_file(tmp_path):
    """A later PR's move, played in a temporary checkout: new files and one
    entry each in BENCHMARK.json, and the harness finds them by name."""
    root = benchmark_tiny.make(str(tmp_path))
    bench = os.path.join(root, "benchmarks")
    before = {}
    for d, _, fs in os.walk(bench):
        for f in fs:
            p = os.path.join(d, f)
            before[p] = os.path.getmtime(p), os.path.getsize(p)
    bm = manifest.manifest(root)
    cfg = manifest.load_json(os.path.join(bench, "configs", "dlrm-kaggle.json"))
    cfg["name"] = "dlrm-other"
    with open(os.path.join(bench, "configs", "dlrm-other.json"), "w") as f:
        json.dump(cfg, f)
    tr = manifest.load_json(os.path.join(bench, "traffic",
                                         "train_onehot_b65536.json"))
    tr["global_batch"] = 128
    with open(os.path.join(bench, "traffic", "train_b128.json"), "w") as f:
        json.dump(tr, f)
    with open(os.path.join(bench, "workloads", "other_train.json"), "w") as f:
        json.dump({"trace_seconds": 1, "limits": {"loss1": 0.1}}, f)
    with open(os.path.join(bench, "metrics", "other_steps.json"), "w") as f:
        json.dump({"reader": "other_steps"}, f)
    with open(os.path.join(bench, "readers", "other_steps.py"), "w") as f:
        f.write("def read(ctx, spec):\n    return float(ctx['steps'])\n")
    bm["configs"].append({"name": "dlrm-other", "source": "test",
                          "file": "benchmarks/configs/dlrm-other.json",
                          "reduced": [], "why": "test"})
    bm["workloads"].append({"name": "other_train", "config": "dlrm-other",
                            "traffic": "train_b128", "chips": 1, "why": "test"})
    bm["per_layer"].append({"name": "other_steps", "unit": "count",
                            "better": "higher", "source": "program_counter",
                            "layer": "step builder", "moves": "samples_per_s",
                            "workloads": ["other_train"]})
    for m in bm["end_to_end"]:
        if m["name"] == "samples_per_s":
            m["workloads"].append("other_train")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bm, f)
    rc, last, err = benchmark_tiny.run_cell(root, "other_train", 5, trace=1)
    assert rc == 0, err[-2000:]
    assert last["metrics"]["other_steps"]["value"] >= 1
    assert "lookup_ms" not in last["metrics"]
    for p, stamp in before.items():
        assert (os.path.getmtime(p), os.path.getsize(p)) == stamp, p
