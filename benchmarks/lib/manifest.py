"""``BENCHMARK.json`` and the data files it names. Everything that belongs to
one cell, configuration, traffic mix or per-layer metric is found by name, so
a later PR adds files and one entry and edits nothing here."""

from __future__ import annotations

import importlib.util
import json
import os
from typing import Optional

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmarks")


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def manifest(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def _named(entries, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"BENCHMARK.json names no {what} {name!r}")


class Cell:
    """One entry of ``workloads`` with the files it leads to."""

    def __init__(self, name: str, root: str = ROOT):
        self.root = root
        self.manifest = manifest(root)
        self.entry = _named(self.manifest["workloads"], name, "workload")
        self.name = name
        self.chips = int(self.entry["chips"])
        cfg = _named(self.manifest["configs"], self.entry["config"], "config")
        self.config = load_json(os.path.join(root, cfg["file"]))
        bench = os.path.join(root, "benchmarks")
        self.traffic = load_json(os.path.join(
            bench, "traffic", self.entry["traffic"] + ".json"))
        # the cell's own file: the limits of its comparison and what else
        # belongs to the pairing and to neither side of it
        self.own = load_json(os.path.join(bench, "workloads", name + ".json"))
        if int(self.config["chips"]) != self.chips:
            raise SystemExit(f"{name}: the cell asks for {self.chips} chips "
                             f"and its configuration for {self.config['chips']}")

    def _reports(self, metric: dict) -> bool:
        return "workloads" not in metric or self.name in metric["workloads"]

    def end_to_end(self) -> list:
        return [m for m in self.manifest["end_to_end"] if self._reports(m)]

    def per_layer(self) -> list:
        return [m for m in self.manifest["per_layer"] if self._reports(m)]


def read_metric(name: str, ctx: dict, root: str = ROOT) -> Optional[float]:
    """Run the reader that ``benchmarks/metrics/<name>.json`` names over the
    run's context. ``None`` when it finds nothing to read."""
    bench = os.path.join(root, "benchmarks")
    spec = load_json(os.path.join(bench, "metrics", name + ".json"))
    path = os.path.join(bench, "readers", spec["reader"] + ".py")
    mod_spec = importlib.util.spec_from_file_location(
        "benchmarks_reader_" + spec["reader"], path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read(ctx, spec)
