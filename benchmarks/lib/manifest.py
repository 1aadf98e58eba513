"""``BENCHMARK.json`` and the files it names. Everything that belongs to one
cell, configuration, traffic mix, per-layer metric or model family is found
by name: a metric's reader by the name in ``benchmarks/metrics/<metric>.json``,
a family (everything that knows the model: ``benchmarks/families/``) by the
name in the configuration's file. So a later PR adds files and one entry, a
configuration of another architecture among them, and edits nothing here."""

from __future__ import annotations

import importlib.util
import json
import os
import sys
import zlib
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
        if not self.config.get("family"):
            raise SystemExit(f"{cfg['file']} names no family")

    @property
    def family(self):
        """The module that knows this configuration's model, loaded on first
        use: it imports the system under test."""
        return load_family(self.config["family"], self.root)

    def _reports(self, metric: dict) -> bool:
        return "workloads" not in metric or self.name in metric["workloads"]

    def end_to_end(self) -> list:
        return [m for m in self.manifest["end_to_end"] if self._reports(m)]

    def per_layer(self) -> list:
        return [m for m in self.manifest["per_layer"] if self._reports(m)]


def _load_by_path(name: str, path: str):
    """The module (a package, where ``path`` is its ``__init__.py``) at
    ``path`` under ``name``, which ``sys.modules`` gets: a package's relative
    imports and a module's dataclasses look it up there."""
    package = os.path.basename(path) == "__init__.py"
    spec = importlib.util.spec_from_file_location(
        name, path, submodule_search_locations=(
            [os.path.dirname(path)] if package else None))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


_families: dict = {}


def load_family(family: str, root: str = ROOT):
    """``benchmarks/families/<family>.py`` of the checkout at ``root``, or
    the package ``benchmarks/families/<family>/``, loaded once by its path."""
    base = os.path.join(os.path.abspath(root), "benchmarks", "families",
                        family)
    if base not in _families:
        path = os.path.join(base, "__init__.py")
        if not os.path.isfile(path):
            path = base + ".py"
        if not os.path.isfile(path):
            raise SystemExit(f"no family {family!r}: neither {base}.py nor "
                             f"{base}/__init__.py")
        # the path is in the module's name: two checkouts in one process (the
        # tests') each keep a family of their own
        name = "benchmarks_family_%s_%08x" % (
            family.replace("-", "_"), zlib.crc32(base.encode()))
        _families[base] = _load_by_path(name, path)
    return _families[base]


def read_metric(name: str, ctx: dict, root: str = ROOT) -> Optional[float]:
    """Run the reader that ``benchmarks/metrics/<name>.json`` names over the
    run's context. ``None`` when it finds nothing to read."""
    bench = os.path.join(root, "benchmarks")
    spec = load_json(os.path.join(bench, "metrics", name + ".json"))
    mod = _load_by_path("benchmarks_reader_" + spec["reader"], os.path.join(
        bench, "readers", spec["reader"] + ".py"))
    return mod.read(ctx, spec)
