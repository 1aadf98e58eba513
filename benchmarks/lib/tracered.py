"""From the profiler's trace to numbers: which operations ran on which device,
under which of the program's scopes, and what the harness was doing on the
host meanwhile. Reads the ``.xplane.pb`` with nothing but JAX.

The scope of an operation is every ``detpu/<name>`` in its metadata joined by
``/`` (the rule of the program's ``utils/traceparse.py`` and
``utils/obs.SCOPE_RE``, copied: a later PR may move the program's reader, not
this one).
"""

from __future__ import annotations

import dataclasses
import glob
import gzip
import json
import os
import re
import shutil
from typing import Dict, List, Sequence, Tuple

import jax

SCOPE_RE = re.compile(r"detpu/([\w.\-]+)")
SPAN_PREFIX = "bench/"
UNSCOPED = "_unscoped_"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class Op:
    device: int
    scope: str      # "" when the operation carries none
    name: str
    start: float    # seconds on the trace's clock
    dur: float


@dataclasses.dataclass
class Trace:
    ops: List[Op]
    modules: List[Op]
    spans: List[Tuple[str, float, float]]   # harness spans: name, start, dur
    devices: int


class Capture:
    """Context manager around the traced window: starts and stops the
    profiler, writing under one fixed directory that is emptied first."""

    def __init__(self, path: str):
        self.path = path

    def __enter__(self):
        shutil.rmtree(self.path, ignore_errors=True)
        os.makedirs(self.path, exist_ok=True)
        # without the Python tracer: it slows the host by enough to push a
        # serving cell past its knee, and fills the trace's room for events
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(self.path, profiler_options=options)
        return self

    def __exit__(self, *exc):
        jax.profiler.stop_trace()
        return False

    @staticmethod
    def span(name: str):
        return jax.profiler.TraceAnnotation(SPAN_PREFIX + name)

    def load(self) -> Trace:
        files = glob.glob(os.path.join(self.path, "**", "*.trace.json.gz"),
                          recursive=True)
        if len(files) != 1:
            raise SystemExit(f"expected one trace under {self.path}, found "
                             f"{files}")
        return load(files[0])


def scope_of(op_name: str) -> str:
    return "/".join(SCOPE_RE.findall(op_name or ""))


def load(path: str) -> Trace:
    """One ``.trace.json.gz`` as the profiler writes it beside its
    ``.xplane.pb``: the devices' ``XLA Ops`` and ``XLA Modules`` lines, the
    operation's scope from its ``tf_op``, and the harness's own spans."""
    with gzip.open(path, "rb") as f:
        events = json.loads(f.read().decode("utf-8"))["traceEvents"]
    procs, threads = {}, {}
    for e in events:
        if e.get("ph") == "M" and e.get("name") == "process_name":
            procs[e["pid"]] = e["args"]["name"]
        elif e.get("ph") == "M" and e.get("name") == "thread_name":
            threads[e["pid"], e["tid"]] = e["args"]["name"]
    device_of = {pid: i for i, pid in enumerate(sorted(
        p for p, n in procs.items() if n.startswith("/device:TPU")))}
    ops, modules, spans = [], [], []
    for e in events:
        if e.get("ph") != "X" or not e.get("dur"):
            continue
        name = str(e.get("name", ""))
        start, dur = e["ts"] * 1e-6, e["dur"] * 1e-6
        dev = device_of.get(e["pid"])
        if dev is not None:
            line = threads.get((e["pid"], e["tid"]))
            if line == OPS_LINE:
                ops.append(Op(dev, scope_of((e.get("args") or {}).get("tf_op")),
                              name, start, dur))
            elif line == MODULES_LINE:
                modules.append(Op(dev, "", name, start, dur))
        elif name.startswith(SPAN_PREFIX):
            spans.append((name[len(SPAN_PREFIX):], start, dur))
    return Trace(ops=ops, modules=modules, spans=spans,
                 devices=len(device_of))


def union(intervals) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(u) -> float:
    return sum(e - s for s, e in u)


def busy_seconds(trace: Trace) -> float:
    """Seconds in which an operation ran, averaged over the devices."""
    if not trace.devices:
        return 0.0
    return sum(total(union((o.start, o.start + o.dur) for o in trace.ops
                           if o.device == d))
               for d in range(trace.devices)) / trace.devices


def matches(scope: str, prefixes, exclude=()) -> bool:
    parts = scope.split("/") if scope else []
    if any(x in p for p in parts for x in exclude):
        return False
    return any(p.startswith(x) for p in parts for x in prefixes)


def scope_seconds(trace: Trace, prefixes=(), exclude=(),
                  unscoped: bool = False) -> float:
    """Device seconds of the operations under the scopes, averaged over the
    devices. A scope matches when one part of its path starts with one of
    ``prefixes`` and none holds one of ``exclude``."""
    if not trace.devices:
        return 0.0
    pick = (lambda o: not o.scope) if unscoped else \
        (lambda o: matches(o.scope, prefixes, exclude))
    return sum(o.dur for o in trace.ops if pick(o)) / trace.devices


def exposed_seconds(trace: Trace, prefixes) -> float:
    """The part of the scopes' operations during which nothing else ran on the
    same device, averaged over the devices."""
    if not trace.devices:
        return 0.0
    out = 0.0
    for d in range(trace.devices):
        mine = union((o.start, o.start + o.dur) for o in trace.ops
                     if o.device == d and matches(o.scope, prefixes))
        rest = union((o.start, o.start + o.dur) for o in trace.ops
                     if o.device == d and not matches(o.scope, prefixes))
        hidden, j = 0.0, 0
        for s, e in mine:
            while j < len(rest) and rest[j][1] <= s:
                j += 1
            k = j
            while k < len(rest) and rest[k][0] < e:
                hidden += min(e, rest[k][1]) - max(s, rest[k][0])
                k += 1
        out += total(mine) - hidden
    return out / trace.devices


def breakdown(trace: Trace, top: int = 10) -> Dict[str, list]:
    """The operations that took most device time (summed over the devices,
    named ``scope:op``) and the idle time of device 0 by the harness span the
    host was in."""
    by_op: Dict[str, float] = {}
    for o in trace.ops:
        key = f"{o.scope or UNSCOPED}:{o.name}"
        by_op[key] = by_op.get(key, 0.0) + o.dur
    dev0 = union((o.start, o.start + o.dur) for o in trace.ops
                 if o.device == 0)
    gaps = [(a[1], b[0]) for a, b in zip(dev0, dev0[1:]) if b[0] > a[1]]
    spans = sorted((s, s + d, n) for n, s, d in trace.spans)
    by_span: Dict[str, float] = {}
    first = 0    # spans before it end before this gap, and so before the next
    for gs, ge in gaps:
        while first < len(spans) and spans[first][1] <= gs:
            first += 1
        covered = 0.0
        k = first
        while k < len(spans) and spans[k][0] < ge:
            ss, se, name = spans[k]
            k += 1
            if se <= gs:    # a short span behind a long one that goes on
                continue
            part = min(ge, se) - max(gs, ss)
            by_span[name] = by_span.get(name, 0.0) + part
            covered += part
        rest = (ge - gs) - covered
        if rest > 0:
            by_span["_no_harness_span_"] = \
                by_span.get("_no_harness_span_", 0.0) + rest
    rank = lambda d: [[k, v] for k, v in  # noqa: E731
                      sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return {"device_ops": rank(by_op), "idle_gaps": rank(by_span)}
