"""The program's own host spans in the traced run's trace: the events that
``utils/obs.span`` writes (``jax.profiler.TraceAnnotation("detpu/<name>")``)
on the host threads' lines, on the clock of the device's operations.
``tracered.load`` keeps only the harness's ``bench/`` spans; this reads the
same file again for the ``detpu/`` ones. A program without such spans (a
parent commit, a train cell) gives an empty list, and no trace gives ``None``.
"""

from __future__ import annotations

import dataclasses
import functools
import glob
import gzip
import json
import os
from typing import Dict, List, Optional, Tuple

from . import manifest

PREFIX = "detpu/"
FLUSH = "serve/flush"


@dataclasses.dataclass
class Span:
    name: str                   # without the prefix: "serve/h2d"
    thread: Tuple[int, int]     # the trace's (pid, tid)
    start: float                # seconds on the trace's clock
    dur: float
    args: Dict[str, str]

    @property
    def end(self) -> float:
        return self.start + self.dur


def load(path: str) -> List[Span]:
    """Every complete host event named ``detpu/...`` of one ``.trace.json.gz``.
    An operation on a device carries its scopes in its metadata, not in its
    name; the devices' processes are left out all the same."""
    with gzip.open(path, "rb") as f:
        events = json.loads(f.read().decode("utf-8"))["traceEvents"]
    devices = {e["pid"] for e in events
               if e.get("ph") == "M" and e.get("name") == "process_name"
               and e["args"]["name"].startswith("/device:")}
    out = []
    for e in events:
        name = str(e.get("name", ""))
        if e.get("ph") != "X" or not name.startswith(PREFIX) \
                or e["pid"] in devices:
            continue
        out.append(Span(name[len(PREFIX):], (e["pid"], e.get("tid", 0)),
                        e["ts"] * 1e-6, e.get("dur", 0.0) * 1e-6,
                        dict(e.get("args") or {})))
    return out


@functools.lru_cache(maxsize=None)
def of_run(root: str = manifest.ROOT) -> Optional[List[Span]]:
    """The spans of this process's traced run: the one trace under
    ``<root>/.bench_trace``, read once a process. ``None`` without one."""
    files = glob.glob(os.path.join(root, ".bench_trace", "**",
                                   "*.trace.json.gz"), recursive=True)
    return load(files[0]) if len(files) == 1 else None


def of_ctx(ctx: dict) -> Optional[List[Span]]:
    """``ctx["prog_spans"]`` where a test hands them in, else the run's."""
    if "prog_spans" in ctx:
        return ctx["prog_spans"]
    return of_run() if ctx.get("trace") is not None else None


def flushes(spans: List[Span]) -> List[Tuple[Span, List[Span]]]:
    """Each ``serve/flush`` span with the spans of its own thread that lie
    inside it, in order of start. A span outside every flush (the warm-up's
    pack) belongs to none. The trace rounds to the nanosecond."""
    out: List[Tuple[Span, List[Span]]] = []
    by_thread: Dict[Tuple[int, int], List[Span]] = {}
    for s in sorted(spans, key=lambda s: (s.start, -s.dur)):
        by_thread.setdefault(s.thread, []).append(s)
    for mine in by_thread.values():
        flush = None
        for s in mine:
            if s.name == FLUSH:
                flush = (s, [])
                out.append(flush)
            elif flush is not None and s.end <= flush[0].end + 2e-9:
                flush[1].append(s)
    return sorted(out, key=lambda fc: fc[0].start)
