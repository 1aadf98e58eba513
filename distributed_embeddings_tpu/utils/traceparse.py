"""Chrome-trace parsing of XLA profiler captures — the measured half of
the phase observatory.

``DETPU_PROFILE_DIR`` (``obs.profile_trace``) has dumped raw TensorBoard
trace directories since PR 2, and nothing in the repo ever *read* one:
every phase number so far is modeled (``analysis/schedule_audit.py``
prices bytes, it does not look at a clock). This module is the reader.
It parses the ``.trace.json[.gz]`` files ``jax.profiler.trace`` writes
(Chrome trace-event JSON under ``plugins/profile/<run>/``), attributes
every XLA op-level event to its ``obs.scope`` phase, and reduces the
events to measured per-phase durations and wall-clock interval unions —
the inputs :mod:`..analysis.phase_profile` turns into a
:class:`~..analysis.phase_profile.PhaseProfile` and calibrates against
the schedule auditor's cost model.

Attribution has two tiers, because backends disagree about where the
scope names survive:

* **metadata-carrying events** (TPU-style): the event's ``args`` (or its
  ``name``) embed the XLA ``op_name``, and :data:`~.obs.SCOPE_RE` — the
  SAME regex the HLO census and schedule auditor use, owned by
  ``utils/obs.py`` next to the :func:`~.obs.scope` writer — extracts the
  ``detpu/...`` path directly;
* **bare-name events** (this container's CPU backend): the event name is
  just the HLO instruction name (``all-to-all.6``,
  ``cosine_add_fusion.clone``). The caller passes a ``resolver`` built
  from the compiled module's own text (instruction name -> phase;
  :func:`~..analysis.phase_profile.HloPhaseIndex` provides it), joining
  the measured events against exactly the program the static gates
  audit.

Like the rest of :mod:`..utils`'s host-side layer this module never
imports jax: parsing a trace somebody else captured must work in
processes that never load a backend (``tools/obs_report.py --selftest``
exercises exactly that on a checked-in miniature trace).
"""

from __future__ import annotations

import dataclasses
import glob
import gzip
import json
import os
from typing import (Any, Callable, Dict, Iterable, List, Optional,
                    Sequence, Tuple)

from . import obs

#: event-name prefixes of host-side bookkeeping the profiler interleaves
#: with the op stream (python frames, threadpool markers, runtime
#: plumbing) — never attributable device work
HOST_EVENT_PREFIXES = (
    "$",                    # python frames ($module.py:line fn)
    "ThreadpoolListener",
    "ThunkExecutor",
    "TfrtCpu", "PjRt", "Pjit", "ParseArguments", "ExecuteContext",
    "DevicePut", "D2D ", "H2D ", "D2H ", "BufferFromHost",
    "TransferTo", "TransferFrom", "CopyTo", "CopyFrom",
)

#: phase-leaf substrings that mark a phase as a cross-chip exchange (the
#: collective phases of the step schedule)
COLLECTIVE_PHASE_MARK = "all_to_all"

#: step-attribution groups of the measured breakdown (exchange vs lookup
#: vs apply vs dense — the ROADMAP item 2 vocabulary)
GROUPS = ("exchange", "lookup", "dense", "apply", "streaming", "other")


@dataclasses.dataclass
class TraceEvent:
    """One complete (``ph == "X"``) trace event, microsecond units."""
    name: str
    ts: float                 # begin, us
    dur: float                # duration, us
    pid: int
    tid: int
    phase: str                # detpu scope path ("" = unattributed)
    resolved: bool            # joined to an HLO instruction / op metadata

    @property
    def end(self) -> float:
        return self.ts + self.dur


def is_host_event(name: str) -> bool:
    """Whether an event name is host-side bookkeeping (python frames,
    runtime plumbing) rather than a candidate op event."""
    return name.startswith(HOST_EVENT_PREFIXES)


def trace_files(root: str) -> List[str]:
    """The ``.trace.json[.gz]`` files of a capture: ``root`` may be the
    profile directory ``jax.profiler.trace`` wrote (searched recursively,
    the ``plugins/profile/<run>/<host>.trace.json.gz`` layout), or one
    trace file directly. Sorted for determinism; every matching file is
    parsed (multi-host captures write one per host)."""
    if os.path.isfile(root):
        return [root]
    out: List[str] = []
    for pat in ("*.trace.json.gz", "*.trace.json"):
        out.extend(glob.glob(os.path.join(root, "**", pat),
                             recursive=True))
    return sorted(out)


def load_trace(path: str) -> Dict[str, Any]:
    """One trace file -> its JSON document (gzip or plain)."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:  # type: ignore[operator]
        return json.loads(f.read().decode("utf-8"))


#: args keys that carry XLA op metadata in profiler events (TPU/GPU
#: traces spell the op_name under one of these)
_METADATA_KEYS = ("op_name", "long_name", "tf_op", "hlo_op", "hlo_module")


def _phase_from_args(name: str, args: Optional[Dict[str, Any]]
                     ) -> Tuple[str, bool]:
    """Tier-1 attribution: ``(phase, metadata_found)``. The phase is a
    detpu scope embedded in the event name or in any string-valued arg
    (TPU traces put the ``op_name`` / ``long_name`` metadata there;
    scanning every string key survives renames). ``metadata_found`` is
    True whenever the event carries op metadata at all — an op with
    metadata but no detpu scope is RESOLVED as genuinely-unscoped
    compute, which is different from an event nothing could identify."""
    p = obs.phase_path(name)
    if p:
        return p, True
    found = False
    if args:
        found = any(k in args for k in _METADATA_KEYS)
        for v in args.values():
            if isinstance(v, str) and "detpu/" in v:
                p = obs.phase_path(v)
                if p:
                    return p, True
    return "", found


def parse_events(doc: Dict[str, Any],
                 resolver: Optional[Callable[[str], Optional[str]]] = None,
                 ) -> List[TraceEvent]:
    """Extract attributable op events from one trace document.

    Every complete (``"X"``) event with a positive duration that is not
    host bookkeeping is kept; ``phase`` comes from the event's own
    metadata when present, else from ``resolver(instruction_name)``
    (compiled-HLO join). Events neither tier can attribute keep
    ``phase=""`` with ``resolved=False`` — they still count toward wall
    time if they look like op events, but a caller can drop them.
    """
    out: List[TraceEvent] = []
    for e in doc.get("traceEvents") or []:
        if e.get("ph") != "X":
            continue
        dur = e.get("dur")
        if not isinstance(dur, (int, float)) or dur <= 0:
            continue
        name = str(e.get("name", ""))
        if (is_host_event(name) or obs.is_request_event(name)
                or obs.is_span_event(name)):
            # request-tracing events (utils/reqtrace.py exports into the
            # same Chrome-trace container) are serving spans, not device
            # work — parse_request_traces reads them; obs.span host
            # spans are NAMED detpu/..., which the scope regex would
            # otherwise read as a device op under that phase
            continue
        args = e.get("args")
        phase, resolved = _phase_from_args(
            name, args if isinstance(args, dict) else None)
        if not phase and resolver is not None:
            key = name.lstrip("%")
            hit = resolver(key)
            if hit is None and isinstance(args, dict) \
                    and isinstance(args.get("hlo_op"), str):
                hit = resolver(args["hlo_op"])
            if hit is not None:
                phase, resolved = hit, True
        out.append(TraceEvent(
            name=name, ts=float(e.get("ts", 0.0)), dur=float(dur),
            pid=int(e.get("pid", 0)), tid=int(e.get("tid", 0)),
            phase=phase, resolved=resolved))
    return out


def parse_capture(root: str,
                  resolver: Optional[Callable[[str], Optional[str]]] = None,
                  ) -> List[TraceEvent]:
    """All attributable op events of one capture directory (every trace
    file merged — multi-host/multi-stream captures concatenate; interval
    math below handles the overlap)."""
    events: List[TraceEvent] = []
    for path in trace_files(root):
        events.extend(parse_events(load_trace(path), resolver=resolver))
    return events


# ------------------------------------------------------------ interval math


def merge_intervals(spans: Iterable[Tuple[float, float]]
                    ) -> List[Tuple[float, float]]:
    """Sorted union of (begin, end) spans."""
    out: List[Tuple[float, float]] = []
    for s, t in sorted(spans):
        if out and s <= out[-1][1]:
            if t > out[-1][1]:
                out[-1] = (out[-1][0], t)
        else:
            out.append((s, t))
    return out


def union_of(events: Iterable[TraceEvent]) -> List[Tuple[float, float]]:
    return merge_intervals((e.ts, e.end) for e in events)


def total(union: Sequence[Tuple[float, float]]) -> float:
    return sum(t - s for s, t in union)


def intersect_total(a: Sequence[Tuple[float, float]],
                    b: Sequence[Tuple[float, float]]) -> float:
    """Total length of the intersection of two merged interval unions."""
    i = j = 0
    tot = 0.0
    while i < len(a) and j < len(b):
        s = max(a[i][0], b[j][0])
        t = min(a[i][1], b[j][1])
        if t > s:
            tot += t - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


# --------------------------------------------------------- phase grouping


def is_collective_phase(phase: str) -> bool:
    """Whether a phase path names a cross-chip exchange."""
    return COLLECTIVE_PHASE_MARK in phase


def group_of(phase: str) -> str:
    """Fold a full phase path into the measured step-attribution group
    (``exchange`` / ``lookup`` / ``dense`` / ``apply`` / ``streaming`` /
    ``other``). Unscoped events land in ``other`` — with the compiled-HLO
    join they are rare (fusion/while internals resolve to their entry
    op's phase)."""
    if not phase:
        return "other"
    if is_collective_phase(phase):
        return "exchange"
    head = phase.split("/", 1)[0]
    if head.startswith("dense"):
        return "dense"
    if head.startswith("sparse_apply") or "dedup" in phase \
            or "expand_update_rows" in phase:
        return "apply"
    if "stream" in phase or "admission" in phase:
        return "streaming"
    if head.startswith("embedding_forward") or "lookup" in phase \
            or "gather" in phase or "decode" in phase \
            or "unique" in phase or "combine" in phase:
        return "lookup"
    return "other"


# ----------------------------------------------------------- measurement


def measure_events(events: Sequence[TraceEvent],
                   independent_spans: Optional[
                       Dict[str, List[Tuple[float, float]]]] = None,
                   overlap_min_frac: float = 0.5) -> Dict[str, Any]:
    """Reduce one capture's op events to the measured step summary.

    Returns a plain JSON-able dict:

    * ``wall_ms`` — length of the union of every op-event interval (the
      measured busy wall clock of the capture);
    * ``phase_ms`` / ``group_ms`` — summed event durations per detpu
      phase path and per :data:`GROUPS` entry (sums EXCEED ``wall_ms``
      whenever devices/streams genuinely run concurrently — that excess
      is the measured overlap);
    * ``concurrency`` — ``sum(phase_ms) / wall_ms``;
    * ``a2a_union_ms`` / ``a2a_frac`` — wall-clock during which at least
      one exchange event was in flight, and its fraction of ``wall_ms``;
    * ``collectives`` — per exchange phase: in-flight union, concurrent
      *hideable* compute (``hidden_ms``), ``hidden_frac``, and the
      measured classification: ``"overlapped"`` when ``hidden_frac >=
      overlap_min_frac``, else ``"serialized"``;
    * ``measured_serialized_fraction`` — exposed (non-hidden) exchange
      time over total exchange time, the measured analogue of the
      schedule auditor's modeled ``serialized_collective_fraction``.

    ``independent_spans`` maps each collective phase to the merged spans
    of compute that is DAG-INDEPENDENT of it (computed by
    :mod:`..analysis.phase_profile` from the schedule auditor's
    dependency cones). Without it, concurrent compute of ANY other
    non-exchange phase counts as hideable — an upper bound that
    over-credits lockstep-skew artifacts; the DAG-aware caller is the
    honest one.
    """
    phase_ms: Dict[str, float] = {}
    group_ms: Dict[str, float] = {g: 0.0 for g in GROUPS}
    for e in events:
        key = e.phase or "(unscoped)"
        phase_ms[key] = phase_ms.get(key, 0.0) + e.dur / 1e3
        group_ms[group_of(e.phase)] += e.dur / 1e3

    wall_union = union_of(events)
    wall_ms = total(wall_union) / 1e3

    coll_phases = sorted({e.phase for e in events
                          if is_collective_phase(e.phase)})
    compute_events = [e for e in events
                      if not is_collective_phase(e.phase)]
    collectives = []
    exposed_us = in_flight_us = 0.0
    for phase in coll_phases:
        cu = union_of([e for e in events if e.phase == phase])
        if independent_spans is not None:
            ind = independent_spans.get(phase, [])
        else:
            ind = union_of(compute_events)
        hidden_us = intersect_total(cu, ind)
        cu_us = total(cu)
        frac = hidden_us / cu_us if cu_us > 0 else 0.0
        in_flight_us += cu_us
        exposed_us += cu_us - hidden_us
        collectives.append({
            "phase": phase,
            "union_ms": round(cu_us / 1e3, 4),
            "hidden_ms": round(hidden_us / 1e3, 4),
            "hidden_frac": round(frac, 4),
            "classification": ("overlapped" if frac >= overlap_min_frac
                               else "serialized"),
        })
    a2a_union = union_of([e for e in events
                          if is_collective_phase(e.phase)])
    a2a_ms = total(a2a_union) / 1e3
    busy_ms = sum(phase_ms.values())
    return {
        "events": len(events),
        "events_resolved": sum(e.resolved for e in events),
        "wall_ms": round(wall_ms, 4),
        "busy_ms": round(busy_ms, 4),
        "concurrency": round(busy_ms / wall_ms, 4) if wall_ms > 0 else 0.0,
        "phase_ms": {k: round(v, 4) for k, v in sorted(phase_ms.items())},
        "group_ms": {k: round(v, 4) for k, v in group_ms.items()},
        "a2a_union_ms": round(a2a_ms, 4),
        "a2a_frac": round(a2a_ms / wall_ms, 4) if wall_ms > 0 else 0.0,
        "collectives": collectives,
        "measured_serialized_fraction": (
            round(exposed_us / in_flight_us, 4) if in_flight_us > 0
            else None),
        "overlap_min_frac": overlap_min_frac,
    }


# ------------------------------------------------- request-trace parsing


def parse_request_traces(path_or_doc: Any) -> List[Dict[str, Any]]:
    """The inverse of :func:`~.reqtrace.traces_to_chrome`: regroup the
    ``req/*`` events of a Chrome trace document (or a path to one,
    ``.gz`` fine) back into per-request trace summaries.

    Every returned dict has ``trace_id`` / ``outcome`` / ``rid`` /
    ``latency_ms`` / ``stages_ms`` / ``events`` / ``attrs`` — enough to
    re-check the span-partition invariant (``sum(stages_ms.values()) ==
    latency_ms``) and to find the restart-crossing trace without ever
    importing the writer. Coalesce (``req/flush``) spans are surfaced
    separately under ``"flushes"`` in each trace's ``attrs`` owner; they
    are returned as-is in no trace (they link several).
    """
    doc = (load_trace(path_or_doc) if isinstance(path_or_doc, str)
           else path_or_doc)
    traces: Dict[str, Dict[str, Any]] = {}
    for e in doc.get("traceEvents") or []:
        name = str(e.get("name", ""))
        if e.get("ph") != "X" or not obs.is_request_event(name):
            continue
        kind = name[len(obs.REQ_EVENT_PREFIX):]
        args = e.get("args") or {}
        tid = args.get("trace_id")
        if kind == "flush" or tid is None:
            continue
        rec = traces.setdefault(tid, {
            "trace_id": tid, "outcome": None, "rid": None,
            "latency_ms": None, "stages_ms": {}, "events": [],
            "attrs": {}})
        if kind.startswith("stage/"):
            rec["stages_ms"][kind[len("stage/"):]] = float(
                args.get("ms", float(e.get("dur", 0.0)) / 1e3))
        elif kind.startswith("mark/"):
            ev = {k: v for k, v in args.items() if k != "trace_id"}
            ev["name"] = kind[len("mark/"):]
            ev["dur_ms"] = float(e.get("dur", 0.0)) / 1e3
            rec["events"].append(ev)
        else:
            # the envelope event: kind IS the outcome
            rec["outcome"] = kind
            rec["rid"] = args.get("rid")
            rec["latency_ms"] = args.get(
                "latency_ms", float(e.get("dur", 0.0)) / 1e3)
            rec["attrs"] = {k: v for k, v in args.items()
                            if k not in ("trace_id", "rid", "outcome",
                                         "latency_ms")}
    # envelope-less fragments (partial exports) are dropped: without an
    # outcome there is nothing to gate on
    return [t for t in traces.values() if t["outcome"] is not None]
