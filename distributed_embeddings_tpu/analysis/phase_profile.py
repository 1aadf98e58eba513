"""Measured phase-time observatory: trace-parsed step attribution and
cost-model calibration against the schedule auditor.

Every phase number the repo had before this module was *modeled*:
:mod:`.schedule_audit` prices the compiled step's dependency DAG from
:data:`~.plan_audit.CHIP_SPECS` byte arithmetic. Nothing measured where
a step's milliseconds actually go — ``DETPU_PROFILE_DIR`` dumped raw
TensorBoard traces no tool ever read. This module closes the loop, with the same profile-then-optimize
discipline the reference library applies to its fused lookup kernels:

* :func:`profile_steps` runs N timed steps, each under its own
  ``jax.profiler.trace`` capture, parses every capture with the jax-free
  :mod:`~..utils.traceparse`, and reduces them to a
  :class:`PhaseProfile`: per-phase measured duration (p50/p95 over
  steps), the measured step breakdown (exchange vs lookup vs apply vs
  dense), the measured all-to-all fraction, measured overlap
  (wall-clock union vs summed phase durations), and a measured
  serialized-vs-overlapped verdict per exchange phase;
* :class:`HloPhaseIndex` joins bare-name trace events (this container's
  CPU backend carries no op metadata in its events) against the compiled
  module's OWN text — instruction name -> ``obs.scope`` phase via
  ``metadata.op_name``, the exact machinery the HLO census and schedule
  auditor share — and supplies each collective's DAG-**independent**
  compute spans, so "measured overlap" only credits compute a scheduler
  could genuinely have hidden the exchange under (concurrent-but-
  dependent work from lockstep skew across virtual devices does not
  count);
* :func:`calibrate` joins the measured per-phase durations against
  :class:`~.schedule_audit.ScheduleReport`'s modeled per-phase costs
  into a drift table — measured/modeled ratio per phase, normalized by
  the cost-weighted median ratio so a *uniform* backend-speed difference
  (CPU proxy vs the modeled v5e) cancels and what remains is relative
  mispricing — flagging phases beyond ``DETPU_PHASE_DRIFT_MAX`` (2x);
* :func:`check_agreement` is the classification cross-check the
  ``make phase-profile`` gate enforces: a collective the model calls
  **serialized** must measure serialized (if it measured overlapped, the
  model is lying about the dependency structure); a modeled
  **overlappable** collective may measure either way — structural
  possibility is not realized overlap until the pipelined step ships
  (ROADMAP item 2).

Profiling is strictly opt-in: nothing here touches how steps are built —
an unprofiled step is bitwise the program it always was.

Module-scope imports stay jax-free (the dataclasses and the calibration
math must be importable by report tooling without a backend); everything
that lowers or traces imports jax lazily.
"""

from __future__ import annotations

import dataclasses
import os
import shutil
import tempfile
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from ..utils import envvars, traceparse
from ..utils.obs import phase_leaf

PROFILE_STEPS_ENV = "DETPU_PHASE_PROFILE_STEPS"
PROFILE_DIR_OVERRIDE_ENV = "DETPU_PHASE_PROFILE_DIR"
DRIFT_MAX_ENV = "DETPU_PHASE_DRIFT_MAX"

#: phases below this share of the step (both measured AND modeled) are
#: reported but never drift-flagged — ratio noise on a 0.1% phase is not
#: a mispricing signal
CALIBRATION_MIN_SHARE = 0.005


class PhaseProfileError(RuntimeError):
    """An unusable capture (no events parsed, no trace files) or a
    strict-mode agreement failure."""


# ---------------------------------------------------------- HLO phase join


class HloPhaseIndex:
    """Instruction-name -> phase resolver + DAG-independence spans, built
    from the compiled module's own text.

    The bare-name join: every trace event named like an HLO instruction
    (``all-to-all.6``, ``cosine_add_fusion.clone``, the ``copy``/``add``
    internals of a while-lowered scatter) resolves to the phase of its
    instruction — the instruction's own ``metadata.op_name`` scope when
    present, else the resolved phase of the ENTRY instruction that
    (transitively) calls its computation, so fusion and loop-body
    internals inherit their parent op's phase instead of polluting
    "(unscoped)".
    """

    def __init__(self, hlo_text: str, *, world: int = 1,
                 chip: str = "v5e"):
        from .plan_audit import CHIP_SPECS
        from .schedule_audit import ScheduleGraph, parse_hlo_module

        comps = parse_hlo_module(hlo_text)
        self.graph = ScheduleGraph(comps, world=world,
                                   chip=CHIP_SPECS[chip])
        self._phase: Dict[str, str] = {}
        self._entry: Dict[str, int] = {}
        # transitive computation ownership: comp name -> entry node
        # indices whose instruction (chain) calls it
        owners: Dict[str, set] = {}
        for node in self.graph.nodes:
            stack = list(node.instr.called)
            seen: set = set()
            while stack:
                cname = stack.pop()
                if cname in seen:
                    continue
                seen.add(cname)
                owners.setdefault(cname, set()).add(node.index)
                comp = comps.get(cname)
                if comp is None:
                    continue
                for inner in comp.instructions:
                    stack.extend(inner.called)
        for node in self.graph.nodes:
            self._phase[node.instr.name] = node.phase
            self._entry[node.instr.name] = node.index
        for cname, comp in comps.items():
            if comp.is_entry:
                continue
            own = owners.get(cname, set())
            entry = next(iter(own)) if len(own) == 1 else None
            for inner in comp.instructions:
                phase = inner.phase
                if not phase and entry is not None:
                    phase = self.graph.nodes[entry].phase
                # entry instruction names win on (rare) collisions
                self._phase.setdefault(inner.name, phase)
                if entry is not None:
                    self._entry.setdefault(inner.name, entry)

    def resolve(self, name: str) -> Optional[str]:
        """Phase of one event/instruction name; ``None`` when the name is
        not an instruction of this module (the event stays unattributed —
        it still counts toward wall time)."""
        hit = self._phase.get(name)
        if hit is None and name.endswith(".clone"):
            hit = self._phase.get(name[: -len(".clone")])
        return hit

    def entry_of(self, name: str) -> Optional[int]:
        hit = self._entry.get(name)
        if hit is None and name.endswith(".clone"):
            hit = self._entry.get(name[: -len(".clone")])
        return hit

    def independent_spans(self, events: Sequence[traceparse.TraceEvent]
                          ) -> Dict[str, List[Tuple[float, float]]]:
        """Per collective phase: merged wall-clock spans of the events of
        entry nodes that are DAG-independent of EVERY collective in that
        phase (outside all their ancestor/descendant cones, non-trivial,
        non-collective) — the compute a latency-hiding schedule could
        genuinely have run under the exchange. Feeding these to
        :func:`~..utils.traceparse.measure_events` makes the measured
        serialized/overlapped verdict dependency-aware instead of
        crediting lockstep skew."""
        g = self.graph
        by_phase_nodes: Dict[str, List[int]] = {}
        for n in g.nodes:
            if n.is_collective and n.phase:
                by_phase_nodes.setdefault(n.phase, []).append(n.index)
        by_entry_events: Dict[int, List[traceparse.TraceEvent]] = {}
        for e in events:
            idx = self.entry_of(e.name.lstrip("%"))
            if idx is not None:
                by_entry_events.setdefault(idx, []).append(e)
        out: Dict[str, List[Tuple[float, float]]] = {}
        for phase, colls in by_phase_nodes.items():
            excluded: set = set()
            for c in colls:
                excluded |= g.ancestors(c) | g.descendants(c) | {c}
            spans: List[Tuple[float, float]] = []
            for n in g.nodes:
                if (n.index in excluded or n.is_collective
                        or n.is_trivial):
                    continue
                for e in by_entry_events.get(n.index, ()):
                    spans.append((e.ts, e.end))
            out[phase] = traceparse.merge_intervals(spans)
        return out


# -------------------------------------------------------------- the report


def _pct(xs: Sequence[float], q: float) -> float:
    """Percentile without numpy (nearest-rank on the sorted sample —
    exact enough for 3-20 step samples and keeps this module jax/numpy
    free)."""
    if not xs:
        return 0.0
    s = sorted(xs)
    k = min(len(s) - 1, max(0, round(q / 100.0 * (len(s) - 1))))
    return float(s[k])


@dataclasses.dataclass
class PhaseProfile:
    """Measured per-phase timing of N profiled steps (the measured
    counterpart of :class:`~.schedule_audit.ScheduleReport`)."""
    label: str
    steps: int
    world: int
    backend: Optional[str]
    #: per detpu phase path: {"p50"/"p95"/"mean" ms summed over events}
    phase_ms: Dict[str, Dict[str, float]]
    #: per step-attribution group (exchange/lookup/dense/apply/...): p50 ms
    group_ms: Dict[str, float]
    step_wall_ms: Dict[str, float]          # p50/p95 busy wall clock
    concurrency: float                      # p50 busy/wall
    a2a_frac: float                         # p50 exchange-in-flight frac
    measured_serialized_fraction: Optional[float]   # p50 over steps
    #: per exchange phase: majority classification + p50 hidden_frac
    collectives: List[Dict[str, Any]]
    events_per_step: float
    resolved_frac: float                    # event-attribution coverage
    per_step: List[Dict[str, Any]]          # raw per-step measurements
    #: p50 wall seconds of one step under capture (the profiler's cost on
    #: the step itself; parsing is off the training path and priced
    #: separately in parse_s)
    capture_s: Optional[float] = None
    parse_s: Optional[float] = None

    @classmethod
    def from_steps(cls, measures: List[Dict[str, Any]], *, label: str,
                   world: int, backend: Optional[str]) -> "PhaseProfile":
        if not measures:
            raise PhaseProfileError(
                f"phase profile {label!r}: no step captures to reduce")
        phases = sorted({p for m in measures for p in m["phase_ms"]})
        phase_ms = {}
        for p in phases:
            xs = [m["phase_ms"].get(p, 0.0) for m in measures]
            phase_ms[p] = {"p50": round(_pct(xs, 50), 4),
                           "p95": round(_pct(xs, 95), 4),
                           "mean": round(sum(xs) / len(xs), 4)}
        group_ms = {g: round(_pct([m["group_ms"].get(g, 0.0)
                                   for m in measures], 50), 4)
                    for g in traceparse.GROUPS}
        walls = [m["wall_ms"] for m in measures]
        fracs = [m["measured_serialized_fraction"] for m in measures
                 if m["measured_serialized_fraction"] is not None]
        coll_phases = sorted({c["phase"] for m in measures
                              for c in m["collectives"]})
        collectives = []
        for p in coll_phases:
            rows = [c for m in measures for c in m["collectives"]
                    if c["phase"] == p]
            n_ser = sum(r["classification"] == "serialized" for r in rows)
            collectives.append({
                "phase": p,
                "union_ms": round(_pct([r["union_ms"] for r in rows], 50),
                                  4),
                "hidden_frac": round(_pct([r["hidden_frac"]
                                           for r in rows], 50), 4),
                "classification": ("serialized" if 2 * n_ser >= len(rows)
                                   else "overlapped"),
                "samples": len(rows),
            })
        n_ev = [m["events"] for m in measures]
        n_res = [m["events_resolved"] for m in measures]
        caps = [m["capture_s"] for m in measures if "capture_s" in m]
        parses = [m["parse_s"] for m in measures if "parse_s" in m]
        return cls(
            capture_s=round(_pct(caps, 50), 4) if caps else None,
            parse_s=round(_pct(parses, 50), 4) if parses else None,
            label=label, steps=len(measures), world=world, backend=backend,
            phase_ms=phase_ms, group_ms=group_ms,
            step_wall_ms={"p50": round(_pct(walls, 50), 4),
                          "p95": round(_pct(walls, 95), 4)},
            concurrency=round(_pct([m["concurrency"] for m in measures],
                                   50), 4),
            a2a_frac=round(_pct([m["a2a_frac"] for m in measures], 50), 4),
            measured_serialized_fraction=(
                round(_pct(fracs, 50), 4) if fracs else None),
            collectives=collectives,
            events_per_step=round(sum(n_ev) / len(n_ev), 1),
            resolved_frac=round(sum(n_res) / max(sum(n_ev), 1), 4),
            per_step=measures)

    def to_json(self, include_steps: bool = False) -> Dict[str, Any]:
        d = dataclasses.asdict(self)
        if not include_steps:
            d.pop("per_step")
        return d

    def summary(self) -> Dict[str, Any]:
        """The compact record ``tools/phase_profile.py`` writes."""
        return {
            "label": self.label,
            "world": self.world,
            "backend": self.backend,
            "steps": self.steps,
            "step_wall_ms_p50": self.step_wall_ms["p50"],
            "group_ms": dict(self.group_ms),
            "a2a_frac": self.a2a_frac,
            "concurrency": self.concurrency,
            "measured_serialized_fraction":
                self.measured_serialized_fraction,
            "collectives": [
                {"phase": c["phase"],
                 "classification": c["classification"],
                 "hidden_frac": c["hidden_frac"]}
                for c in self.collectives],
            "resolved_frac": self.resolved_frac,
        }

    def markdown(self) -> str:
        lines = [
            f"measured phase profile `{self.label}` — {self.steps} steps, "
            f"world {self.world}, backend {self.backend or '?'}:",
            "",
            "| phase | p50 ms | p95 ms |",
            "|---|---|---|",
        ]
        order = sorted(self.phase_ms,
                       key=lambda p: -self.phase_ms[p]["p50"])
        for p in order:
            row = self.phase_ms[p]
            lines.append(f"| `{p}` | {row['p50']:.3f} | {row['p95']:.3f} |")
        lines.append("")
        lines.append(
            "breakdown (p50 ms): " + ", ".join(
                f"{g}={self.group_ms.get(g, 0.0):.3f}"
                for g in traceparse.GROUPS))
        lines.append(
            f"step wall p50 {self.step_wall_ms['p50']:.3f} ms | "
            f"concurrency x{self.concurrency:.2f} | a2a in flight "
            f"{self.a2a_frac * 100:.1f}% | measured serialized fraction "
            + (f"{self.measured_serialized_fraction:.3f}"
               if self.measured_serialized_fraction is not None else "n/a"))
        for c in self.collectives:
            lines.append(
                f"  - `{c['phase']}`: **{c['classification']}** "
                f"(hidden {c['hidden_frac'] * 100:.1f}% of "
                f"{c['union_ms']:.3f} ms in flight)")
        return "\n".join(lines)


# ------------------------------------------------------------- the harness


def default_profile_steps() -> int:
    return max(1, envvars.get_int(PROFILE_STEPS_ENV))


def profile_steps(run_step: Callable[[], Any], *,
                  steps: Optional[int] = None,
                  profile_dir: Optional[str] = None,
                  index: Optional[HloPhaseIndex] = None,
                  world: int = 1,
                  label: str = "step",
                  overlap_min_frac: float = 0.5) -> PhaseProfile:
    """Capture and reduce N profiled steps.

    ``run_step`` runs exactly one already-compiled step AND blocks on its
    result (the caller owns state threading and the readback — the same
    contract as any timed loop). Each step gets its OWN
    ``jax.profiler.trace`` capture so the per-phase numbers carry real
    p50/p95 spread instead of one blurred total. Captures land under
    ``profile_dir`` (default ``DETPU_PHASE_PROFILE_DIR``, else a temp
    directory deleted after parsing — set the env var to keep
    TensorBoard-loadable traces).

    Profiling is opt-in by construction: this wraps EXECUTION only; the
    step program is whatever the caller compiled, bitwise.
    """
    import jax

    steps = default_profile_steps() if steps is None else max(1, steps)
    base = profile_dir or envvars.get(PROFILE_DIR_OVERRIDE_ENV)
    cleanup = base is None
    if base is None:
        base = tempfile.mkdtemp(prefix="detpu_phase_profile_")
    resolver = index.resolve if index is not None else None
    try:
        # throwaway warm-up capture: the process's FIRST profiler
        # session pays a multi-second one-time init that would skew the
        # first step's p95 by two orders of magnitude
        warm = os.path.join(base, label.replace("/", "_"), "_warmup")
        os.makedirs(warm, exist_ok=True)
        with jax.profiler.trace(warm):
            run_step()
        shutil.rmtree(warm, ignore_errors=True)
        measures = []
        for k in range(steps):
            d = os.path.join(base, label.replace("/", "_"),
                             f"step{k:03d}")
            os.makedirs(d, exist_ok=True)
            t0 = time.perf_counter()
            with jax.profiler.trace(d):
                run_step()
            t_cap = time.perf_counter() - t0
            events = traceparse.parse_capture(d, resolver=resolver)
            if not events:
                raise PhaseProfileError(
                    f"phase profile {label!r}: step {k} capture at {d} "
                    "parsed 0 op events — unrecognized trace format or "
                    "an empty capture; the measured gate cannot run on it")
            ind = (index.independent_spans(events)
                   if index is not None else None)
            m = traceparse.measure_events(
                events, independent_spans=ind,
                overlap_min_frac=overlap_min_frac)
            m["capture_s"] = round(t_cap, 4)
            m["parse_s"] = round(time.perf_counter() - t0 - t_cap, 4)
            measures.append(m)
        try:
            backend = jax.default_backend()
        except Exception:  # noqa: BLE001 - stamp is best-effort
            backend = None
        return PhaseProfile.from_steps(measures, label=label, world=world,
                                       backend=backend)
    finally:
        if cleanup:
            shutil.rmtree(base, ignore_errors=True)


# ---------------------------------------------------------- calibration


@dataclasses.dataclass
class CalibrationRow:
    phase: str
    measured_ms: float
    modeled_ms: float            # schedule auditor cost, ns -> ms
    ratio: Optional[float]       # measured / modeled
    normalized: Optional[float]  # ratio / cost-weighted median ratio
    share_measured: float
    share_modeled: float
    flagged: bool


@dataclasses.dataclass
class CalibrationReport:
    """The measured-vs-modeled drift table: where the byte-cost model
    of the schedule auditor drifts from the clock."""
    label: str
    rows: List[CalibrationRow]
    scale: float                 # the cancelled backend-speed factor
    drift_max: float
    flagged: List[str]

    @property
    def ok(self) -> bool:
        return not self.flagged

    def to_json(self) -> Dict[str, Any]:
        return {
            "label": self.label,
            "scale_measured_over_modeled": round(self.scale, 4),
            "drift_max": self.drift_max,
            "flagged": list(self.flagged),
            "rows": [dataclasses.asdict(r) for r in self.rows],
        }

    def markdown(self) -> str:
        lines = [
            f"calibration `{self.label}` — backend-speed scale "
            f"x{self.scale:.2f} cancelled; flag at >{self.drift_max:g}x "
            "relative drift:",
            "",
            "| phase | measured ms | modeled ms | ratio | vs median | |",
            "|---|---|---|---|---|---|",
        ]
        for r in self.rows:
            lines.append(
                f"| `{r.phase}` | {r.measured_ms:.3f} | "
                f"{r.modeled_ms:.4f} | "
                + (f"{r.ratio:.1f}x" if r.ratio is not None else "—")
                + " | "
                + (f"{r.normalized:.2f}x" if r.normalized is not None
                   else "—")
                + (" | **DRIFT** |" if r.flagged else " | |"))
        if self.flagged:
            lines.append("")
            lines.extend(f"- DRIFT: {f}" for f in self.flagged)
        return "\n".join(lines)


def calibrate(profile: PhaseProfile, schedule_report,
              drift_max: Optional[float] = None,
              label: Optional[str] = None) -> CalibrationReport:
    """Join measured per-phase p50 durations against the schedule
    auditor's modeled per-phase costs (``ScheduleReport.phase_cost_ns``).

    Measured and modeled run on different clocks (a CPU-proxy capture vs
    the v5e byte model), so the RAW ratio is dominated by backend speed.
    The drift table therefore normalizes every phase's ratio by the
    cost-weighted median ratio: a phase whose normalized ratio exceeds
    ``drift_max`` (``DETPU_PHASE_DRIFT_MAX``, default 2x) costs that much
    more — or less, below ``1/drift_max`` — than the model believes
    *relative to the other phases*, which is exactly the mispricing that
    would mislead a CHIP_SPECS-gated decision. Phases below
    :data:`CALIBRATION_MIN_SHARE` of the step on both sides are reported
    but never flagged."""
    if drift_max is None:
        drift_max = envvars.get_float(DRIFT_MAX_ENV)
        if drift_max <= 0:
            drift_max = 2.0
    modeled = {p: ns / 1e6 for p, ns in
               getattr(schedule_report, "phase_cost_ns", {}).items() if ns}
    measured = {p: v["p50"] for p, v in profile.phase_ms.items()}
    tot_meas = sum(measured.values()) or 1.0
    tot_mod = sum(modeled.values()) or 1.0
    phases = sorted(set(measured) | set(modeled),
                    key=lambda p: -(measured.get(p, 0.0)))
    # cost-weighted median of measured/modeled over phases both sides see
    pairs = [(measured[p] / modeled[p], modeled[p])
             for p in phases
             if p in measured and p in modeled and modeled[p] > 0
             and measured[p] > 0]
    scale = 1.0
    if pairs:
        pairs.sort()
        half = sum(w for _, w in pairs) / 2.0
        acc = 0.0
        for ratio, w in pairs:
            acc += w
            if acc >= half:
                scale = ratio
                break
    rows: List[CalibrationRow] = []
    flagged: List[str] = []
    for p in phases:
        if p in ("(unscoped)", ""):
            continue
        meas = measured.get(p, 0.0)
        mod = modeled.get(p, 0.0)
        ratio = meas / mod if mod > 0 and meas > 0 else None
        norm = ratio / scale if ratio is not None and scale > 0 else None
        sm, so = meas / tot_meas, mod / tot_mod
        flag = bool(
            norm is not None
            and (norm > drift_max or norm < 1.0 / drift_max)
            and max(sm, so) >= CALIBRATION_MIN_SHARE)
        rows.append(CalibrationRow(
            phase=p, measured_ms=round(meas, 4), modeled_ms=round(mod, 4),
            ratio=None if ratio is None else round(ratio, 3),
            normalized=None if norm is None else round(norm, 3),
            share_measured=round(sm, 4), share_modeled=round(so, 4),
            flagged=flag))
        if flag:
            flagged.append(
                f"phase '{p}': measured/modeled {ratio:.1f}x is "
                f"{norm:.2f}x the step's median {scale:.1f}x — the byte "
                f"model misprices this phase beyond {drift_max:g}x "
                f"({meas:.3f} ms measured vs {mod:.4f} ms modeled)")
    return CalibrationReport(
        label=label or profile.label, rows=rows, scale=scale,
        drift_max=drift_max, flagged=flagged)


# ------------------------------------------------------------- agreement


def check_agreement(profile: PhaseProfile, schedule_report) -> List[str]:
    """Measured-vs-modeled classification cross-check (the acceptance
    contract of ``make phase-profile``):

    * every collective phase the schedule auditor classifies
      **serialized** must exist in the measured profile AND measure
      serialized — a measured overlap on a modeled-serialized exchange
      means the model's dependency cones are wrong;
    * a modeled **overlappable** collective may measure either way (the
      unpipelined step is free to serialize what is merely possible);
    * a measured exchange phase the model never saw is a join failure
      worth failing on (the two views drifted onto different programs).

    Only EXCHANGE phases (``*all_to_all*`` — the step schedule's
    collective phases) are compared: the psum all-reduces (loss pmean,
    nan-guard verdict) are collectives to the DAG model but are not part
    of the overlap contract, and the measured side deliberately counts
    only exchanges.

    Returns violation strings; empty = agreement.
    """
    out: List[str] = []
    modeled: Dict[str, List[str]] = {}
    for c in schedule_report.collectives:
        if not traceparse.is_collective_phase(c.phase):
            continue
        modeled.setdefault(c.phase, []).append(c.classification)
    measured = {c["phase"]: c["classification"]
                for c in profile.collectives}
    for phase, cls_list in sorted(modeled.items()):
        got = measured.get(phase)
        if got is None:
            out.append(
                f"agreement: modeled collective phase '{phase}' never "
                "appeared in the measured capture — trace too coarse, "
                "phase renamed, or the profiled program is not the "
                "audited one")
            continue
        if "serialized" in cls_list and got != "serialized":
            out.append(
                f"agreement: phase '{phase}' is modeled SERIALIZED but "
                f"measured {got.upper()} — the cost model's dependency "
                "cones disagree with the clock")
    for phase in sorted(measured):
        if phase not in modeled:
            out.append(
                f"agreement: measured exchange phase '{phase}' is not a "
                "collective of the modeled schedule graph — the measured "
                "and modeled views audit different programs")
    return out
