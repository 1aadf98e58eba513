"""Schedule-graph auditor: static critical-path / overlap analysis of the
compiled step.

The jaxpr auditor (PR 4) checks which collectives we *ask for* and the
HLO census (PR 7) counts what XLA *emits* — but neither sees the
**dependency structure** between the emitted ops, which is exactly what
decides whether a pipelined step can hide communication under compute.
This module closes that gap: it extends the census's HLO text parsing to
capture **operands**, builds the full dependency DAG of the optimized
entry computation, attributes every node to its ``obs.scope`` phase,
prices every node under a bytes-based cost model (chip numbers from
:data:`~.plan_audit.CHIP_SPECS`; collective payloads priced off-chip
with the same ``(world-1)/world`` convention as the on-device
``*_a2a_bytes`` step metrics), computes the **critical path**, and
classifies each collective as

* **serialized-on** dense compute — no independent compute chain of
  sufficient modeled cost exists outside the collective's ancestor /
  descendant cones (nothing the scheduler could hide it under), or
* **overlappable-with** dense compute — such a chain exists, so a
  latency-hiding schedule is structurally possible.

On top of the graph sit two contract layers:

* declarative :class:`ScheduleContract`\\ s ("the ``id_all_to_all``
  phase holds >= 1 collective, serialized, on the critical path" — the
  documented baseline of today's unpipelined step), enforced by
  ``tools/schedule_audit.py --strict`` (= ``make schedule-audit``,
  inside ``make verify``);
* the :class:`~..parallel.schedule.StepSchedule` **declaration check**
  (:meth:`ScheduleReport.check_against_schedule`): every overlap a
  schedule *claims* must exist in the compiled program's DAG — a
  schedule that says "the exchange hides under dense compute" while XLA
  serialized them fails loudly. In the GSPMD framing (SNIPPETS.md [2],
  "8-chip → 6000-chip without changing application code") this is the
  scaling story: an overlap contract checked at trace time holds at any
  mesh size, because the DAG shape — unlike the wall clock — does not
  depend on how many chips run the program.

Like the census, everything here is ``lower().compile()`` + text
parsing: nothing executes on any backend.
"""

from __future__ import annotations

import dataclasses
import fnmatch
import json
import re
from typing import Any, Dict, List, Optional, Sequence, Set, Tuple

import jax

from .hlo_census import _DETPU_RE, _OPNAME_RE, _SHAPE_TOKEN_RE, _token_bytes
from .plan_audit import CHIP_SPECS, ChipSpec

#: HLO opcodes that move bytes across chips (priced over ICI, not HBM)
COLLECTIVE_OPS = frozenset((
    "all-to-all", "all-reduce", "all-gather", "reduce-scatter",
    "collective-permute", "collective-broadcast",
))

#: opcodes that are bookkeeping, not work — priced at ZERO cost and
#: excluded from the "independent compute that could hide a collective"
#: sum. A parameter is already resident in HBM, a get-tuple-element is a
#: pointer, and a broadcast is a splat the TPU backend fuses into its
#: consumer — counting any of them as hideable work would overstate both
#: the critical path and the overlap capacity (the CPU lowering used for
#: the static audit materializes some of them, but the model prices the
#: program, not the audit backend).
TRIVIAL_OPS = frozenset((
    "parameter", "constant", "iota", "get-tuple-element", "tuple",
    "bitcast", "broadcast", "copy", "after-all", "partition-id",
    "replica-id", "rng-get-and-update-state", "opt-barrier",
))

# computation header: `ENTRY %main.1_spmd (params...) -> type {` or
# `%fused_computation.1 (...) -> type {` (name with or without `%`)
_COMP_RE = re.compile(r"^(?P<entry>ENTRY\s+)?%?(?P<name>[\w.\-]+)\s*\(")
# instruction with captured name (the census regex, plus the name group)
_INST_NAME_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?(?P<name>[\w.\-]+)\s*=\s*"
    r"(?P<shape>\((?:[^()]|\([^()]*\))*\)|\S+)\s+"
    r"(?P<op>[a-z][\w\-]*)\(")
_CALLED_RE = re.compile(
    r"(?:calls|to_apply|body|condition)=%?([\w.\-]+)")
_BRANCHES_RE = re.compile(r"branch_computations=\{([^}]*)\}")
_NAME_TOKEN_RE = re.compile(r"%([\w.\-]+)")


class ScheduleGraphError(RuntimeError):
    """A malformed compiled module (unparseable text, dependency cycle,
    no roots) or a strict-mode contract failure
    (:meth:`ScheduleReport.raise_on_violations`)."""


# --------------------------------------------------------------- HLO parsing


@dataclasses.dataclass
class HloInstr:
    """One parsed HLO instruction (one DAG node candidate)."""
    name: str
    op: str
    shape: str                    # raw result-shape text
    operands: Tuple[str, ...]     # operand instruction names (same comp)
    called: Tuple[str, ...]       # called computation names
    op_name: str                  # metadata op_name (may be "")
    is_root: bool
    line: str                     # full raw line (byte accounting)

    @property
    def phase(self) -> str:
        """Full ``detpu/`` scope path, e.g.
        ``embedding_forward/id_all_to_all`` (may be ``""``)."""
        return "/".join(_DETPU_RE.findall(self.op_name))

    @property
    def phase_leaf(self) -> str:
        p = self.phase
        return p.rsplit("/", 1)[-1] if p else ""


@dataclasses.dataclass
class HloComputation:
    name: str
    is_entry: bool
    instructions: List[HloInstr]

    def by_name(self) -> Dict[str, HloInstr]:
        return {i.name: i for i in self.instructions}


def _split_operands(segment: str) -> List[str]:
    """Split an operand segment on top-level commas, respecting nested
    ``()``/``[]``/``{}`` (tuple-shaped operands, TPU tile suffixes like
    ``{1,0:T(8,128)}``, constant literals)."""
    out, depth, cur = [], 0, []
    for ch in segment:
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        if ch == "," and depth == 0:
            out.append("".join(cur))
            cur = []
        else:
            cur.append(ch)
    if cur:
        out.append("".join(cur))
    return out


def _operand_segment(line: str, start: int) -> Tuple[str, int]:
    """The text inside the operand parens opening at ``line[start] ==
    '('``; returns ``(segment, index_after_close)``."""
    depth = 0
    for i in range(start, len(line)):
        c = line[i]
        if c == "(":
            depth += 1
        elif c == ")":
            depth -= 1
            if depth == 0:
                return line[start + 1:i], i + 1
    return line[start + 1:], len(line)


def _operand_names(segment: str) -> Tuple[str, ...]:
    """Operand instruction names from a split chunk list: the LAST
    ``%name`` token of each chunk (typed form ``f32[2]{0} %x``), or the
    bare trailing identifier (untyped handwritten modules). Chunks
    holding no plausible name (constant literals, index comments) yield
    nothing — unknown names simply create no edge."""
    names = []
    for chunk in _split_operands(segment):
        toks = _NAME_TOKEN_RE.findall(chunk)
        if toks:
            names.append(toks[-1])
            continue
        tail = chunk.strip().split()
        if tail and re.fullmatch(r"[A-Za-z_][\w.\-]*", tail[-1]):
            names.append(tail[-1])
    return tuple(names)


def parse_hlo_module(txt: str) -> Dict[str, HloComputation]:
    """Parse optimized HLO module text into named computations with
    per-instruction operand lists. Pure text -> dataclasses."""
    comps: Dict[str, HloComputation] = {}
    cur: Optional[HloComputation] = None
    for line in txt.splitlines():
        stripped = line.strip()
        if cur is None:
            if not stripped or stripped.startswith(("HloModule",
                                                    "//", "#")):
                continue
            if stripped.endswith("{") and "=" not in stripped.split(
                    "(", 1)[0]:
                m = _COMP_RE.match(stripped)
                if m:
                    cur = HloComputation(
                        name=m.group("name"),
                        is_entry=bool(m.group("entry")), instructions=[])
                    comps[cur.name] = cur
            continue
        if stripped == "}":
            cur = None
            continue
        m = _INST_NAME_RE.match(line)
        if m is None:
            continue
        seg, after = _operand_segment(line, m.end() - 1)
        tail = line[after:]
        called = list(_CALLED_RE.findall(tail))
        bm = _BRANCHES_RE.search(tail)
        if bm:
            called += _NAME_TOKEN_RE.findall(bm.group(1))
        nm = _OPNAME_RE.search(line)
        cur.instructions.append(HloInstr(
            name=m.group("name"), op=m.group("op"),
            shape=m.group("shape"),
            operands=_operand_names(seg),
            called=tuple(called),
            op_name=nm.group(1) if nm else "",
            is_root=stripped.startswith("ROOT "),
            line=line))
    return comps


def entry_computation(comps: Dict[str, HloComputation]) -> HloComputation:
    for c in comps.values():
        if c.is_entry:
            return c
    raise ScheduleGraphError(
        f"no ENTRY computation among {sorted(comps)[:8]}... — "
        "unrecognized HLO text")


# ------------------------------------------------------------ the graph


@dataclasses.dataclass
class GraphNode:
    """One entry-computation instruction with its modeled cost."""
    instr: HloInstr
    index: int
    phase: str
    phase_leaf: str
    is_collective: bool
    is_trivial: bool
    result_bytes: int
    operand_bytes: int
    payload_bytes: int        # off-chip bytes for collectives, else 0
    cost_ns: float


def _shape_bytes(text: str) -> int:
    return sum(_token_bytes(dt, dims)
               for dt, dims in _SHAPE_TOKEN_RE.findall(text))


def _called_all_trivial(instr: HloInstr,
                        comps: Dict[str, HloComputation]) -> bool:
    """Whether a ``call``/``fusion`` wraps ONLY trivial work — the CPU
    backend outlines even zero-splat broadcasts into
    ``call(..., to_apply=%parallel_broadcast...)`` computations, which
    must not masquerade as hideable compute."""
    if not instr.called:
        return False
    saw_any = False
    for cname in instr.called:
        comp = comps.get(cname)
        if comp is None:
            return False
        for inner in comp.instructions:
            saw_any = True
            if inner.op not in TRIVIAL_OPS:
                return False
    return saw_any


def _resolve_phase(instr: HloInstr,
                   comps: Dict[str, HloComputation]) -> str:
    """A node's ``detpu`` phase path: its own ``op_name`` scope, else the
    majority scope of the computations it calls (fusions usually stamp
    the root op's scope on the fusion instruction itself; ``while`` loops
    from the scatter expander sometimes only scope the body)."""
    p = instr.phase
    if p:
        return p
    votes: Dict[str, int] = {}
    for cname in instr.called:
        comp = comps.get(cname)
        if comp is None:
            continue
        for inner in comp.instructions:
            ip = inner.phase
            if ip:
                votes[ip] = votes.get(ip, 0) + 1
    if not votes:
        return ""
    return max(sorted(votes), key=lambda k: votes[k])


class ScheduleGraph:
    """Dependency DAG of the optimized entry computation, with modeled
    per-node costs.

    Cost model (``ns ~= bytes / GBps`` — X GB/s moves ~X bytes per ns):

    * compute node: ``(result + operand bytes) / hbm_gbps`` — row ops and
      fusions on this class of model are HBM-bound (docs/perf_tpu.md);
    * collective node: ``payload / ici_eff_gbps`` where ``payload`` is
      the operand bytes times ``(world-1)/world`` — bytes actually
      leaving the chip, the SAME convention as the ``*_a2a_bytes`` step
      metrics and ``plan_audit``'s a2a pricing (an 8-way tiled
      all-to-all keeps 1/8 of its block local).
    """

    def __init__(self, comps: Dict[str, HloComputation], *,
                 world: int = 1, chip: ChipSpec = CHIP_SPECS["v5e"]):
        self.world = max(int(world), 1)
        self.chip = chip
        self.comps = comps
        entry = entry_computation(comps)
        self.entry = entry
        names = entry.by_name()
        off_frac = (self.world - 1) / self.world if self.world > 1 else 0.0
        self.nodes: List[GraphNode] = []
        index = {}
        for i, instr in enumerate(entry.instructions):
            res_b = _shape_bytes(instr.shape)
            # operand bytes: this XLA prints operands as bare names, so
            # they resolve through the defining instruction's result
            # shape; typed inline spellings (older text, handwritten
            # modules with undefined operands) are the fallback
            defs = [names[o] for o in instr.operands if o in names]
            op_b = (sum(_shape_bytes(d.shape) for d in defs) if defs
                    else max(_shape_bytes(instr.line) - res_b, 0))
            is_coll = instr.op in COLLECTIVE_OPS or (
                instr.op == "custom-call" and "all_to_all" in instr.op_name)
            payload = int(op_b * off_frac) if is_coll else 0
            is_triv = instr.op in TRIVIAL_OPS or (
                instr.op in ("call", "fusion")
                and _called_all_trivial(instr, comps))
            if is_coll:
                cost = payload / max(chip.ici_eff_gbps, 1e-9)
            elif is_triv:
                cost = 0.0
            else:
                cost = (res_b + op_b) / max(chip.hbm_gbps, 1e-9)
            self.nodes.append(GraphNode(
                instr=instr, index=i,
                phase=_resolve_phase(instr, comps),
                phase_leaf="", is_collective=is_coll,
                is_trivial=is_triv,
                result_bytes=res_b, operand_bytes=op_b,
                payload_bytes=payload, cost_ns=cost))
            index[instr.name] = i
        for n in self.nodes:
            n.phase_leaf = (n.phase.rsplit("/", 1)[-1] if n.phase else "")
        # edges: operand -> consumer (unknown operand names create none)
        self.preds: List[List[int]] = [[] for _ in self.nodes]
        self.succs: List[List[int]] = [[] for _ in self.nodes]
        for n in self.nodes:
            for op_name_ in n.instr.operands:
                j = index.get(op_name_)
                if j is not None and j != n.index:
                    self.preds[n.index].append(j)
                    self.succs[j].append(n.index)
        self._topo: Optional[List[int]] = None

    # -- structure --------------------------------------------------------
    def roots(self) -> List[int]:
        """Sink nodes (no consumers). A compiled module always has at
        least one — the ROOT instruction."""
        return [n.index for n in self.nodes if not self.succs[n.index]]

    def topo_order(self) -> List[int]:
        """Kahn topological order; raises :class:`ScheduleGraphError` on
        a dependency cycle (impossible in well-formed SSA HLO — a cycle
        means the parser mis-read operands)."""
        if self._topo is not None:
            return self._topo
        indeg = [len(p) for p in self.preds]
        ready = [i for i, d in enumerate(indeg) if d == 0]
        out: List[int] = []
        while ready:
            i = ready.pop()
            out.append(i)
            for j in self.succs[i]:
                indeg[j] -= 1
                if indeg[j] == 0:
                    ready.append(j)
        if len(out) != len(self.nodes):
            stuck = [self.nodes[i].instr.name
                     for i, d in enumerate(indeg) if d > 0][:6]
            raise ScheduleGraphError(
                f"dependency cycle in parsed entry computation "
                f"(involving {stuck}) — operand extraction mis-read the "
                "module text")
        self._topo = out
        return out

    def _cone(self, start: int, edges: List[List[int]]) -> Set[int]:
        seen: Set[int] = set()
        stack = list(edges[start])
        while stack:
            i = stack.pop()
            if i in seen:
                continue
            seen.add(i)
            stack.extend(edges[i])
        return seen

    def ancestors(self, i: int) -> Set[int]:
        return self._cone(i, self.preds)

    def descendants(self, i: int) -> Set[int]:
        return self._cone(i, self.succs)

    def critical_path(self) -> List[int]:
        """Longest (max summed cost) source→sink chain, as node indices
        in execution order."""
        order = self.topo_order()
        dist = [0.0] * len(self.nodes)
        back: List[Optional[int]] = [None] * len(self.nodes)
        for i in order:
            best, arg = 0.0, None
            for p in self.preds[i]:
                if dist[p] > best:
                    best, arg = dist[p], p
            dist[i] = best + self.nodes[i].cost_ns
            back[i] = arg
        end = max(range(len(self.nodes)), key=lambda i: dist[i],
                  default=None)
        if end is None:
            return []
        path = []
        cur: Optional[int] = end
        while cur is not None:
            path.append(cur)
            cur = back[cur]
        return path[::-1]

    def independent_compute_ns(self, i: int) -> float:
        """Total modeled cost of REAL compute (non-trivial, non-collective
        nodes) neither upstream nor downstream of node ``i`` — the work a
        latency-hiding scheduler could run concurrently with it."""
        return sum(self.independent_compute_by_phase(i).values())

    def independent_compute_by_phase(self, i: int) -> Dict[str, float]:
        """The :meth:`independent_compute_ns` sum broken down by the
        independent nodes' ``detpu`` phase — what lets the schedule
        declaration check verify an overlap claim against the DECLARED
        partner phase rather than against any independent work."""
        cone = self.ancestors(i) | self.descendants(i) | {i}
        out: Dict[str, float] = {}
        for n in self.nodes:
            if (n.index in cone or n.is_collective or n.is_trivial
                    or n.cost_ns <= 0):
                continue
            out[n.phase] = out.get(n.phase, 0.0) + n.cost_ns
        return out


# ----------------------------------------------------------- the contracts


@dataclasses.dataclass(frozen=True)
class ScheduleContract:
    """One declarative expectation on the collectives of a phase.

    ``phase`` is an ``fnmatch`` glob tested against each collective's
    full ``detpu`` path AND its leaf (census convention). ``expect`` is
    ``"present"`` (>= ``min_count`` matching collectives), or
    ``"serialized"`` / ``"overlappable"`` (present AND every match
    classified so). ``on_critical_path`` additionally pins whether the
    matches sit on the modeled critical path."""
    phase: str
    expect: str = "present"
    min_count: int = 1
    on_critical_path: Optional[bool] = None
    reason: str = ""

    def __post_init__(self) -> None:
        if self.expect not in ("present", "serialized", "overlappable"):
            raise ValueError(
                f"ScheduleContract({self.phase!r}): expect must be "
                f"'present' | 'serialized' | 'overlappable', got "
                f"{self.expect!r}")


def declared_overlap_contracts(schedule) -> List[ScheduleContract]:
    """One ``expect="overlappable"`` contract per collective phase that
    DECLARES an overlap — the expectations a pipelined (or streaming)
    :class:`~..parallel.schedule.StepSchedule`'s claims imply. Running
    these next to :meth:`ScheduleReport.check_against_schedule` makes
    the gate two-sided: the declaration check verifies the claimed
    partner compute exists, and these verify the collective's GLOBAL
    classification flipped to overlappable."""
    out: List[ScheduleContract] = []
    for p in schedule.phases:
        if p.kind == "collective" and p.overlaps:
            out.append(ScheduleContract(
                p.name, expect="overlappable",
                reason=f"schedule '{schedule.name}' declares overlap "
                       f"with {list(p.overlaps)}"))
    return out


def baseline_contracts() -> List[ScheduleContract]:
    """The documented baseline of today's UNPIPELINED hybrid step: the
    id / out / grad all-to-alls exist, sit on the critical path, and are
    serialized against dense compute — the measured starting line the
    pipelined step (ROADMAP item 2) has to beat. A future overlap win
    ships a new schedule AND flips these to ``expect="overlappable"`` in
    the same PR; until then, a candidate that silently changes the
    dependency shape fails the gate either way."""
    why = ("unpipelined baseline: the exchange runs strictly between its "
           "producer and consumer phases")
    return [
        ScheduleContract("id_all_to_all", expect="serialized",
                         on_critical_path=True, reason=why),
        ScheduleContract("out_all_to_all", expect="serialized",
                         on_critical_path=True, reason=why),
        ScheduleContract("grad_all_to_all", expect="serialized",
                         on_critical_path=True, reason=why),
    ]


# -------------------------------------------------------------- the report


@dataclasses.dataclass
class CollectiveInfo:
    """One collective of the compiled step, classified."""
    name: str
    op: str
    phase: str
    phase_leaf: str
    payload_bytes: int
    cost_ns: float
    independent_compute_ns: float
    #: the independent compute broken down by its nodes' detpu phase —
    #: the declaration check verifies overlap claims against the
    #: DECLARED partner's share, not the global sum
    independent_by_phase: Dict[str, float]
    overlap_ratio: float          # independent compute / collective cost
    classification: str           # "serialized" | "overlappable"
    on_critical_path: bool

    def independent_matching(self, globs) -> float:
        """Independent compute attributable to phases matching any of
        ``globs`` — full path, leaf (census convention), or any single
        path COMPONENT, so a declared partner phase owns its nested
        sub-scopes (``embedding_forward_mb1/lookup_w4_d_mb1/
        packed_gather`` counts toward a ``lookup_*_mb1`` claim: the
        gather IS the lookup's compute)."""
        total = 0.0
        for phase, ns in self.independent_by_phase.items():
            parts = phase.split("/") if phase else []
            if any(fnmatch.fnmatchcase(phase, g)
                   or any(fnmatch.fnmatchcase(p, g) for p in parts)
                   for g in globs):
                total += ns
        return total

    def to_json(self) -> Dict[str, Any]:
        d = dataclasses.asdict(self)
        d["independent_by_phase"] = {
            k or "(unscoped)": round(v, 3)
            for k, v in self.independent_by_phase.items()}
        return d


@dataclasses.dataclass
class ScheduleReport:
    """Structured result of one schedule-graph audit."""
    label: str
    world: int
    chip: str
    backend: Optional[str]
    nodes: int
    edges: int
    collectives: List[CollectiveInfo]
    critical_path_ns: float
    critical_path_bytes: int
    critical_path_phases: List[Tuple[str, float]]   # condensed runs
    serialized_collective_fraction: float
    total_collective_ns: float
    total_compute_ns: float
    overlap_min_ratio: float
    violations: List[str]
    #: modeled cost summed per detpu phase path (non-trivial nodes,
    #: collectives included under their exchange phase) — the modeled
    #: half of the measured-vs-modeled drift table
    #: (:func:`~.phase_profile.calibrate` joins measured trace durations
    #: against exactly these keys)
    phase_cost_ns: Dict[str, float] = dataclasses.field(
        default_factory=dict)

    @property
    def ok(self) -> bool:
        return not self.violations

    def _matching(self, glob: str) -> List[CollectiveInfo]:
        return [c for c in self.collectives
                if fnmatch.fnmatchcase(c.phase, glob)
                or fnmatch.fnmatchcase(c.phase_leaf, glob)]

    def _add(self, msg: str) -> None:
        if msg not in self.violations:
            self.violations.append(msg)

    def check(self, contracts: Sequence[ScheduleContract]
              ) -> "ScheduleReport":
        """Evaluate contracts; violations accumulate (idempotent)."""
        for c in contracts:
            matched = self._matching(c.phase)
            why = f" — {c.reason}" if c.reason else ""
            if len(matched) < c.min_count:
                self._add(
                    f"schedule contract: phase '{c.phase}' expected >= "
                    f"{c.min_count} collective(s), found {len(matched)}"
                    f"{why}")
                continue
            for m in matched:
                if c.expect in ("serialized", "overlappable") \
                        and m.classification != c.expect:
                    self._add(
                        f"schedule contract: collective {m.name} in phase "
                        f"'{m.phase}' is {m.classification}, expected "
                        f"{c.expect} (cost {m.cost_ns:.1f} ns vs "
                        f"independent compute "
                        f"{m.independent_compute_ns:.1f} ns){why}")
                if c.on_critical_path is not None \
                        and m.on_critical_path != c.on_critical_path:
                    self._add(
                        f"schedule contract: collective {m.name} in phase "
                        f"'{m.phase}' on_critical_path="
                        f"{m.on_critical_path}, expected "
                        f"{c.on_critical_path}{why}")
        return self

    def check_against_schedule(self, schedule) -> "ScheduleReport":
        """Verify a :class:`~..parallel.schedule.StepSchedule`'s claims
        against the compiled reality:

        * every declared ``collective`` phase must match >= 1 compiled
          collective (a declared exchange that compiled to nothing means
          the schedule and the program drifted apart);
        * every declared **overlap** of a collective phase must exist in
          the DAG — each matching collective must be classified
          overlappable. A schedule claiming overlap over a serialized
          program is the lie ``--strict`` exists to catch.
        """
        for p in schedule.phases:
            if p.kind != "collective":
                continue
            matched = self._matching(p.name)
            if not matched:
                self._add(
                    f"schedule '{schedule.name}': declared collective "
                    f"phase '{p.name}' matches no compiled collective — "
                    "the schedule no longer describes the program")
                continue
            if not p.overlaps:
                continue
            for m in matched:
                # the claim is verified against the DECLARED partner's
                # independent-compute share, not the global sum — a
                # claim of "hides under dense compute" must not be
                # satisfied by some unrelated independent chain
                partner_ind = m.independent_matching(p.overlaps)
                if partner_ind < self.overlap_min_ratio * m.cost_ns:
                    self._add(
                        f"schedule '{schedule.name}': phase '{p.name}' "
                        f"declares overlap with {list(p.overlaps)} but "
                        f"the compiled program SERIALIZES collective "
                        f"{m.name} against it (independent "
                        f"{list(p.overlaps)} compute {partner_ind:.1f} "
                        f"ns < {self.overlap_min_ratio:.2f} x cost "
                        f"{m.cost_ns:.1f} ns) — the declared overlap "
                        "does not exist in what XLA emitted")
        return self

    def raise_on_violations(self) -> "ScheduleReport":
        if self.violations:
            raise ScheduleGraphError(
                "schedule audit failed:\n  - "
                + "\n  - ".join(self.violations))
        return self

    # -- serialization ----------------------------------------------------
    def summary(self) -> Dict[str, Any]:
        """The compact record: the head of :meth:`to_json`."""
        return {
            "label": self.label,
            "world": self.world,
            "chip": self.chip,
            "serialized_collective_fraction":
                round(self.serialized_collective_fraction, 6),
            "critical_path_ns": round(self.critical_path_ns, 3),
            "critical_path_bytes": self.critical_path_bytes,
            "total_collective_ns": round(self.total_collective_ns, 3),
            "total_compute_ns": round(self.total_compute_ns, 3),
            "collectives": [
                {"phase": c.phase, "op": c.op,
                 "payload_bytes": c.payload_bytes,
                 "classification": c.classification,
                 "on_critical_path": c.on_critical_path}
                for c in self.collectives],
            "violations": list(self.violations),
        }

    def to_json(self) -> Dict[str, Any]:
        d = self.summary()
        d.update(
            backend=self.backend, nodes=self.nodes, edges=self.edges,
            overlap_min_ratio=self.overlap_min_ratio,
            critical_path_phases=[
                {"phase": p, "cost_ns": round(ns, 3)}
                for p, ns in self.critical_path_phases],
            phase_cost_ns={k or "(unscoped)": round(v, 3)
                           for k, v in self.phase_cost_ns.items()},
            collectives=[c.to_json() for c in self.collectives])
        return d

    def dumps(self, **kw: Any) -> str:
        return json.dumps(self.to_json(), **kw)

    def markdown(self) -> str:
        """The per-collective classification as a markdown table (docs /
        PR bodies) plus the condensed critical path."""
        lines = [
            "| collective | phase | payload | cost | independent "
            "compute | verdict | critical path |",
            "|---|---|---|---|---|---|---|",
        ]
        for c in self.collectives:
            lines.append(
                f"| `{c.name}` | `{c.phase}` | {c.payload_bytes} B "
                f"| {c.cost_ns:.1f} ns | "
                f"{c.independent_compute_ns:.1f} ns "
                f"| **{c.classification}** "
                f"| {'yes' if c.on_critical_path else 'no'} |")
        lines.append("")
        lines.append(
            f"critical path: {self.critical_path_ns:.1f} ns modeled, "
            f"{self.critical_path_bytes} bytes, "
            f"serialized_collective_fraction="
            f"{self.serialized_collective_fraction:.3f}")
        lines.append("phases on the path: " + " -> ".join(
            f"{p or '(unscoped)'} ({ns:.1f} ns)"
            for p, ns in self.critical_path_phases))
        return "\n".join(lines)


# ----------------------------------------------------------- entry points


def analyze_graph(graph: ScheduleGraph, *, label: str = "step",
                  backend: Optional[str] = None,
                  overlap_min_ratio: float = 1.0) -> ScheduleReport:
    """Classify a built :class:`ScheduleGraph` into a
    :class:`ScheduleReport` (no contracts applied yet).

    A collective is **overlappable** when the modeled independent
    compute outside its ancestor/descendant cones is at least
    ``overlap_min_ratio`` times its own cost — i.e. enough concurrent
    work exists to hide the whole transfer; anything less is
    **serialized** (partial hiding is a follow-up refinement, and a
    gate must not reward it prematurely)."""
    path = graph.critical_path()
    on_path = set(path)
    collectives: List[CollectiveInfo] = []
    ser_cost = tot_cost = 0.0
    for n in graph.nodes:
        if not n.is_collective:
            continue
        by_phase = graph.independent_compute_by_phase(n.index)
        ind = sum(by_phase.values())
        ratio = ind / n.cost_ns if n.cost_ns > 0 else float("inf")
        cls = ("overlappable" if ratio >= overlap_min_ratio
               else "serialized")
        tot_cost += n.cost_ns
        if cls == "serialized":
            ser_cost += n.cost_ns
        collectives.append(CollectiveInfo(
            name=n.instr.name, op=n.instr.op, phase=n.phase,
            phase_leaf=n.phase_leaf, payload_bytes=n.payload_bytes,
            cost_ns=n.cost_ns, independent_compute_ns=ind,
            independent_by_phase=by_phase,
            overlap_ratio=ratio, classification=cls,
            on_critical_path=n.index in on_path))
    # condensed critical path: consecutive same-phase nodes fold into one
    runs: List[Tuple[str, float]] = []
    for i in path:
        n = graph.nodes[i]
        if runs and runs[-1][0] == n.phase:
            runs[-1] = (n.phase, runs[-1][1] + n.cost_ns)
        else:
            runs.append((n.phase, n.cost_ns))
    phase_cost: Dict[str, float] = {}
    for n in graph.nodes:
        if n.is_trivial or n.cost_ns <= 0:
            continue
        phase_cost[n.phase] = phase_cost.get(n.phase, 0.0) + n.cost_ns
    return ScheduleReport(
        label=label, world=graph.world, chip=graph.chip.name,
        backend=backend,
        nodes=len(graph.nodes),
        edges=sum(len(s) for s in graph.succs),
        collectives=collectives,
        critical_path_ns=sum(graph.nodes[i].cost_ns for i in path),
        critical_path_bytes=sum(
            graph.nodes[i].payload_bytes if graph.nodes[i].is_collective
            else (0 if graph.nodes[i].is_trivial
                  else graph.nodes[i].result_bytes
                  + graph.nodes[i].operand_bytes)
            for i in path),
        critical_path_phases=runs,
        serialized_collective_fraction=(
            ser_cost / tot_cost if tot_cost > 0 else 0.0),
        total_collective_ns=tot_cost,
        total_compute_ns=sum(n.cost_ns for n in graph.nodes
                             if not n.is_collective and not n.is_trivial),
        overlap_min_ratio=overlap_min_ratio,
        violations=[],
        phase_cost_ns=phase_cost)


def audit_text(txt: str, *, label: str = "step", world: int = 1,
               chip: str = "v5e", backend: Optional[str] = None,
               overlap_min_ratio: float = 1.0) -> ScheduleReport:
    """Parse optimized HLO text, build the DAG, classify. Pure text ->
    dataclass (the census's ``census_of_text`` analogue)."""
    graph = ScheduleGraph(parse_hlo_module(txt), world=world,
                          chip=CHIP_SPECS[chip])
    if not graph.nodes:
        raise ScheduleGraphError(
            f"schedule audit of {label!r} parsed 0 entry instructions "
            f"from a {len(txt)}-byte module — unrecognized HLO text; "
            "the overlap gate cannot run on it")
    if not graph.roots():
        raise ScheduleGraphError(
            f"schedule audit of {label!r}: parsed graph has no sink "
            "nodes — operand extraction mis-read the module")
    graph.topo_order()   # cycle check up front, before any contract runs
    return analyze_graph(graph, label=label, backend=backend,
                         overlap_min_ratio=overlap_min_ratio)


def audit_step_fn(step_fn, args: Sequence[Any], *, world: int = 1,
                  label: str = "step", chip: str = "v5e",
                  schedule=None,
                  contracts: Optional[Sequence[ScheduleContract]] = None,
                  overlap_min_ratio: float = 1.0) -> ScheduleReport:
    """Compile a jitted step abstractly and audit its schedule graph.

    ``args`` may be concrete arrays or ``jax.ShapeDtypeStruct`` pytrees —
    ``step_fn.lower(*args).compile()`` never executes anything. Plain
    callables are wrapped in ``jax.jit`` first. ``schedule`` (a
    :class:`~..parallel.schedule.StepSchedule`) adds the declaration
    check; ``contracts`` adds the declarative expectations
    (``None`` applies none — callers pin their own baselines)."""
    if not hasattr(step_fn, "lower"):
        step_fn = jax.jit(step_fn)
    txt = step_fn.lower(*args).compile().as_text()
    try:
        backend = jax.default_backend()
    except Exception:  # noqa: BLE001 - stamp is best-effort
        backend = None
    rep = audit_text(txt, label=label, world=world, chip=chip,
                     backend=backend,
                     overlap_min_ratio=overlap_min_ratio)
    if schedule is not None:
        rep.check_against_schedule(schedule)
    if contracts:
        rep.check(contracts)
    return rep


def audit_train_step(de,
                     loss_fn,
                     dense_tx,
                     emb_optimizer,
                     cat_inputs,
                     batch,
                     mesh=None,
                     lr_schedule=1.0,
                     with_metrics: Optional[bool] = None,
                     nan_guard: Optional[bool] = None,
                     telemetry=None,
                     dynamic=None,
                     dense_params=None,
                     state=None,
                     chip: str = "v5e",
                     schedule=None,
                     contracts: Optional[Sequence[ScheduleContract]] = None,
                     overlap_min_ratio: float = 1.0,
                     label: str = "hybrid_train_step") -> ScheduleReport:
    """Build the hybrid train step exactly like
    :func:`~..parallel.trainer.make_hybrid_train_step` (the shared
    :func:`~.audit.build_abstract_step` harness, so this gate audits the
    same program as the jaxpr auditor and the HLO census) and audit its
    schedule graph.

    ``schedule=None`` checks the layer's own declared schedule
    (``de.schedule``); ``contracts=None`` applies
    :func:`baseline_contracts` — pass an explicit (possibly empty) list
    to override either."""
    from .audit import build_abstract_step

    from ..parallel.schedule import without_streaming

    step, args, _, _, _, _ = build_abstract_step(
        de, loss_fn, dense_tx, emb_optimizer, cat_inputs, batch,
        mesh=mesh, lr_schedule=lr_schedule, with_metrics=with_metrics,
        nan_guard=nan_guard, telemetry=telemetry, dynamic=dynamic,
        dense_params=dense_params, state=state)
    if schedule is None:
        schedule = de.schedule
        if dynamic is None or dynamic is False:
            # a streaming-capable layer trained WITHOUT dynamic=
            # executes the non-streaming program: its compiled DAG has
            # no admission-staging nodes, so the streaming overlap
            # declaration must not be checked against it
            schedule = without_streaming(schedule)
    if contracts is None:
        contracts = baseline_contracts() if de.world_size > 1 else []
    return audit_step_fn(
        step, args, world=de.world_size, label=label, chip=chip,
        schedule=schedule, contracts=contracts,
        overlap_min_ratio=overlap_min_ratio)
