"""Per-phase op census of the *optimized HLO* — the compiled pass budget.

:mod:`.audit` (PR 4) verifies the SPMD communication contract at the
jaxpr level, but the jaxpr is what we *asked for*; on TPU the compiler
owns the hot path, and what actually runs — how many gather passes, how
many sorts, which converts — only exists in the post-optimization HLO
module. In the GSPMD framing (SNIPPETS.md [2]) the compiled program is
the scaling contract, so that is the artifact this module audits.

:func:`census_step_fn` lowers + compiles a jitted step (abstract — the
same harness as :func:`~.memory.compiled_step_report`, nothing executes),
parses the optimized HLO text, and attributes every instruction to its
``obs.scope`` phase: ``jax.named_scope`` components survive XLA
optimization inside ``metadata={op_name="..."}``, including into fused
computations and the ``while``-loops CPU's scatter expander produces. The
result is a :class:`CensusReport` — per phase (full ``detpu/`` scope
path): gather / scatter / sort / cumsum / convert / transpose /
all-to-all passes, convert dtype pairs, fusion count, and estimated bytes
touched. This is the additive per-phase budget of ROADMAP 3(a) (decode,
gather, exchange, bwd expand, dedup, apply), emitted as a dataclass, a
JSON/JSONL record, and a markdown table.

On top of the census sit declarative :class:`PassBudget` contracts
("the ``dedup`` phase holds zero sort/segment-sum passes when the sparse
optimizer declares ``needs_dedup=False``", "at most N gather passes per
lookup group", "no float convert round-trips inside the apply phase"),
enforced by ``tools/hlo_audit.py --strict`` inside ``make verify``.

Counting convention: one HLO instruction of a row-op opcode = one pass.
Backend lowering differences are normalized where they matter for the
gates (a CPU ``while`` whose ``op_name`` primitive is a scatter counts as
a scatter pass; a ``reduce-window`` from a ``cumsum`` counts as cumsum),
and budgets are pinned against the same parser on the same backend, so
the gate is self-consistent. Bytes are estimates: the sum of result +
listed-operand element bytes of the counted instruction.

Run under ``JAX_PLATFORMS=cpu`` with
``--xla_force_host_platform_device_count=N`` for an N-position mesh, like
the step auditor; ``tools/hlo_audit.py`` does exactly that.
"""

from __future__ import annotations

import dataclasses
import fnmatch
import json
import re
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax

from ..utils import obs

#: census op kinds a PassBudget can cap (plus "convert_roundtrip" and
#: "fusion"); these are the row-op passes of the ROADMAP 3(a) budget
ROW_OP_KINDS = ("gather", "scatter", "sort", "cumsum", "all_to_all",
                "convert", "transpose")

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3b11fnuz": 1, "f8e5m2fnuz": 1,
    "f8e4m3fnuz": 1,
}
_FLOAT_DTYPES = frozenset(d for d in _DTYPE_BYTES
                          if d.startswith(("f", "bf")))

# one HLO instruction: `[ROOT ]%name = SHAPE opcode(...)` where SHAPE is a
# tuple `(f32[..], /*index=5*/ s32[..])` (XLA interleaves index comments
# into long tuples) or a plain whitespace-free token — `f32[16,8]{1,0}`,
# or post-layout-assignment TPU spellings like `f32[16,8]{1,0:T(8,128)}`
# / `...S(1)}` (the required whitespace before the opcode disambiguates,
# so `\S+` backtracks off `opcode(` correctly)
_INST_RE = re.compile(
    r"^\s*(?:ROOT\s+)?(?P<name>%?[\w.\-]+)\s*=\s*"
    r"(?P<shape>\((?:[^()]|\([^()]*\))*\)|\S+)\s+"
    r"(?P<op>[a-z][\w\-]*)\(")
_OPNAME_RE = re.compile(r'op_name="([^"]*)"')
_OPERAND_RE = re.compile(r"%[\w.\-]+")
# the phase-name extractor is SHARED with the scope writer (utils/obs.py
# mints the names) and with the measured-trace parser, so the static and
# measured attributions can never drift onto different spellings
_DETPU_RE = obs.SCOPE_RE
_SHAPE_TOKEN_RE = re.compile(
    r"\b(pred|bf16|f8\w+|[fsuc]\d+)\[([\d,]*)\]")


def _token_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def _kind_of(op: str, prim: str) -> Optional[str]:
    """Normalize an HLO opcode (+ the trailing jax primitive from its
    op_name) into a census kind."""
    if op in ("gather", "scatter", "sort", "transpose"):
        return op
    if op == "convert":
        return "convert"
    if op == "all-to-all":
        return "all_to_all"
    if op == "while" and "scatter" in prim:
        return "scatter"  # CPU's scatter expander rewrites scatter->while
    if op == "reduce-window" and "cumsum" in prim:
        return "cumsum"
    if op == "custom-call" and ("all_to_all" in prim or "cumsum" in prim):
        return "all_to_all" if "all_to_all" in prim else "cumsum"
    if op == "fusion":
        return "fusion"
    return None


class CensusError(RuntimeError):
    """Raised by :meth:`CensusReport.raise_on_violations` in strict use."""


@dataclasses.dataclass
class PhasePasses:
    """Aggregated passes of one phase (one full ``detpu/`` scope path)."""
    path: str                       # e.g. "sparse_apply/sparse_apply_w8/dedup"
    leaf: str                       # last component, e.g. "dedup"
    counts: Dict[str, int]          # kind -> pass count (ROW_OP_KINDS)
    convert_pairs: Dict[str, int]   # "bf16->f32" -> count
    fusions: int
    instructions: int               # every instruction attributed here
    bytes_est: int                  # result+operand bytes of counted passes

    def roundtrips(self) -> int:
        """Float narrowing/widening convert pairs inside this phase:
        ``min(count[a->b], count[b->a])`` summed over unordered FLOAT dtype
        pairs. A value squeezed f32->bf16->f32 inside one phase silently
        lost 16 bits of mantissa; integer casts are excluded (index
        arithmetic legitimately round-trips)."""
        n = 0
        seen = set()
        for pair, cnt in self.convert_pairs.items():
            a, b = pair.split("->")
            if a not in _FLOAT_DTYPES or b not in _FLOAT_DTYPES or a == b:
                continue
            key = tuple(sorted((a, b)))
            if key in seen:
                continue
            seen.add(key)
            n += min(cnt, self.convert_pairs.get(f"{b}->{a}", 0))
        return n

    def to_json(self) -> Dict[str, Any]:
        d = dict(self.counts)
        d.update(path=self.path, leaf=self.leaf, fusion=self.fusions,
                 instructions=self.instructions, bytes_est=self.bytes_est,
                 convert_pairs=dict(self.convert_pairs),
                 convert_roundtrip=self.roundtrips())
        return d


@dataclasses.dataclass(frozen=True)
class PassBudget:
    """One declarative cap on the passes of a phase.

    ``phase`` is an ``fnmatch`` glob tested against each phase's full
    ``detpu`` path AND its leaf name (so ``"dedup"`` hits the dedup scope
    wherever it nests, and ``"*/lookup_*/packed_gather"`` pins the gathers
    of every lookup group). ``kind`` is a :data:`ROW_OP_KINDS` entry,
    ``"fusion"``, or ``"convert_roundtrip"``. ``per_path=True`` applies
    the cap to every matching phase individually (per-group budgets);
    otherwise the counts of all matching phases sum first.
    ``max_passes=None`` means unbounded, so a floor-only contract
    (``min_passes=N`` alone) guards a pass whose *disappearance* would be
    the bug without also capping it."""
    phase: str
    kind: str
    max_passes: Optional[int] = None
    min_passes: int = 0
    per_path: bool = False
    reason: str = ""

    def __post_init__(self) -> None:
        if self.max_passes is not None and self.min_passes > self.max_passes:
            raise ValueError(
                f"PassBudget({self.phase!r}, {self.kind!r}): min_passes="
                f"{self.min_passes} > max_passes={self.max_passes} can "
                "never hold")


def dedup_zero_contracts(reason: str) -> List[PassBudget]:
    """The SGD pass-cut contract: a ``detpu/dedup`` scope must compile to
    NOTHING — no sort, no segment-sum scatter, no cumsum boundary pass, no
    gather — when the optimizer declares ``needs_dedup=False``."""
    return [PassBudget("dedup", k, max_passes=0, reason=reason)
            for k in ("sort", "scatter", "cumsum", "gather")]


def apply_contracts() -> List[PassBudget]:
    """The sparse apply's budget. A small table's cotangents are summed as
    ``onehot(ids)^T @ cotangents`` (``parallel/apply.py:small_table_sums``):
    the ``small_sum`` scope of the dense slots and the ``ragged_sum`` scope
    of the ragged ones (whose ``take`` of the cotangents stays outside it)
    hold no sort, scatter, cumsum or gather, or the sums are back on a row
    path. And the sweep's forms are ONE scatter a width slab, whatever
    joined its stream."""
    why = ("small tables are summed on the MXU: a row operation there is "
           "the scatter's stream again")
    out = [PassBudget(scope, k, max_passes=0, reason=why)
           for scope in ("small_sum", "ragged_sum")
           for k in ("sort", "scatter", "cumsum", "gather")]
    out += [PassBudget("scatter_" + form, "scatter", max_passes=1,
                       per_path=True, reason="ONE scatter a width slab")
            for form in ("sort_fused", "unsorted")]
    return out


def lookup_contracts() -> List[PassBudget]:
    """The lookup's budget. A small-table ragged group's bags are summed
    from within-tile prefixes of rows made on the MXU, read at the splits
    (``parallel/lookup.py:block_bag_sums``): the ``segment_prefix`` scope
    holds no scatter and no sort, or the combine is back on the row path."""
    why = ("small-table bags are prefix differences: a scatter or a sort "
           "there is the row path again")
    return [PassBudget("segment_prefix", k, max_passes=0, reason=why)
            for k in ("scatter", "sort")]


def default_contracts(emb_optimizer=None) -> List[PassBudget]:
    """Config-independent contracts for a hybrid train step census.

    That is :func:`apply_contracts`, :func:`lookup_contracts` and the dedup
    budget: when the sparse optimizer declares ``needs_dedup=False`` (and
    ``DETPU_SGD_DEDUP`` does not force the pass back in), the compiled dedup
    phase must be empty. Shape-dependent
    budgets (gathers per lookup group, pinned dedup counts for stateful
    optimizers) belong to the caller — ``tools/hlo_audit.py`` pins them
    for the reference configurations."""
    from ..parallel.optimizers import sgd_dedup_forced

    out = apply_contracts() + lookup_contracts()
    if emb_optimizer is not None and not getattr(
            emb_optimizer, "needs_dedup", True) and not sgd_dedup_forced():
        out += dedup_zero_contracts(
            f"{type(emb_optimizer).__name__} declares needs_dedup=False "
            "(linear update: duplicates are scatter-add-safe)")
    return out


@dataclasses.dataclass
class CensusReport:
    """Structured result of one optimized-HLO census."""
    label: str
    world: int
    backend: Optional[str]
    phases: Dict[str, PhasePasses]        # keyed by full detpu path
    total_instructions: int
    unattributed_row_ops: int             # counted kinds with no detpu scope
    violations: List[str]

    @property
    def ok(self) -> bool:
        return not self.violations

    def _matching(self, glob: str) -> List[PhasePasses]:
        return [p for p in self.phases.values()
                if fnmatch.fnmatchcase(p.path, glob)
                or fnmatch.fnmatchcase(p.leaf, glob)]

    def _phase_count(self, p: PhasePasses, kind: str) -> int:
        if kind == "convert_roundtrip":
            return p.roundtrips()
        if kind == "fusion":
            return p.fusions
        return p.counts.get(kind, 0)

    def passes(self, phase_glob: str, kind: str) -> int:
        """Total passes of ``kind`` across every phase matching the glob."""
        return sum(self._phase_count(p, kind)
                   for p in self._matching(phase_glob))

    def check(self, contracts: Sequence[PassBudget]) -> "CensusReport":
        """Evaluate pass budgets; violations append to ``self.violations``
        (idempotent per distinct message). Returns self for chaining."""
        for b in contracts:
            matched = self._matching(b.phase)
            units: List[Tuple[str, int]]
            if b.per_path:
                units = [(p.path, self._phase_count(p, b.kind))
                         for p in matched]
                if not matched and b.min_passes > 0:
                    # a min contract must fire when the phase itself is
                    # gone, not just when it compiled to too few passes
                    units = [(b.phase, 0)]
            else:
                # no matches sums to 0, which also makes a min contract
                # fire on a vanished phase
                units = [(b.phase, sum(self._phase_count(p, b.kind)
                                       for p in matched))]
            for where, n in units:
                msg = None
                if b.max_passes is not None and n > b.max_passes:
                    msg = (f"pass budget exceeded: {n} {b.kind} pass(es) in "
                           f"phase '{where}' (budget {b.max_passes})")
                elif n < b.min_passes:
                    msg = (f"pass budget underrun: {n} {b.kind} pass(es) in "
                           f"phase '{where}' (expected >= {b.min_passes} — "
                           "a pass the contract relies on disappeared)")
                if msg:
                    if b.reason:
                        msg += f" — {b.reason}"
                    if msg not in self.violations:
                        self.violations.append(msg)
        return self

    def raise_on_violations(self) -> "CensusReport":
        if self.violations:
            raise CensusError(
                "HLO pass census failed:\n  - "
                + "\n  - ".join(self.violations))
        return self

    def phase_table(self) -> Dict[str, Dict[str, int]]:
        """The per-phase budget as rows: kind counts + fusion + bytes_est
        per phase path."""
        out: Dict[str, Dict[str, int]] = {}
        for path, p in sorted(self.phases.items()):
            row = {k: p.counts.get(k, 0) for k in ROW_OP_KINDS}
            row["fusion"] = p.fusions
            row["convert_roundtrip"] = p.roundtrips()
            row["bytes_est"] = p.bytes_est
            out[path or "(unscoped)"] = row
        return out

    def markdown(self) -> str:
        """The per-phase budget as a markdown table (docs / PR bodies)."""
        cols = list(ROW_OP_KINDS) + ["fusion", "bytes_est"]
        lines = ["| phase | " + " | ".join(cols) + " |",
                 "|---" * (len(cols) + 1) + "|"]
        for path, row in self.phase_table().items():
            cells = [str(row[c]) for c in cols]
            lines.append(f"| `{path}` | " + " | ".join(cells) + " |")
        return "\n".join(lines)

    def to_json(self) -> Dict[str, Any]:
        return {
            "label": self.label,
            "world": self.world,
            "backend": self.backend,
            "ok": self.ok,
            "phases": {k or "(unscoped)": p.to_json()
                       for k, p in sorted(self.phases.items())},
            "total_instructions": self.total_instructions,
            "unattributed_row_ops": self.unattributed_row_ops,
            "violations": list(self.violations),
        }

    def dumps(self, **kw: Any) -> str:
        return json.dumps(self.to_json(), **kw)


# ------------------------------------------------------------- the parser


def census_of_text(txt: str, *, label: str = "step", world: int = 1,
                   backend: Optional[str] = None) -> CensusReport:
    """Parse optimized HLO module text into a :class:`CensusReport`.

    Pure text -> dataclass (no jax beyond what the caller already did):
    every instruction line — entry computation, fused computations, while
    bodies, sort comparators — is attributed to the ``detpu/`` scope path
    recorded in its ``metadata.op_name``."""
    phases: Dict[str, PhasePasses] = {}
    total = 0
    unattributed = 0
    # instruction name -> its result shape tokens: this XLA prints operands
    # as bare names (``convert(%param_0.2)``), so operand shapes resolve
    # through their defining instruction (names are module-unique)
    results: Dict[str, List[Tuple[str, str]]] = {}
    for line in txt.splitlines():
        m = _INST_RE.match(line)
        if m is None:
            continue
        total += 1
        result = _SHAPE_TOKEN_RE.findall(m.group("shape"))
        results["%" + m.group("name").lstrip("%")] = result
        op = m.group("op")
        nm = _OPNAME_RE.search(line)
        op_name = nm.group(1) if nm else ""
        parts = _DETPU_RE.findall(op_name)
        path = "/".join(parts)
        prim = op_name.rsplit("/", 1)[-1] if op_name else ""
        kind = _kind_of(op, prim)
        ph = phases.get(path)
        if ph is None:
            ph = phases[path] = PhasePasses(
                path=path, leaf=parts[-1] if parts else "",
                counts={}, convert_pairs={}, fusions=0, instructions=0,
                bytes_est=0)
        ph.instructions += 1
        if kind is None:
            continue
        if kind == "fusion":
            ph.fusions += 1
            continue
        if not parts:
            unattributed += 1
        ph.counts[kind] = ph.counts.get(kind, 0) + 1
        args = line[m.end():].split(")", 1)[0]
        # operand shapes: printed inline by older XLA text, else resolved
        operands = _SHAPE_TOKEN_RE.findall(args) or [
            t for n in _OPERAND_RE.findall(args) for t in results.get(n, ())]
        ph.bytes_est += sum(_token_bytes(dt, dims)
                            for dt, dims in result + operands)
        if kind == "convert" and result and operands:
            pair = f"{operands[0][0]}->{result[0][0]}"
            ph.convert_pairs[pair] = ph.convert_pairs.get(pair, 0) + 1
    return CensusReport(
        label=label, world=world, backend=backend, phases=phases,
        total_instructions=total, unattributed_row_ops=unattributed,
        violations=[])


# -------------------------------------------------------- the entry points


def census_step_fn(step_fn, args: Sequence[Any], *,
                   world: int = 1,
                   label: str = "step",
                   contracts: Optional[Sequence[PassBudget]] = None
                   ) -> CensusReport:
    """Compile a jitted step abstractly and census its optimized HLO.

    ``args`` may be concrete arrays or ``jax.ShapeDtypeStruct`` pytrees —
    ``step_fn.lower(*args).compile()`` never executes anything (the
    :func:`~.memory.compiled_step_report` harness). Plain callables are
    wrapped in ``jax.jit`` first.
    """
    if not hasattr(step_fn, "lower"):
        step_fn = jax.jit(step_fn)
    txt = step_fn.lower(*args).compile().as_text()
    try:
        backend = jax.default_backend()
    except Exception:  # noqa: BLE001 - stamp is best-effort
        backend = None
    rep = census_of_text(txt, label=label, world=world, backend=backend)
    if rep.total_instructions == 0:
        # a compiled step always holds instructions: zero means THIS
        # backend's HLO text didn't match the parser, and every budget
        # downstream would pass vacuously — fail loudly instead
        raise CensusError(
            f"census of {label!r} parsed 0 instructions from a "
            f"{len(txt)}-byte compiled module (backend {backend}) — "
            "unrecognized HLO text format; the pass-budget gate cannot "
            "run on it")
    if contracts:
        rep.check(contracts)
    return rep


def census_train_step(de,
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
                      dense_params=None,
                      state=None,
                      contracts: Optional[Sequence[PassBudget]] = None,
                      label: str = "hybrid_train_step") -> CensusReport:
    """Build the hybrid train step exactly like
    :func:`~..parallel.trainer.make_hybrid_train_step` (the
    :func:`~.audit.audit_train_step` build, shared conventions: abstract
    state derived via ``eval_shape`` from ``dense_params`` when ``state``
    is omitted, metrics/guard/telemetry variants included) and census its
    optimized HLO against ``contracts``.

    ``contracts=None`` applies :func:`default_contracts` for the given
    ``emb_optimizer`` (today: the empty-dedup budget when it declares
    ``needs_dedup=False``); pass an explicit list — possibly empty — to
    override.
    """
    from .audit import build_abstract_step

    step, args, _, _, _, _ = build_abstract_step(
        de, loss_fn, dense_tx, emb_optimizer, cat_inputs, batch,
        mesh=mesh, lr_schedule=lr_schedule, with_metrics=with_metrics,
        nan_guard=nan_guard, telemetry=telemetry,
        dense_params=dense_params, state=state)

    if contracts is None:
        contracts = default_contracts(emb_optimizer)
    return census_step_fn(step, args, world=de.world_size, label=label,
                          contracts=contracts)
