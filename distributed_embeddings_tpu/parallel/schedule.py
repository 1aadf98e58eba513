"""The explicit step schedule: named phases, declared ordering, declared
overlap.

The hybrid step is a fixed chain of phases — id exchange, lookup, output
exchange, dense forward/backward, gradient exchange, sparse apply — that
used to exist only implicitly, as the order of statements inside one
2,200-line module. This module makes the schedule a first-class object:

* each phase has a **name** that doubles as its ``obs.scope`` label, so
  the same identifier threads from the Python orchestration through the
  jaxpr auditor's collective contract, the HLO census's pass budgets, and
  the schedule auditor's dependency DAG
  (:mod:`~..analysis.schedule_audit`);
* a :class:`StepSchedule` declares, per phase, what it must run
  **after** and what it claims to **overlap** with. The declaration is a
  CONTRACT, not a wish: ``tools/schedule_audit.py --strict`` checks every
  declared overlap against the dependency structure of the compiled
  program and fails when the overlap does not exist in what XLA emitted
  (a schedule that *says* "the id exchange hides under dense compute"
  while the program serializes them is exactly the silent perf lie the
  auditor exists to catch).

The executor modules (:mod:`.exchange`, :mod:`.lookup`, :mod:`.apply`)
take their scope names from the constants below; the orchestrator
(:meth:`~.dist_embedding.DistributedEmbedding.forward_with_residuals` +
:meth:`~.dist_embedding.DistributedEmbedding.sparse_apply_gradients`)
steps through :func:`default_schedule`'s phases in declaration order.
Today's default schedule is honest about being SERIALIZED — every
collective declares ``overlaps=()`` — which the schedule auditor's
baseline report documents as the measured starting line; a pipelined
step (ROADMAP item 2) will ship a schedule whose declared overlaps the
same auditor then has to certify.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple, Union

from ..utils import envvars

# ---------------------------------------------------------------- phase names
# These strings ARE the obs.scope labels of the compiled step (and hence
# the detpu/ phase paths in the optimized HLO). Globs (trailing ``*``)
# name phase FAMILIES that expand per width group at trace time.

#: dp→mp id all-to-all (block assembly + the collective)
PHASE_ID_EXCHANGE = "id_all_to_all"
#: per-(width, kind) gather+combine groups — ``lookup_w{w}_{kind}``
PHASE_LOOKUP = "lookup_*"
#: mp→dp activation all-to-all
PHASE_OUT_EXCHANGE = "out_all_to_all"
#: the dense model's forward + backward (trainer scope)
PHASE_DENSE = "dense_forward_backward"
#: reverse (cotangent) all-to-all
PHASE_GRAD_EXCHANGE = "grad_all_to_all"
#: per-width optimizer scatter streams — ``sparse_apply`` and
#: ``sparse_apply_w{k}``
PHASE_APPLY = "sparse_apply*"
#: streaming-vocab admission staging — the count-min fold + claim
#: resolution chain (``streaming_admit_w{w}``), consumed only at commit,
#: so it is DAG-independent of the out/grad exchanges (the measured
#: overlap candidate of docs/perf_tpu.md Round 13)
PHASE_STREAM_ADMIT = "streaming_admit_*"
#: streaming-vocab commit — post-apply slot-map select + claimed-row
#: scrub (``streaming_commit`` / ``streaming_commit_w{w}``)
PHASE_STREAM_COMMIT = "streaming_commit*"
#: per-microbatch slot-map SERVE remap of the pipelined streaming step
#: (``streaming_serve_w{w}_mb{k}``) — read-only against the carried
#: slot map, so each microbatch's lookup depends only on its own id
#: exchange, never on the admission staging
PHASE_STREAM_SERVE = "streaming_serve_*"

#: scope-name suffix of microbatch ``k``'s phase instances in a
#: pipelined step (``id_all_to_all_mb0``, ``lookup_w8_d_mb1``, ...)
MICROBATCH_TAG = "_mb{k}"


def microbatch_tag(k: int) -> str:
    """The scope suffix the executors append for microbatch ``k``."""
    return MICROBATCH_TAG.format(k=k)


def mb_phase(name: str, k: int) -> str:
    """Microbatch ``k``'s instance of a phase name. Glob families keep
    their trailing ``*`` AFTER the suffix (``lookup_*`` ->
    ``lookup_*_mb0``) so ``lookup_w8_d_mb0`` still matches."""
    tag = microbatch_tag(k)
    if name.endswith("*"):
        return name.rstrip("*") + "*" + tag
    return name + tag


class ScheduleError(ValueError):
    """An inconsistent :class:`StepSchedule` declaration."""


@dataclasses.dataclass(frozen=True)
class PhaseDecl:
    """One named phase of the step schedule.

    ``name`` is the ``obs.scope`` label (an ``fnmatch`` glob for phase
    families like ``lookup_*``). ``kind`` is ``"collective"`` (pays ICI
    bandwidth) or ``"compute"`` (pays HBM bandwidth). ``after`` lists the
    phases that must have produced this phase's inputs — the declared
    dependency order. ``overlaps`` lists the phases this one CLAIMS to
    run concurrently with; the schedule auditor verifies each claim
    against the compiled program's dependency DAG and fails a declared
    overlap the program serializes."""

    name: str
    kind: str = "compute"
    after: Tuple[str, ...] = ()
    overlaps: Tuple[str, ...] = ()

    def __post_init__(self) -> None:
        if self.kind not in ("collective", "compute"):
            raise ScheduleError(
                f"phase {self.name!r}: kind must be 'collective' | "
                f"'compute', got {self.kind!r}")


@dataclasses.dataclass(frozen=True)
class StepSchedule:
    """A named, ordered set of :class:`PhaseDecl`\\ s.

    Declaration order is execution order for the serialized portions of
    the step; ``validate()`` (run on construction) checks the references
    and rejects ordering cycles, self-overlap, and overlap claims that
    contradict the declared ``after`` chain (a phase cannot overlap a
    phase it depends on)."""

    name: str
    phases: Tuple[PhaseDecl, ...]
    #: microbatch count the trainer splits the step into (1 = the
    #: serialized, unpipelined program). Carried on the schedule so the
    #: one ``schedule=`` selection drives BOTH the declaration the
    #: auditor certifies and the program the trainer traces.
    microbatches: int = 1

    def __post_init__(self) -> None:
        if int(self.microbatches) < 1:
            raise ScheduleError(
                f"schedule {self.name!r}: microbatches must be >= 1, got "
                f"{self.microbatches}")
        self.validate()

    # -- introspection ----------------------------------------------------
    def by_name(self) -> Dict[str, PhaseDecl]:
        return {p.name: p for p in self.phases}

    def phase(self, name: str) -> PhaseDecl:
        try:
            return self.by_name()[name]
        except KeyError:
            raise ScheduleError(
                f"schedule {self.name!r} declares no phase {name!r} "
                f"(has: {[p.name for p in self.phases]})") from None

    def collectives(self) -> Tuple[PhaseDecl, ...]:
        return tuple(p for p in self.phases if p.kind == "collective")

    def declared_overlaps(self) -> Tuple[Tuple[str, str], ...]:
        """Every (phase, partner) overlap claim, in declaration order."""
        return tuple((p.name, q) for p in self.phases for q in p.overlaps)

    def depends_on(self, name: str, other: str) -> bool:
        """Whether phase ``name`` transitively runs after ``other``."""
        decls = self.by_name()
        seen = set()
        stack = [name]
        while stack:
            cur = stack.pop()
            if cur in seen or cur not in decls:
                continue
            seen.add(cur)
            for dep in decls[cur].after:
                if dep == other:
                    return True
                stack.append(dep)
        return False

    # -- validation -------------------------------------------------------
    def validate(self) -> "StepSchedule":
        names = [p.name for p in self.phases]
        if len(set(names)) != len(names):
            dup = sorted({n for n in names if names.count(n) > 1})
            raise ScheduleError(
                f"schedule {self.name!r}: duplicate phase name(s) {dup}")
        known = set(names)
        for p in self.phases:
            for ref in p.after + p.overlaps:
                if ref not in known:
                    raise ScheduleError(
                        f"schedule {self.name!r}: phase {p.name!r} "
                        f"references undeclared phase {ref!r}")
            if p.name in p.overlaps:
                raise ScheduleError(
                    f"schedule {self.name!r}: phase {p.name!r} cannot "
                    "overlap itself")
        # cycle check over the `after` relation (iterative DFS)
        decls = self.by_name()
        color: Dict[str, int] = {}  # 0 in-stack, 1 done

        def visit(root: str) -> None:
            stack = [(root, iter(decls[root].after))]
            color[root] = 0
            while stack:
                node, it = stack[-1]
                dep = next(it, None)
                if dep is None:
                    color[node] = 1
                    stack.pop()
                    continue
                c = color.get(dep)
                if c == 0:
                    chain = [n for n, _ in stack] + [dep]
                    raise ScheduleError(
                        f"schedule {self.name!r}: ordering cycle "
                        f"{' -> '.join(chain)}")
                if c is None:
                    color[dep] = 0
                    stack.append((dep, iter(decls[dep].after)))

        for n in names:
            if n not in color:
                visit(n)
        # an overlap claim against a phase this phase (transitively)
        # depends on is self-contradictory: the data dependency forces
        # serialization regardless of what the compiler does
        for p in self.phases:
            for q in p.overlaps:
                if self.depends_on(p.name, q) or self.depends_on(q, p.name):
                    raise ScheduleError(
                        f"schedule {self.name!r}: phase {p.name!r} "
                        f"declares overlap with {q!r} but the `after` "
                        "chain orders them — a data dependency cannot "
                        "overlap")
        return self


def default_schedule() -> StepSchedule:
    """The serialized baseline schedule of today's hybrid step.

    Honest declaration of what the unpipelined step does: the three
    all-to-alls sit strictly between their producers and consumers, and
    no phase claims overlap. This is the schedule the auditor's baseline
    report certifies (all three collectives serialized on the critical
    path) and the one every A/B-identity guarantee is pinned against."""
    return StepSchedule(
        name="serialized-v1",
        phases=(
            PhaseDecl(PHASE_ID_EXCHANGE, kind="collective"),
            PhaseDecl(PHASE_LOOKUP, kind="compute",
                      after=(PHASE_ID_EXCHANGE,)),
            PhaseDecl(PHASE_OUT_EXCHANGE, kind="collective",
                      after=(PHASE_LOOKUP,)),
            PhaseDecl(PHASE_DENSE, kind="compute",
                      after=(PHASE_OUT_EXCHANGE,)),
            PhaseDecl(PHASE_GRAD_EXCHANGE, kind="collective",
                      after=(PHASE_DENSE,)),
            PhaseDecl(PHASE_APPLY, kind="compute",
                      after=(PHASE_GRAD_EXCHANGE,)),
        ))


def streaming_schedule() -> StepSchedule:
    """The serialized streaming-vocab schedule, with the one overlap the
    compiled program ALREADY has declared: the admission-staging chain
    (count-min fold + claim resolution, ``streaming_admit_w*``) branches
    off the received ids and is consumed only at commit, so it is
    DAG-independent of the out/grad exchanges — the schedule auditor
    classified it overlappable in PR 12 (fraction 0.225) and the
    measured phase profile confirmed it on the clock in PR 13 (0.036
    measured serialized). Declaring it here is what lets
    ``make schedule-audit`` certify the overlap against the compiled
    DAG (its ``--strict`` run builds the streaming case), so a refactor
    that re-serializes the staging chain fails loudly.

    The lookup's real dependency on the SERVE half of the admit phase
    (slot-map reads feeding the remapped ids) is deliberately not
    declared: the auditor's overlap check excludes exactly those
    ancestor-cone nodes from the independent sum, so the declaration is
    verified against the genuinely independent staging nodes only."""
    return StepSchedule(
        name="streaming-serialized-v1",
        phases=(
            PhaseDecl(PHASE_ID_EXCHANGE, kind="collective"),
            PhaseDecl(PHASE_STREAM_ADMIT, kind="compute",
                      after=(PHASE_ID_EXCHANGE,)),
            PhaseDecl(PHASE_LOOKUP, kind="compute",
                      after=(PHASE_ID_EXCHANGE,)),
            PhaseDecl(PHASE_OUT_EXCHANGE, kind="collective",
                      after=(PHASE_LOOKUP,),
                      overlaps=(PHASE_STREAM_ADMIT,)),
            PhaseDecl(PHASE_DENSE, kind="compute",
                      after=(PHASE_OUT_EXCHANGE,)),
            PhaseDecl(PHASE_GRAD_EXCHANGE, kind="collective",
                      after=(PHASE_DENSE,),
                      overlaps=(PHASE_STREAM_ADMIT,)),
            PhaseDecl(PHASE_APPLY, kind="compute",
                      after=(PHASE_GRAD_EXCHANGE,)),
            PhaseDecl(PHASE_STREAM_COMMIT, kind="compute",
                      after=(PHASE_APPLY, PHASE_STREAM_ADMIT)),
        ))


def resolve_microbatches(k: Optional[int] = None) -> int:
    """The microbatch count: an explicit ``k`` wins, else
    ``DETPU_MICROBATCH`` (declared default 2 — only pipelined-schedule
    opt-ins resolve through here, and asking for a pipeline must build
    one; ``DETPU_MICROBATCH=1`` or an explicit ``k=1`` selects the
    serialized degenerate)."""
    if k is None:
        k = envvars.get_int("DETPU_MICROBATCH")
    k = int(k)
    if k < 1:
        raise ScheduleError(f"microbatches must be >= 1, got {k}")
    return k


def pipelined_schedule(microbatches: Optional[int] = None,
                       streaming: bool = False) -> StepSchedule:
    """The K-microbatch software-pipelined schedule (ROADMAP item 2).

    The global batch splits into K microbatches INSIDE the jitted step;
    each runs its own id-exchange → lookup → out-exchange → dense
    fwd/bwd chain (phase instances suffixed ``_mb{k}``), gradients
    accumulate across microbatches, and ONE sparse apply runs at the
    end — so the applied update is numerically equivalent to the
    serialized step while the K chains share no data dependencies until
    the accumulation point. That independence is what the declared
    overlaps claim and what the schedule auditor certifies against the
    compiled DAG:

    * microbatch ``k``'s id and out exchanges overlap microbatch
      ``k-1``'s dense forward/backward (ship the next microbatch's ids
      while the current one computes);
    * microbatch ``k``'s grad exchange overlaps microbatch ``k+1``'s
      dense forward/backward (drain cotangents under later compute);
    * microbatch 0's collectives overlap microbatch 1's lookup chain
      (the pipeline has no cold edge at K >= 2).

    ``microbatches=None`` resolves K from ``DETPU_MICROBATCH``; K == 1
    returns the serialized baseline schedule unchanged (the trainer
    then traces the bitwise-identical serialized program — the K=1
    identity contract). ``streaming=True`` adds the streaming-vocab
    phases: per-microbatch read-only slot-map serves
    (``streaming_serve_*_mb{k}``), ONE admission-staging pass over the
    concatenated id streams (bitwise the serialized staging decision),
    and the post-apply commit — with the out/grad exchanges also
    declaring the staging overlap the serialized streaming schedule
    already certifies."""
    K = resolve_microbatches(microbatches)
    if K == 1:
        return streaming_schedule() if streaming else default_schedule()

    def dense(k: int) -> str:
        return mb_phase(PHASE_DENSE, k)

    def chain(j: int) -> Tuple[str, str]:
        """Microbatch ``j``'s hideable compute: its lookup gathers and
        its dense forward/backward."""
        return (mb_phase(PHASE_LOOKUP, j), dense(j))

    phases = []
    for k in range(K):
        id_k = mb_phase(PHASE_ID_EXCHANGE, k)
        lookup_k = mb_phase(PHASE_LOOKUP, k)
        out_k = mb_phase(PHASE_OUT_EXCHANGE, k)
        grad_k = mb_phase(PHASE_GRAD_EXCHANGE, k)
        # the partners a collective hides under: every OTHER
        # microbatch's lookup + dense chain (none of it shares a data
        # dependency with this microbatch's exchanges before the
        # accumulation point — the whole design of the pipeline)
        others = tuple(p for j in range(K) if j != k for p in chain(j))
        fwd_partner = others
        bwd_partner = others
        admit = (PHASE_STREAM_ADMIT,) if streaming else ()
        lookup_after = (id_k,)
        phases.append(PhaseDecl(id_k, kind="collective",
                                overlaps=fwd_partner))
        if streaming:
            serve_k = mb_phase(PHASE_STREAM_SERVE, k)
            phases.append(PhaseDecl(serve_k, kind="compute",
                                    after=(id_k,)))
            lookup_after = (id_k, serve_k)
        phases.append(PhaseDecl(lookup_k, kind="compute",
                                after=lookup_after))
        phases.append(PhaseDecl(out_k, kind="collective",
                                after=(lookup_k,),
                                overlaps=fwd_partner + admit))
        phases.append(PhaseDecl(dense(k), kind="compute",
                                after=(out_k,)))
        phases.append(PhaseDecl(grad_k, kind="collective",
                                after=(dense(k),),
                                overlaps=bwd_partner + admit))
    if streaming:
        phases.append(PhaseDecl(
            PHASE_STREAM_ADMIT, kind="compute",
            after=tuple(mb_phase(PHASE_ID_EXCHANGE, k)
                        for k in range(K))))
    phases.append(PhaseDecl(
        PHASE_APPLY, kind="compute",
        after=tuple(mb_phase(PHASE_GRAD_EXCHANGE, k) for k in range(K))))
    if streaming:
        phases.append(PhaseDecl(
            PHASE_STREAM_COMMIT, kind="compute",
            after=(PHASE_APPLY, PHASE_STREAM_ADMIT)))
    return StepSchedule(
        name=f"pipelined-k{K}" + ("-streaming" if streaming else ""),
        phases=tuple(phases), microbatches=K)


def without_streaming(schedule: StepSchedule) -> StepSchedule:
    """The non-streaming twin of a schedule that declares streaming
    phases — what a program built WITHOUT ``dynamic=`` on a
    streaming-capable layer honestly executes (its compiled DAG has no
    ``streaming_admit_*`` nodes, so the staging overlap declaration
    must not be checked against it). Schedules without streaming
    declarations pass through unchanged."""
    streamy = (PHASE_STREAM_ADMIT, PHASE_STREAM_COMMIT,
               PHASE_STREAM_SERVE)
    if not any(p.name in streamy or p.name.startswith("streaming_serve")
               for p in schedule.phases):
        return schedule
    if schedule.microbatches > 1:
        return pipelined_schedule(schedule.microbatches, streaming=False)
    return default_schedule()


def resolve_schedule(spec: Union[None, str, StepSchedule] = None,
                     streaming: bool = False) -> StepSchedule:
    """Normalize :class:`~.dist_embedding.DistributedEmbedding`'s
    ``schedule=`` argument: ``None``/``"serialized"`` is the honest
    serialized baseline (the streaming declaration included when the
    layer has dynamic tables), ``"pipelined"`` builds
    :func:`pipelined_schedule` with ``DETPU_MICROBATCH``'s K, and a
    :class:`StepSchedule` passes through as-is."""
    if spec is None or spec == "serialized":
        return streaming_schedule() if streaming else default_schedule()
    if spec == "pipelined":
        return pipelined_schedule(streaming=streaming)
    if isinstance(spec, StepSchedule):
        return spec
    raise ScheduleError(
        f"schedule= takes None | 'serialized' | 'pipelined' | a "
        f"StepSchedule, got {spec!r}")
