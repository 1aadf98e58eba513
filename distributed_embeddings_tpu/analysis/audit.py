"""Jaxpr-level SPMD invariant auditor for the hybrid train step.

The whole value proposition of hybrid model/data parallelism is a tight
communication contract: per train step, the distributed embedding runs
exactly ONE id all-to-all and ONE activation all-to-all forward and ONE
cotangent all-to-all backward (plus the loss/dense-gradient pmeans the
data-parallel side owes). Nothing used to verify that — a refactor that
sneaks an extra ``all_gather`` into the sparse path, leaks a float64, or
routes a host callback through the jitted step only showed up as a silent
throughput drop in a later bench round.

:func:`audit_train_step` builds the step exactly like
:func:`~..parallel.trainer.make_hybrid_train_step` does, traces it
abstractly (``jax.make_jaxpr`` + ``jit(...).lower()`` — shapes and dtypes
only, nothing executes on a backend), and returns a structured
:class:`AuditReport`:

* **collective census** — every ``all_to_all`` / ``psum`` / ``all_gather``
  /``reduce_scatter`` / ``ppermute`` in the step, attributed to the
  ``obs.scope`` phase it was traced under, with per-device payload and
  estimated off-chip bytes; checked against the expected contract for the
  layer's configuration (:func:`expected_collectives`).
* **dtype audit** — any float64/complex128 value anywhere in the step is a
  violation (an x64 leak doubles exchange bytes and HBM traffic); the
  embedding-slab dtype must be preserved input-state -> output-state.
* **host-interop audit** — ``pure_callback`` / ``io_callback`` /
  ``debug_callback`` / infeed/outfeed inside the step are violations:
  every one is a device->host sync in the hot path.
* **donation audit** — the step donates its whole state
  (``donate_argnums=(0,)``); the lowered module must carry a donation
  marker (``jax.buffer_donor`` / ``tf.aliasing_output``) for every state
  leaf, or slab-sized buffers silently double in HBM.
* **recompile-hazard scan** — weak-typed step *arguments* (a Python
  scalar rode into the jitted signature; a weak->strong flip retraces) and
  a count of weak-typed captured literals (closure scalars baked into the
  program — rebuild the step per value and every build recompiles).

The auditor never talks to an accelerator: run it under
``JAX_PLATFORMS=cpu`` with ``--xla_force_host_platform_device_count=N``
for an N-position mesh (``tools/audit_step.py`` does exactly that).
"""

from __future__ import annotations

import dataclasses
import json
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple

import jax
import jax.extend.core as jcore
import numpy as np

from ..parallel import trainer as trainer_mod
from ..parallel.dist_embedding import DistributedEmbedding, MpInputs

# primitive-name classes: under shard_map's VMA typing a psum / all_gather
# whose result is device-invariant traces as its *_invariant twin
PSUM_PRIMS = frozenset({"psum", "psum_invariant"})
ALL_TO_ALL_PRIMS = frozenset({"all_to_all"})
ALL_GATHER_PRIMS = frozenset({"all_gather", "all_gather_invariant"})
REDUCE_SCATTER_PRIMS = frozenset({"reduce_scatter"})
OTHER_COLLECTIVE_PRIMS = frozenset({"ppermute", "pmax", "pmin", "pgather"})
COLLECTIVE_PRIMS = (PSUM_PRIMS | ALL_TO_ALL_PRIMS | ALL_GATHER_PRIMS
                    | REDUCE_SCATTER_PRIMS | OTHER_COLLECTIVE_PRIMS)

#: primitives that cross the host<->device boundary inside a jitted step
HOST_INTEROP_PRIMS = frozenset({
    "pure_callback", "io_callback", "debug_callback", "callback",
    "outside_call", "infeed", "outfeed", "host_callback_call",
})

#: obs.scope phase -> contract role of an all_to_all traced under it
_A2A_ROLES = (
    ("id_all_to_all", "id_exchange_fwd"),
    ("out_all_to_all", "out_exchange_fwd"),
    ("grad_all_to_all", "grad_exchange_bwd"),
)

_FORBIDDEN_DTYPES = ("float64", "complex128")


class AuditError(RuntimeError):
    """Raised by :meth:`AuditReport.raise_on_violations` in strict use."""


@dataclasses.dataclass
class CollectiveRecord:
    """One collective op found in the traced step."""
    kind: str            # psum | all_to_all | all_gather | reduce_scatter...
    primitive: str       # exact jaxpr primitive name
    role: str            # contract role derived from the obs.scope phase
    scope: str           # full named_scope stack at the trace site
    shape: Tuple[int, ...]
    dtype: str
    payload_bytes: int   # per-device operand size
    offchip_bytes: int   # estimated bytes leaving the chip (all_to_all)

    def to_json(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)


@dataclasses.dataclass
class AuditReport:
    """Structured result of one step audit. ``violations`` is empty iff
    every invariant holds; everything else is the evidence."""
    world: int
    dp_input: bool
    label: str
    collectives: List[CollectiveRecord]
    collective_counts: Dict[str, int]
    expected: Dict[str, Any]
    dtype_leaks: List[str]
    emb_dtype_changes: List[str]
    host_interop: List[str]
    donation: Dict[str, Any]
    recompile_hazards: List[str]
    weak_literals: int
    primitive_counts: Dict[str, int]
    violations: List[str]

    @property
    def ok(self) -> bool:
        return not self.violations

    def a2a_census(self) -> Dict[str, int]:
        """all_to_all count per contract role (the 2-fwd + 1-bwd check)."""
        out: Dict[str, int] = {}
        for c in self.collectives:
            if c.kind == "all_to_all":
                out[c.role] = out.get(c.role, 0) + 1
        return out

    def raise_on_violations(self) -> "AuditReport":
        if self.violations:
            raise AuditError(
                "step audit failed:\n  - " + "\n  - ".join(self.violations))
        return self

    def to_json(self) -> Dict[str, Any]:
        d = dataclasses.asdict(self)
        d["ok"] = self.ok
        d["a2a_census"] = self.a2a_census()
        return d

    def dumps(self, **kw: Any) -> str:
        return json.dumps(self.to_json(), **kw)


# ------------------------------------------------------------ jaxpr walking


def _sub_jaxprs(value: Any) -> Iterator[jcore.Jaxpr]:
    """Every Jaxpr nested inside an eqn-param value (pjit/shard_map/scan/
    cond branches/custom_*_call all stash theirs differently)."""
    if isinstance(value, jcore.Jaxpr):
        yield value
    elif isinstance(value, jcore.ClosedJaxpr):
        yield value.jaxpr
    elif isinstance(value, (list, tuple)):
        for v in value:
            yield from _sub_jaxprs(v)


def iter_eqns(jaxpr: jcore.Jaxpr) -> Iterator[jcore.JaxprEqn]:
    """Depth-first walk over every equation reachable from ``jaxpr``,
    descending through call/ control-flow primitives."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in _sub_jaxprs(v):
                yield from iter_eqns(sub)


def _scope_of(eqn: jcore.JaxprEqn) -> str:
    try:
        return str(eqn.source_info.name_stack)
    except Exception:  # noqa: BLE001 - name stacks are metadata, not load-bearing
        return ""


def _aval_of(var: Any) -> Optional[jax.core.AbstractValue]:
    return getattr(var, "aval", None)


def _role_of_a2a(scope: str) -> str:
    for marker, role in _A2A_ROLES:
        if marker in scope:
            return role
    return "unscoped"


def _kind_of(prim: str) -> Optional[str]:
    if prim in ALL_TO_ALL_PRIMS:
        return "all_to_all"
    if prim in PSUM_PRIMS:
        return "psum"
    if prim in ALL_GATHER_PRIMS:
        return "all_gather"
    if prim in REDUCE_SCATTER_PRIMS:
        return "reduce_scatter"
    if prim in OTHER_COLLECTIVE_PRIMS:
        return prim
    return None


# --------------------------------------------------------------- the audits


def _collect(jaxpr: jcore.Jaxpr, world: int):
    """One walk, every census: collectives, dtype leaks, host interop,
    weak literals, primitive counts."""
    collectives: List[CollectiveRecord] = []
    dtype_leaks: List[str] = []
    host_interop: List[str] = []
    weak_literals = 0
    prim_counts: Dict[str, int] = {}
    seen_literal_ids = set()

    def leak_check(aval, where: str) -> None:
        name = getattr(getattr(aval, "dtype", None), "name", None)
        if name in _FORBIDDEN_DTYPES and len(dtype_leaks) < 32:
            shape = tuple(getattr(aval, "shape", ()))
            dtype_leaks.append(f"{name}{list(shape)} at {where}")

    for eqn in iter_eqns(jaxpr):
        prim = eqn.primitive.name
        prim_counts[prim] = prim_counts.get(prim, 0) + 1
        scope = _scope_of(eqn)
        where = f"{prim} [{scope}]" if scope else prim
        for v in eqn.outvars:
            aval = _aval_of(v)
            if aval is not None:
                leak_check(aval, where)
        for v in eqn.invars:
            if isinstance(v, jcore.Literal):
                aval = _aval_of(v)
                if (aval is not None and getattr(aval, "weak_type", False)
                        and id(v) not in seen_literal_ids):
                    seen_literal_ids.add(id(v))
                    weak_literals += 1
                # literal avals are also dtype-checked: a captured numpy
                # f64 constant is a leak even if every op output is f32
                if aval is not None:
                    leak_check(aval, where)
        if prim in HOST_INTEROP_PRIMS:
            host_interop.append(where)
        kind = _kind_of(prim)
        if kind is not None:
            aval = _aval_of(eqn.invars[0]) if eqn.invars else None
            shape = tuple(int(d) for d in getattr(aval, "shape", ()))
            dtype = getattr(getattr(aval, "dtype", None), "name", "?")
            payload = int(np.prod(shape, dtype=np.int64)
                          * np.dtype(dtype).itemsize) if shape and \
                dtype != "?" else 0
            offchip = (payload * (world - 1) // world
                       if kind == "all_to_all" and world > 1 else 0)
            collectives.append(CollectiveRecord(
                kind=kind, primitive=prim,
                role=(_role_of_a2a(scope) if kind == "all_to_all"
                      else ("nanguard" if "nanguard" in scope
                            else "unscoped")),
                scope=scope, shape=shape, dtype=dtype,
                payload_bytes=payload, offchip_bytes=offchip))
    return collectives, dtype_leaks, host_interop, weak_literals, prim_counts


def expected_collectives(de: DistributedEmbedding, *,
                         nan_guard: bool,
                         n_dense_leaves: int,
                         microbatches: Optional[int] = None
                         ) -> Dict[str, Any]:
    """The communication contract for one hybrid train step on ``de``.

    * all_to_all — the paper's exchange structure: dp input runs the id
      exchange + output exchange forward and the cotangent exchange
      backward (2 fwd + 1 bwd); mp input (``dp_input=False``) skips the id
      exchange (1 fwd + 1 bwd); a single worker runs none. A PIPELINED
      schedule (``de.schedule.microbatches == K > 1``; override with
      ``microbatches=``) runs each role once per microbatch — the
      ``_mb{k}``-scoped instances still carry the role marker in their
      scope, so the census buckets them correctly — and exactly K of
      each is the contract: K+1 means a microbatch leaked an extra
      exchange, K-1 means one got fused away with its batch semantics.
    * psum — what the data-parallel side owes: one loss ``pmean``, one
      ``pmean`` per dense-gradient leaf, plus the non-finite guard's
      verdict ``pmean`` when the guard is built in. K-INVARIANT: the
      pipelined step accumulates locally and resolves once — a psum
      count that grows with K is the per-microbatch-pmean regression
      this contract exists to catch.
    * all_gather / reduce_scatter — never: the design's point is that NO
      slab-sized collective exists (an all_gather of the tables is the
      failure mode the paper's layout avoids).
    """
    if de.world_size <= 1:
        return {"all_to_all_roles": {}, "all_to_all": 0, "psum": 0,
                "all_gather": 0, "reduce_scatter": 0}
    if microbatches is None:
        microbatches = int(getattr(de.schedule, "microbatches", 1) or 1)
    k = max(int(microbatches), 1)
    roles = (["out_exchange_fwd", "grad_exchange_bwd"]
             if not de.dp_input else
             ["id_exchange_fwd", "out_exchange_fwd", "grad_exchange_bwd"])
    return {
        "all_to_all_roles": {r: k for r in roles},
        "all_to_all": len(roles) * k,
        "psum": 1 + n_dense_leaves + (1 if nan_guard else 0),
        "all_gather": 0,
        "reduce_scatter": 0,
    }


def expected_eval_collectives(de: DistributedEmbedding) -> Dict[str, Any]:
    """The communication contract for one no-grad FORWARD on ``de`` —
    the serving runtime's compiled program (:mod:`~..parallel.serving`)
    and :func:`~..parallel.trainer.make_hybrid_eval_step`'s body.

    Half the train contract: the dp-input forward runs the id exchange
    and the output exchange (1 + 1), mp input only the output exchange,
    a single worker none — and NOTHING else: no cotangent exchange (no
    grad), no psum (no loss pmean, no dense-gradient resolution), and
    the same never-any-all_gather rule as training. A serve program
    that trips this census is quietly paying training-shaped
    communication per request.
    """
    if de.world_size <= 1:
        return {"all_to_all_roles": {}, "all_to_all": 0, "psum": 0,
                "all_gather": 0, "reduce_scatter": 0}
    roles = (["out_exchange_fwd"] if not de.dp_input
             else ["id_exchange_fwd", "out_exchange_fwd"])
    return {
        "all_to_all_roles": {r: 1 for r in roles},
        "all_to_all": len(roles),
        "psum": 0,
        "all_gather": 0,
        "reduce_scatter": 0,
    }


def _donation_audit(lowered_text: Optional[str],
                    expected_leaves: int) -> Dict[str, Any]:
    """Count donation markers in the lowered StableHLO. jax marks a donated
    parameter either with an established input/output alias
    (``tf.aliasing_output``) or a ``jax.buffer_donor`` attribute (alias
    left to the compiler); a state leaf with neither was silently dropped."""
    if lowered_text is None:
        return {"checked": False, "expected": expected_leaves,
                "donated": None, "dropped": None}
    aliased = lowered_text.count("tf.aliasing_output")
    donor = lowered_text.count("jax.buffer_donor")
    donated = aliased + donor
    return {"checked": True, "expected": expected_leaves,
            "donated": donated, "aliased": aliased, "donor_only": donor,
            "dropped": max(0, expected_leaves - donated)}


def _weak_arg_hazards(args) -> List[str]:
    """Weak-typed leaves among the step arguments: each is a Python scalar
    riding the jitted signature — a weak->strong flip (or an int->float
    drift in the calling code) retraces the whole step."""
    hazards = []
    flat, _ = jax.tree_util.tree_flatten(args)
    for i, leaf in enumerate(flat):
        weak = getattr(leaf, "weak_type", None)
        if weak is None:
            aval = getattr(leaf, "aval", None)
            weak = getattr(aval, "weak_type", False)
        if weak or isinstance(leaf, (int, float)) and not isinstance(
                leaf, bool) and not hasattr(leaf, "dtype"):
            hazards.append(
                f"arg leaf #{i}: weak-typed scalar "
                f"({type(leaf).__name__}) in the jitted signature — pass a "
                "committed jnp array instead")
    return hazards


def audit_step_fn(step_fn, args: Sequence[Any], *,
                  world: int = 1,
                  dp_input: bool = True,
                  expected: Optional[Dict[str, Any]] = None,
                  expected_donated: Optional[int] = None,
                  check_donation: bool = True,
                  label: str = "step") -> AuditReport:
    """Audit an arbitrary (jitted or plain) step callable against an
    expected-collectives contract.

    Abstract only: ``jax.make_jaxpr`` traces the function (nothing runs on
    a backend) and, when ``check_donation`` and ``step_fn`` is a jit
    wrapper, ``step_fn.lower(*args).as_text()`` supplies the donation
    attributes. ``args`` may be concrete arrays or
    ``jax.ShapeDtypeStruct`` pytrees.
    """
    report, _ = _audit_step_fn(
        step_fn, args, world=world, dp_input=dp_input, expected=expected,
        expected_donated=expected_donated, check_donation=check_donation,
        label=label)
    return report


def _audit_step_fn(step_fn, args: Sequence[Any], *,
                   world: int = 1,
                   dp_input: bool = True,
                   expected: Optional[Dict[str, Any]] = None,
                   expected_donated: Optional[int] = None,
                   check_donation: bool = True,
                   label: str = "step"):
    """:func:`audit_step_fn` plus the traced output shape tree (the
    train-step entry point compares state dtypes through it)."""
    jaxpr, out_shape = jax.make_jaxpr(step_fn, return_shape=True)(*args)
    (collectives, dtype_leaks, host_interop, weak_literals,
     prim_counts) = _collect(jaxpr.jaxpr, world)

    counts: Dict[str, int] = {}
    for c in collectives:
        counts[c.kind] = counts.get(c.kind, 0) + 1

    lowered_text = None
    if check_donation and hasattr(step_fn, "lower"):
        lowered_text = step_fn.lower(*args).as_text()
    donation = _donation_audit(
        lowered_text,
        expected_donated if expected_donated is not None else 0)

    hazards = _weak_arg_hazards(args)

    violations: List[str] = []
    if expected is not None:
        exp_roles = expected.get("all_to_all_roles", {})
        census: Dict[str, int] = {}
        for c in collectives:
            if c.kind == "all_to_all":
                census[c.role] = census.get(c.role, 0) + 1
        for role, n in exp_roles.items():
            got = census.get(role, 0)
            if got != n:
                violations.append(
                    f"all_to_all census: expected {n} x {role}, found "
                    f"{got} — the exchange contract is broken")
        for role, got in census.items():
            if role not in exp_roles:
                violations.append(
                    f"all_to_all census: unexpected all_to_all in role "
                    f"{role!r} ({got}x) — every exchange must run under "
                    "a known obs.scope phase")
        for kind in ("psum", "all_gather", "reduce_scatter"):
            exp_n = expected.get(kind)
            if exp_n is None:
                continue
            got = counts.get(kind, 0)
            if got != exp_n:
                detail = "; ".join(
                    f"{c.primitive}@{c.scope or 'unscoped'}"
                    for c in collectives if c.kind == kind) or "none"
                violations.append(
                    f"{kind} census: expected {exp_n}, found {got} "
                    f"({detail})")
        for kind in counts:
            if kind not in ("psum", "all_to_all", "all_gather",
                            "reduce_scatter") and kind not in expected:
                violations.append(
                    f"unexpected collective {kind} "
                    f"({counts[kind]}x) in the step")
    if dtype_leaks:
        violations.append(
            "f64/x64 leak: " + "; ".join(dtype_leaks[:8])
            + (" ..." if len(dtype_leaks) > 8 else ""))
    if host_interop:
        violations.append(
            "host interop inside the jitted step: "
            + "; ".join(host_interop[:8]))
    if donation["checked"] and donation["expected"] and donation["dropped"]:
        violations.append(
            f"donation audit: {donation['dropped']} of "
            f"{donation['expected']} state leaves carry no donation marker "
            "— those buffers silently double in HBM")
    violations.extend(hazards)

    return AuditReport(
        world=world, dp_input=dp_input, label=label,
        collectives=collectives, collective_counts=counts,
        expected=expected or {}, dtype_leaks=dtype_leaks,
        emb_dtype_changes=[], host_interop=host_interop,
        donation=donation, recompile_hazards=hazards,
        weak_literals=weak_literals, primitive_counts=prim_counts,
        violations=violations), out_shape


def build_abstract_step(de: DistributedEmbedding,
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
                        state=None):
    """Build the hybrid train step EXACTLY like
    :func:`~..parallel.trainer.make_hybrid_train_step` plus the abstract
    argument tuple to trace/compile it with — nothing materializes.

    The single build both static gates share: :func:`audit_train_step`
    (jaxpr/collective contract) and
    :func:`~.hlo_census.census_train_step` (optimized-HLO pass budget)
    audit the step this helper returns, so the two cannot drift into
    auditing different programs while each claims to audit "the" hybrid
    step. ``with_metrics``/``nan_guard`` default from the env (the step
    builder's convention); ``state`` is derived via ``eval_shape`` from
    ``dense_params`` when omitted; a telemetry config appends the
    abstract carried state as the fourth argument, and a streaming
    config (``dynamic=``, the step builder's argument) the abstract
    slot-map/sketch state after it — the aux order of
    :data:`~..parallel.trainer.AUX_ARG_REGISTRY`.

    Returns:
      ``(step, args, state, tel_cfg, with_metrics, nan_guard)``.
    """
    from ..utils import obs
    from ..parallel import streaming as streaming_mod
    from . import telemetry as tel

    if with_metrics is None:
        with_metrics = obs.metrics_enabled()
    if nan_guard is None:
        nan_guard = obs.nanguard_enabled()
    tel_cfg = tel.resolve_config(telemetry)
    dyn_cfg = streaming_mod.resolve_config(dynamic)

    if state is None:
        if dense_params is None:
            raise ValueError(
                "building an abstract hybrid step needs dense_params (to "
                "derive an abstract state) or an explicit state=")
        state = jax.eval_shape(
            lambda k, dp: trainer_mod.init_hybrid_state(
                de, emb_optimizer, dp, dense_tx, k),
            jax.random.key(0), dense_params)

    step = trainer_mod.make_hybrid_train_step(
        de, loss_fn, dense_tx, emb_optimizer, mesh=mesh,
        lr_schedule=lr_schedule, with_metrics=with_metrics,
        nan_guard=nan_guard, telemetry=tel_cfg if tel_cfg else False,
        dynamic=dyn_cfg if dyn_cfg else False)

    args: Tuple[Any, ...] = (state, cat_inputs, batch)
    if tel_cfg is not None:
        args = args + (jax.eval_shape(
            lambda: tel.init_telemetry(de, tel_cfg)),)
    if dyn_cfg is not None:
        args = args + (jax.eval_shape(
            lambda: streaming_mod.init_streaming(de, dyn_cfg)),)
    return step, args, state, tel_cfg, with_metrics, nan_guard


def audit_train_step(de: DistributedEmbedding,
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
                     expected: Optional[Dict[str, Any]] = None,
                     label: str = "hybrid_train_step") -> AuditReport:
    """Build the hybrid train step exactly like
    :func:`~..parallel.trainer.make_hybrid_train_step` and audit it.

    ``telemetry`` follows the step builder's contract (explicit opt-in:
    ``True``/config = on): the telemetry-instrumented variant is audited
    with an abstract carried state as the fourth argument, and the SAME
    communication contract — access telemetry is rank-local by design
    (sketch scatter-adds + top-k merges, no collectives, no host
    interop), so a telemetry build that changes the census is a bug this
    audit catches. The donation audit grows by the telemetry leaves
    (the carried state is donated like the train state).

    Args mirror the step builder; additionally:

    Args:
      cat_inputs: the categorical inputs the step would receive — concrete
        arrays, ``jax.ShapeDtypeStruct`` leaves, ``Ragged``/:class:`MpInputs`
        of either. Only shapes/dtypes matter.
      batch: the loss batch pytree (same abstract-ok rule).
      dense_params: dense parameter pytree (or abstract shapes), used to
        derive the training state when ``state`` is not given.
      state: optional :class:`~..parallel.trainer.HybridTrainState` (or an
        abstract eval_shape of one). Built via
        ``jax.eval_shape(init_hybrid_state, ...)`` from ``dense_params``
        when omitted — nothing is materialized either way.
      expected: override for :func:`expected_collectives` (tests seed
        deliberately-wrong expectations through this).

    Returns:
      :class:`AuditReport`; call :meth:`AuditReport.raise_on_violations`
      for strict use.
    """
    step, args, state, tel_cfg, with_metrics, nan_guard = \
        build_abstract_step(
            de, loss_fn, dense_tx, emb_optimizer, cat_inputs, batch,
            mesh=mesh, lr_schedule=lr_schedule, with_metrics=with_metrics,
            nan_guard=nan_guard, telemetry=telemetry,
            dense_params=dense_params, state=state)

    if expected is None:
        expected = expected_collectives(
            de, nan_guard=nan_guard,
            n_dense_leaves=len(jax.tree_util.tree_leaves(
                state.dense_params)))

    donated = sum(len(jax.tree_util.tree_leaves(a))
                  for a in (state,) + args[3:])  # + the telemetry carry

    report, out_shape = _audit_step_fn(
        step, args,
        world=de.world_size, dp_input=de.dp_input, expected=expected,
        expected_donated=donated,
        label=label)

    # embedding-table dtype must be preserved end-to-end: state out is
    # (loss, new_state[, metrics]) — compare slab dtypes leaf-wise
    new_state = out_shape[1]
    in_emb = jax.tree_util.tree_leaves_with_path(state.emb_params)
    out_emb = jax.tree_util.tree_leaves_with_path(new_state.emb_params)
    changes = []
    for (pi, vi), (_, vo) in zip(in_emb, out_emb):
        di = getattr(vi, "dtype", None)
        do = getattr(vo, "dtype", None)
        if di is not None and do is not None and di != do:
            changes.append(
                f"emb_params{jax.tree_util.keystr(pi)}: {di} -> {do}")
    if changes:
        report.emb_dtype_changes = changes
        report.violations.append(
            "embedding-table dtype not preserved: " + "; ".join(changes))
    return report
