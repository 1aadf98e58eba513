"""Plan-time capacity & cost auditor — static HBM/comms contracts.

The repo has two static gates already: :mod:`.audit` (PR 4) checks the
jaxpr we ASK the compiler for and :mod:`.hlo_census` (PR 7) checks what
XLA EMITS. Both need a traceable step, i.e. a built
:class:`~..parallel.dist_embedding.DistributedEmbedding` and a jax
import. This module is the gate that runs *before either*: a pure-host
analytic model of what a :class:`~..parallel.strategy.
DistEmbeddingStrategy` plan will cost once executed — per-rank
parameter + optimizer + exchange-buffer bytes, per-step all-to-all
payload bytes, padded-group shape count (the recompile surface), what the
apply's scatter pays for each slab's size, placement imbalance —
with nothing but integer arithmetic over the plan. GSPMD-style systems
validate placements before touching a pod (SNIPPETS.md [2]'s "8-chip →
6000-chip without changing application code"); this is that validation
for the 26-table / 188M-row Criteo-1TB shapes the ≥2M samples/s
north star is projected at.

The model is *calibrated*, not parallel-universe arithmetic:

* slab geometry (lane packing, row alignment, per-width physical
  capacity) mirrors ``DistributedEmbedding.__init__`` /
  ``ops/packed_slab.py`` exactly and is pinned to them by test;
* exchange layout (``l_max``/``s_max``/groups) comes from the
  executor's OWN plan builder (:func:`~..parallel.plan.build_plan`,
  numpy-only — no jax executes);
* per-step payload bytes use the same ``(world-1) * padded_block``
  formula ``DistributedEmbedding.step_metrics`` reports on device, so
  the prediction is checkable against the measured ``*_a2a_bytes``
  step metrics;
* parameter/optimizer byte totals are cross-checked against
  :func:`.memory.table_memory_report`'s ``eval_shape`` accounting
  (which becomes the calibration target rather than the only source)
  by :func:`compare_with_memory` — ``tools/plan_audit.py --strict``
  enforces agreement.

On top sit declarative :class:`PlanContract` s (max per-rank HBM, max
a2a bytes/step, every rank owns a table, padded-group ceiling),
enforced by ``tools/plan_audit.py --strict`` inside ``make verify`` —
including a ``criteo1tb`` case with
the real vocab vector — and consumed by planners through
:meth:`DistEmbeddingStrategy.predicted_cost` / :func:`rank_strategies`
to rank candidate plans by predicted cost before anything is built.

This module is also the repo's **capacity registry**: chip capability
numbers (HBM bytes, ICI bandwidth, peak FLOPs) live HERE as named
constants. The detlint rule ``hardcoded-capacity`` forbids capacity
literals elsewhere in the package — a device count or HBM size inlined
at a call site drifts silently when hardware assumptions change.
"""

from __future__ import annotations

import dataclasses
import json
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

# --------------------------------------------------------------------------
# capacity registry (the single home for hardware capability numbers;
# everything else in the package must reference these — detlint rule
# `hardcoded-capacity`)
# --------------------------------------------------------------------------

#: TPU vector lane count — the packed-slab layout constant
#: (mirrors ``ops/packed_slab.LANES``; agreement is test-pinned so the
#: jax-free arithmetic here cannot drift from the executor's).
LANES = 128


@dataclasses.dataclass(frozen=True)
class ChipSpec:
    """Capability numbers of one accelerator generation.

    ``hbm_headroom`` is the fraction of HBM a plan may budget: XLA
    reserves workspace, the step needs transients (exchange buffers are
    priced separately but fusions/temps are not), and a plan sized to
    100% of HBM OOMs on the first compile with different flags.
    """

    name: str
    hbm_bytes: int
    hbm_gbps: float
    ici_eff_gbps: float
    bf16_peak_flops: float
    hbm_headroom: float = 0.90
    #: the ``jax.Device.device_kind`` strings this generation reports
    device_kinds: Tuple[str, ...] = ()


#: Known chips. v5e (reports ``device_kind == "TPU v5 lite"``): 16 GiB HBM
#: at 819 GB/s and 197 TFLOP/s bf16 peak are the published figures (Google
#: Cloud documentation, "TPU v5e"). ``ici_eff_gbps`` is NOT a published
#: figure: ~100 GB/s effective per-chip all-to-all bandwidth is an
#: assumption (2D torus, 4x 400 Gbps links, conservative) that no
#: measurement in this repo has checked.
CHIP_SPECS: Dict[str, ChipSpec] = {
    "v5e": ChipSpec("v5e", hbm_bytes=16 * 1024**3, hbm_gbps=819.0,
                    ici_eff_gbps=100.0, bf16_peak_flops=197e12,
                    device_kinds=("TPU v5 lite", "TPU v5e")),
}


def chip_spec_for_device_kind(device_kind: str) -> ChipSpec:
    """The :data:`CHIP_SPECS` entry for a ``jax.Device.device_kind``.
    A device the table does not know is an error — there is no default
    peak and no default HBM size to compute a utilisation against."""
    for spec in CHIP_SPECS.values():
        if device_kind in spec.device_kinds:
            return spec
    raise KeyError(
        f"device_kind {device_kind!r} is not in plan_audit.CHIP_SPECS "
        f"(known: {sorted(k for s in CHIP_SPECS.values() for k in s.device_kinds)}); "
        "add it with its published peaks and their source")


def _scatter_price(stream_rows: int, slab_bytes: int) -> Tuple[str, float]:
    """``(form, ms)`` of the one scatter-add a step makes into a slab, by the
    rule the step itself uses (``parallel/optimizers.py:scatter_form`` and
    its costs, read on the v5e inside the benchmark's cells: ``PERF.md``
    section 6, PR 31). What older notes called a rate cliff between a 2.7
    and an 8.65 GB slab (43 -> 70 ms, 4.5 ms a GB) was the sweep's one pass
    over the slab, 4.04 ms a GiB here; the step now goes row at a time
    where that pass costs more than the stream, so a slab's size is priced,
    not refused. No routing (``stream_rows`` 0) prices nothing."""
    if not stream_rows:
        return "", 0.0
    from ..parallel.optimizers import scatter_form, scatter_ns

    form = scatter_form(stream_rows, slab_bytes)
    return form, scatter_ns(form, stream_rows, slab_bytes) / 1e6


#: Default ceiling on padded (width, kind, hotness) group shapes per
#: plan. Each group is one statically-shaped exchange region — the
#: compiled program is O(#groups) heavy ops, and every distinct
#: (encodings, batch) signature compiles once; the zoo-scale invariant
#: tests pin <= 12 groups at 2002 tables, so a plan past this ceiling
#: has lost the rank-uniform layout property.
DEFAULT_MAX_GROUPS = 16


# --------------------------------------------------------------------------
# jax-free mirrors of the packed-slab arithmetic (ops/packed_slab.py);
# the parity test in tests/test_plan_audit.py pins these to the real ones
# --------------------------------------------------------------------------


def _pack_factor(width: int) -> int:
    return max(1, LANES // int(width))


def _phys_width(width: int) -> int:
    return LANES if _pack_factor(width) > 1 else int(width)


def _align_rows(rows: int, width: int) -> int:
    p = _pack_factor(width)
    return -(-int(rows) // p) * p


_DTYPE_BYTES = {
    "bool": 1, "int8": 1, "uint8": 1, "float8_e4m3fn": 1, "float8_e5m2": 1,
    "bfloat16": 2, "float16": 2, "int16": 2, "uint16": 2,
    "float32": 4, "int32": 4, "uint32": 4,
    "float64": 8, "int64": 8, "uint64": 8,
}


def _dtype_name(dtype) -> str:
    name = getattr(dtype, "__name__", None)
    if name:
        return name
    try:
        return str(np.dtype(dtype))
    except TypeError:
        return str(dtype)


def _dtype_bytes(dtype) -> int:
    """Itemsize of a dtype-like without importing jax (``np.dtype`` knows
    bfloat16 only when ml_dtypes is registered, so the extension names
    are table-driven)."""
    name = _dtype_name(dtype)
    if name in _DTYPE_BYTES:
        return _DTYPE_BYTES[name]
    return int(np.dtype(dtype).itemsize)


# --------------------------------------------------------------------------
# optimizer state model (calibrated against eval_shape over the real
# optimizers' init by compare_with_memory)
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class OptimizerModel:
    """Byte model of one sparse slab optimizer: ``slots`` whole-slab
    state copies in the slab dtype (SGD 0, Adagrad/Momentum 1, Adam 2)
    plus ``aux_bytes_per_slab`` per-rank bookkeeping (Adam's ``[.., 1,
    1]`` f32 step count)."""

    name: str
    slots: int
    aux_bytes_per_slab: int = 0


OPTIMIZER_MODELS: Dict[str, OptimizerModel] = {
    "sgd": OptimizerModel("sgd", 0),
    "adagrad": OptimizerModel("adagrad", 1),
    "momentum": OptimizerModel("momentum", 1),
    "adam": OptimizerModel("adam", 2, aux_bytes_per_slab=4),
}


def optimizer_model(optimizer) -> OptimizerModel:
    """Resolve an optimizer argument — a registry name, an
    :class:`OptimizerModel`, or a ``Sparse*`` instance/class (matched by
    class name) — to its byte model."""
    if isinstance(optimizer, OptimizerModel):
        return optimizer
    if isinstance(optimizer, str):
        try:
            return OPTIMIZER_MODELS[optimizer.lower()]
        except KeyError:
            raise ValueError(
                f"unknown optimizer {optimizer!r} (have: "
                f"{', '.join(sorted(OPTIMIZER_MODELS))})") from None
    name = type(optimizer).__name__ if not isinstance(optimizer, type) \
        else optimizer.__name__
    key = name.lower().removeprefix("sparse")
    if key in OPTIMIZER_MODELS:
        return OPTIMIZER_MODELS[key]
    raise ValueError(
        f"cannot derive a byte model from optimizer {name!r}; pass an "
        "OptimizerModel or a registry name "
        f"({', '.join(sorted(OPTIMIZER_MODELS))})")


# --------------------------------------------------------------------------
# slab geometry from the strategy alone (mirror of
# DistributedEmbedding.__init__'s width grouping; test-pinned)
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class SlabGeometry:
    """Physical slab layout a strategy implies: per width the packed
    ``[world, phys_cap, phys_w]`` stacked-table shape every rank
    allocates, plus each local table's logical row offset."""

    widths: Tuple[int, ...]
    row_offsets_list: Tuple[Tuple[int, ...], ...]
    rows_cap: Dict[int, int]
    phys_cap: Dict[int, int]
    phys_w: Dict[int, int]

    def rank_param_bytes(self, param_bytes: int) -> int:
        """Allocated slab bytes per rank (identical on every rank: the
        layout is SPMD-uniform, padding rows absorb imbalance)."""
        return sum(self.phys_cap[w] * self.phys_w[w] * param_bytes
                   for w in self.widths)


def slab_geometry(strategy) -> SlabGeometry:
    """Derive the packed slab geometry from a planned strategy — the
    same width grouping / row alignment / max-over-ranks capacity
    computation ``DistributedEmbedding.__init__`` performs, without
    building the layer (or importing jax)."""
    widths = sorted({int(c["output_dim"])
                     for cfgs in strategy.local_configs_list
                     for c in cfgs})
    row_offsets_list: List[Tuple[int, ...]] = []
    per_rank_rows: List[Dict[int, int]] = []
    for cfgs in strategy.local_configs_list:
        used = {w: 0 for w in widths}
        offsets = []
        for c in cfgs:
            w = int(c["output_dim"])
            offsets.append(used[w])
            used[w] += _align_rows(int(c["input_dim"]), w)
        row_offsets_list.append(tuple(offsets))
        per_rank_rows.append(used)
    rows_cap = {w: max(max(max(r[w] for r in per_rank_rows), 1),
                       _pack_factor(w)) for w in widths}
    rows_cap = {w: _align_rows(rows_cap[w], w) for w in widths}
    phys_cap = {w: rows_cap[w] // _pack_factor(w) for w in widths}
    phys_w = {w: _phys_width(w) for w in widths}
    return SlabGeometry(widths=tuple(widths),
                        row_offsets_list=tuple(row_offsets_list),
                        rows_cap=rows_cap, phys_cap=phys_cap, phys_w=phys_w)


def encodings_from_inputs(strategy, cat_inputs, world: int
                          ) -> Tuple[List[tuple], int]:
    """Derive the exchange-plan encodings and the per-shard batch from
    abstract (or concrete) GLOBAL inputs — the shapes a caller hands the
    distributed step. Dense arrays map like
    ``DistributedEmbedding._dense_enc`` (leading dim = global batch);
    Ragged-likes (anything with ``values``/``row_splits``) carry their
    per-shard static capacity as ``values.shape[0] // world``.
    """
    encs: List[tuple] = []
    b_local: Optional[int] = None

    def see_batch(gb: int, what: str) -> None:
        nonlocal b_local
        if gb % world:
            raise ValueError(
                f"{what}: global batch {gb} not divisible by world {world}")
        lb = gb // world
        if b_local is None:
            b_local = lb
        elif b_local != lb:
            raise ValueError(
                f"{what}: per-shard batch {lb} disagrees with {b_local}")

    for i, inp in enumerate(cat_inputs):
        tid = strategy.input_table_map[i]
        comb = strategy.global_configs[tid].get("combiner")
        if hasattr(inp, "row_splits"):
            cap = int(inp.values.shape[0])
            nsplit = int(inp.row_splits.shape[0])
            if cap % world or nsplit % world:
                raise ValueError(
                    f"input {i}: ragged shapes {(cap, nsplit)} not "
                    f"divisible by world {world}")
            see_batch(nsplit - world, f"input {i}")
            kind = "rw" if getattr(inp, "weights", None) is not None else "r"
            encs.append((kind, cap // world))
            continue
        shape = tuple(int(d) for d in inp.shape)
        if not shape:
            raise ValueError(f"input {i}: scalar inputs are not routable")
        see_batch(shape[0], f"input {i}")
        dims = shape[1:]
        if comb:
            h = dims[-1] if dims else 1
            ns = int(np.prod(dims[:-1], dtype=np.int64)) if len(dims) > 1 \
                else 1
            encs.append(("d", h, ns))
        else:
            ns = int(np.prod(dims, dtype=np.int64)) if dims else 1
            encs.append(("d", 1, ns))
    if b_local is None:
        raise ValueError("no inputs to derive a batch from")
    return encs, b_local


# --------------------------------------------------------------------------
# the report
# --------------------------------------------------------------------------


def _gb(x: float) -> float:
    return x / 1024**3


@dataclasses.dataclass
class RankBudget:
    """Predicted steady-state bytes of one rank."""

    rank: int
    tables: int
    live_param_bytes: int     # logical rows * width * itemsize placed here
    alloc_param_bytes: int    # the rank-uniform packed slab share
    opt_state_bytes: int
    a2a_buffer_bytes: int     # id block + fwd/bwd activation blocks
    total_bytes: int
    hbm_frac: float           # total / chip HBM
    # jit-carried streaming-vocab state (slot map + freq + admission
    # sketch per width slab with a dynamic table; parallel/streaming.py)
    # — rank-uniform like the slabs, 0 for fully-static plans
    streaming_state_bytes: int = 0
    # the online runtime's RCU double-buffer (parallel/online.py): two
    # param-slab copies live at the publish instant (published view +
    # in-flight clone), one frozen opt-shaped slab shared across
    # versions, and two streaming-state copies — 0 for offline plans
    snapshot_bytes: int = 0
    # the process-isolated serving transport (parallel/supervisor.py):
    # the double-buffered seqlock shared-memory region the trainer maps
    # to publish snapshots to out-of-process workers (utils/shm.py).
    # HOST RAM on the trainer host, not HBM — reported but excluded
    # from total_bytes / hbm_frac and the HBM contract. Rank-uniform
    # (the pickled payload carries the GLOBAL gathered slabs); 0 for
    # in-process plans.
    shm_region_bytes: int = 0

    def to_json(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)


@dataclasses.dataclass
class SlabBudget:
    """One width slab's per-rank apply-scatter target."""

    width: int
    phys_rows: int
    phys_width: int
    rank_bytes: int
    stream_rows: int          # update rows a step scatters into it (0: no routing)
    scatter_form: str         # the form the step's rule picks for them
    scatter_ms: float         # and what that form costs (all rows distinct)

    def to_json(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)


@dataclasses.dataclass
class PlanReport:
    """Everything the static model predicts about one plan at one
    (batch, optimizer, dtype) configuration, plus any contract
    violations. All byte figures are PER RANK unless suffixed
    ``_global``; a2a payloads are per rank per step (bytes leaving the
    chip — the same convention as the on-device ``*_a2a_bytes`` step
    metrics, so predictions are directly checkable against telemetry).
    """

    label: str
    chip: str
    world: int
    strategy: str
    dp_input: bool
    global_batch: int
    local_batch: int
    param_dtype: str
    comm_dtype: str
    optimizer: str
    n_tables: int
    n_sliced_tables: int
    n_groups: int             # padded-group shape count (recompile surface)
    l_max: int
    s_max: int
    groups: List[Dict[str, Any]]
    per_rank: List[RankBudget]
    slabs: List[SlabBudget]
    id_a2a_bytes_per_step: int
    out_a2a_bytes_per_step: int
    grad_a2a_bytes_per_step: int
    total_a2a_bytes_per_step: int
    imbalance_ratio: float
    out_pad_frac: float       # dead-column fraction of the padded exchange
    violations: List[str] = dataclasses.field(default_factory=list)
    n_streaming_tables: int = 0  # dynamic-vocab tables in the plan
    # small-table slots the backward sums into dense blocks, and the stream
    # rows (one an id) that they would have sent (``GroupSpec.block``)
    dense_slots: int = 0
    dense_rows: int = 0

    @property
    def ok(self) -> bool:
        return not self.violations

    @property
    def max_rank_bytes(self) -> int:
        return max(r.total_bytes for r in self.per_rank)

    def to_json(self) -> Dict[str, Any]:
        d = dataclasses.asdict(self)
        return d

    def raise_on_violations(self) -> None:
        if self.violations:
            raise PlanAuditError(
                f"{self.label}: {len(self.violations)} plan-contract "
                "violation(s):\n  " + "\n  ".join(self.violations))

    def markdown(self) -> str:
        """Per-rank budget table + slab/scatter table, for docs and CLI."""
        lines = [
            f"### plan audit: {self.label}",
            "",
            f"chip {self.chip} · world {self.world} · strategy "
            f"{self.strategy} · batch {self.global_batch} (local "
            f"{self.local_batch}) · {self.param_dtype} params · "
            f"{self.optimizer} · {'dp' if self.dp_input else 'mp'} input",
            "",
            f"groups {self.n_groups} · l_max {self.l_max} · s_max "
            f"{self.s_max} · pad {self.out_pad_frac:.1%} · imbalance "
            f"{self.imbalance_ratio:.2f} · a2a/step "
            f"{self.total_a2a_bytes_per_step / 1e6:.2f} MB/rank"
            + (f" · {self.n_streaming_tables} streaming table(s), "
               f"{self.per_rank[0].streaming_state_bytes / 1e6:.2f} MB/rank "
               "slot-map+sketch state"
               if self.n_streaming_tables and self.per_rank else "")
            + (f" · online RCU snapshots "
               f"{self.per_rank[0].snapshot_bytes / 1e6:.2f} MB/rank"
               if self.per_rank and self.per_rank[0].snapshot_bytes
               else "")
            + (f" · shm serving region "
               f"{self.per_rank[0].shm_region_bytes / 1e6:.2f} MB host"
               if self.per_rank and self.per_rank[0].shm_region_bytes
               else ""),
            "",
            "| rank | tables | live GB | alloc GB | opt GB | a2a buf GB "
            "| total GB | HBM frac |",
            "|---:|---:|---:|---:|---:|---:|---:|---:|",
        ]
        for r in self.per_rank:
            lines.append(
                f"| {r.rank} | {r.tables} | {_gb(r.live_param_bytes):.3f} "
                f"| {_gb(r.alloc_param_bytes):.3f} "
                f"| {_gb(r.opt_state_bytes):.3f} "
                f"| {_gb(r.a2a_buffer_bytes):.3f} "
                f"| {_gb(r.total_bytes):.3f} | {r.hbm_frac:.1%} |")
        lines += ["", "| slab | phys shape | rank GB | stream rows "
                  "| scatter |", "|---|---|---:|---:|---|"]
        for s in self.slabs:
            lines.append(
                f"| w{s.width} | [{s.phys_rows}, {s.phys_width}] "
                f"| {_gb(s.rank_bytes):.3f} | {s.stream_rows} "
                f"| {s.scatter_form or '-'} {s.scatter_ms:.1f} ms |")
        if self.dense_slots:
            lines += ["", f"{self.dense_slots} small-table slot(s) summed "
                      f"into dense blocks: {self.dense_rows} rows a step "
                      "left the streams"]
        if self.violations:
            lines += ["", "violations:"] + [f"* {v}" for v in self.violations]
        return "\n".join(lines)


class PlanAuditError(RuntimeError):
    """Raised by :meth:`PlanReport.raise_on_violations` in strict use."""


# --------------------------------------------------------------------------
# contracts
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class PlanContract:
    """Declarative limits a plan must satisfy before it is worth
    building. ``None`` fields are unchecked; :func:`default_contract`
    fills the HBM limit from the chip registry. Violation messages name
    the offending rank / slab so the fix (re-balance, slice, shrink) is
    actionable without re-deriving the report."""

    max_rank_bytes: Optional[int] = None
    max_a2a_bytes_per_step: Optional[int] = None
    max_groups: Optional[int] = DEFAULT_MAX_GROUPS
    require_every_rank_owns_a_table: bool = True
    reason: str = ""


def default_contract(chip: str = "v5e") -> PlanContract:
    """The make-verify contract: fit the chip's usable HBM, keep every
    rank populated, padded-group count within the zoo-scale invariant."""
    spec = CHIP_SPECS[chip]
    return PlanContract(
        max_rank_bytes=int(spec.hbm_bytes * spec.hbm_headroom),
        reason=f"fit {spec.name} ({_gb(spec.hbm_bytes):.0f} GiB HBM at "
               f"{spec.hbm_headroom:.0%} headroom)")


def check_contract(report: PlanReport, contract: PlanContract,
                   strategy=None) -> List[str]:
    """Evaluate one contract against a report; returns violation strings
    (empty = clean). Also appends them to ``report.violations``."""
    out: List[str] = []
    if contract.require_every_rank_owns_a_table and strategy is not None:
        empty = [r for r, tids in enumerate(strategy.table_ids_list)
                 if not tids]
        if empty:
            out.append(
                f"rank(s) {empty} own no table slice (world "
                f"{report.world} > {report.n_sliced_tables} sliced tables"
                " — DistributedEmbedding refuses such plans; shrink the "
                "world or slice the big tables)")
    if contract.max_rank_bytes is not None:
        for r in report.per_rank:
            if r.total_bytes > contract.max_rank_bytes:
                snap = (f" + online snapshots {_gb(r.snapshot_bytes):.2f}"
                        if r.snapshot_bytes else "")
                out.append(
                    f"rank {r.rank}: predicted {_gb(r.total_bytes):.2f} GB "
                    f"(params {_gb(r.alloc_param_bytes):.2f} + opt "
                    f"{_gb(r.opt_state_bytes):.2f} + a2a buffers "
                    f"{_gb(r.a2a_buffer_bytes):.2f}{snap}) exceeds the "
                    f"per-rank HBM contract "
                    f"{_gb(contract.max_rank_bytes):.2f} GB"
                    f" ({contract.reason or report.chip})")
    if contract.max_a2a_bytes_per_step is not None and \
            report.total_a2a_bytes_per_step > contract.max_a2a_bytes_per_step:
        out.append(
            f"per-rank a2a payload {report.total_a2a_bytes_per_step / 1e6:.1f}"
            f" MB/step exceeds the contract "
            f"{contract.max_a2a_bytes_per_step / 1e6:.1f} MB/step "
            f"(id {report.id_a2a_bytes_per_step / 1e6:.1f} + out "
            f"{report.out_a2a_bytes_per_step / 1e6:.1f} + grad "
            f"{report.grad_a2a_bytes_per_step / 1e6:.1f})")
    if contract.max_groups is not None and \
            report.n_groups > contract.max_groups:
        out.append(
            f"{report.n_groups} padded group shapes exceed the ceiling "
            f"{contract.max_groups} — the rank-uniform O(#groups) layout "
            "property is lost (compile surface grows with table "
            "heterogeneity)")
    report.violations.extend(out)
    return out


# --------------------------------------------------------------------------
# the audit
# --------------------------------------------------------------------------


def audit_plan(target,
               global_batch: int,
               *,
               optimizer="sgd",
               param_dtype="float32",
               comm_dtype=None,
               id_dtype_bytes: int = 4,
               encodings: Optional[Sequence[tuple]] = None,
               cat_inputs: Optional[Sequence[Any]] = None,
               dp_input: Optional[bool] = None,
               chip: str = "v5e",
               label: Optional[str] = None,
               contract: Optional[PlanContract] = None,
               streaming_config=None,
               online: bool = False,
               isolated: bool = False) -> PlanReport:
    """Price a plan without building it.

    Args:
      target: a planned :class:`~..parallel.strategy.
        DistEmbeddingStrategy` or a built ``DistributedEmbedding`` (its
        strategy, ``dp_input`` and ``compute_dtype`` become defaults).
      global_batch: global batch size (divided over ``world`` ranks).
      optimizer: registry name (``sgd|adagrad|momentum|adam``), a
        ``Sparse*`` optimizer instance, or an :class:`OptimizerModel`.
      param_dtype / comm_dtype: slab dtype and exchanged-activation
        dtype (``None`` comm = the param dtype, matching the executor's
        ``compute_dtype=None`` default).
      encodings: explicit per-input exchange encodings (the
        ``("d", hot[, nslots])`` / ``("r"|"rw", cap)`` tuples of
        ``parallel/plan.py``). Defaults to hotness-1 dense for every
        input, or is derived from ``cat_inputs`` (global abstract/
        concrete arrays or Ragged-likes) when given.
      dp_input: whether the id all-to-all runs (``False`` = mp input,
        id exchange skipped — its payload prices at zero).
      contract: checked into ``report.violations`` when given
        (:func:`default_contract` is NOT applied implicitly — an audit
        is a report first, a gate only when asked).
      streaming_config: admission-sketch geometry for pricing
        streaming-table state — anything carrying ``.depth`` and
        ``.buckets`` (a :class:`~..parallel.streaming.StreamingConfig`;
        duck-typed so this module stays jax-free). Default: the
        ``DETPU_ADMIT_SKETCH_*`` env policy. Pass the SAME config the
        step builder gets via ``dynamic=`` or the per-rank
        ``streaming_state_bytes`` under-/over-bills a non-default
        sketch.
      online: price the concurrent train-and-serve runtime
        (``parallel/online.py``): bills the RCU snapshot double-buffer
        per rank as ``snapshot_bytes`` — two param-slab copies (the
        published view plus the in-flight clone at the publish
        instant), ONE opt-shaped frozen slab (the publisher clones
        optimizer state once and shares the buffers across every
        version — the serve forward never reads them), and two
        streaming-state copies. An offline-fitting plan can exceed HBM
        the moment serving runs beside training; this prices that
        before building anything.
      isolated: price the process-isolated serving transport
        (``parallel/supervisor.py``): bills the double-buffered seqlock
        shared-memory region as the rank-uniform ``shm_region_bytes``,
        using ``utils/shm.py``'s exact arithmetic —
        ``region_bytes(slack_capacity(payload))`` where the payload is
        the host-pickled GLOBAL snapshot (gathered packed slabs plus
        streaming leaves, world-wide; workers re-shard on ingest) and
        the slack is the ``DETPU_SHM_SLACK`` growth headroom. HOST RAM
        on the trainer host, not HBM: reported, but excluded from
        ``total_bytes`` / ``hbm_frac`` and the HBM contract.

    Nothing executes and nothing is materialized: the heaviest object
    built is the executor's numpy plan tensors (``[world, n]`` per
    group).
    """
    from ..parallel import plan as plan_mod  # numpy-only plan builder

    # a strategy exposes local_configs_list itself; a DistributedEmbedding
    # wraps one under .strategy (which on the strategy itself is the NAME)
    strategy = (target if hasattr(target, "local_configs_list")
                else target.strategy)
    if dp_input is None:
        dp_input = bool(getattr(target, "dp_input", True))
    if comm_dtype is None:
        comm_dtype = getattr(target, "compute_dtype", None) or param_dtype
    world = int(strategy.world_size)
    p_isz = _dtype_bytes(param_dtype)
    c_isz = _dtype_bytes(comm_dtype)
    model = optimizer_model(optimizer)

    if encodings is not None:
        encs = [tuple(e) for e in encodings]
        if global_batch % world:
            raise ValueError(
                f"global_batch {global_batch} not divisible by world {world}")
        b_local = global_batch // world
    elif cat_inputs is not None:
        encs, b_local = encodings_from_inputs(strategy, cat_inputs, world)
        if b_local * world != int(global_batch):
            raise ValueError(
                f"cat_inputs imply global batch {b_local * world}, "
                f"got global_batch={global_batch}")
    else:
        encs = [("d", 1)] * len(strategy.input_table_map)
        if global_batch % world:
            raise ValueError(
                f"global_batch {global_batch} not divisible by world {world}")
        b_local = global_batch // world

    geom = slab_geometry(strategy)
    plan = plan_mod.build_plan(strategy, [list(o) for o in
                                          geom.row_offsets_list],
                               encs, b_local)

    alloc_rank = geom.rank_param_bytes(p_isz)
    opt_rank = (model.slots * alloc_rank
                + model.aux_bytes_per_slab * len(geom.widths))

    # transient exchange buffers a step holds per rank: the id block
    # send+recv pair ([world, l_max] ids each; mp input holds one packed
    # block instead of a send/recv pair) and the output exchange's
    # forward send+recv pair ([world, b, s_max] activations; the
    # backward cotangent exchange reuses the same shapes after the
    # forward pair is dead, so it is not double-counted)
    id_blocks = 1 if not dp_input else 2
    a2a_buf = (id_blocks * world * plan.l_max * id_dtype_bytes
               + 2 * world * b_local * plan.s_max * c_isz)

    live_rank = [0] * world
    tables_rank = [0] * world
    for r, cfgs in enumerate(strategy.local_configs_list):
        tables_rank[r] = len(cfgs)
        for c in cfgs:
            live_rank[r] += int(c["input_dim"]) * int(c["output_dim"]) * p_isz

    # streaming-vocab carried state: slot map + frequency record (one
    # int32 each per logical slab row) + the admission sketch, for every
    # width slab holding a dynamic table (parallel/streaming.py). The
    # slab + shared-bucket ROWS are already priced above (a streaming
    # table declares input_dim = capacity + buckets); this is the extra
    # jit-carried state the dynamic mode adds to the per-rank HBM bill.
    stream_tids = [t for t, c in enumerate(strategy.global_configs)
                   if c.get("streaming")]
    stream_bytes = 0
    if stream_tids:
        if streaming_config is not None:
            depth = max(1, int(streaming_config.depth))
            buckets = max(2, int(streaming_config.buckets))
        else:
            from ..utils import envvars

            depth = max(1, envvars.get_int("DETPU_ADMIT_SKETCH_DEPTH"))
            buckets = max(2, envvars.get_int("DETPU_ADMIT_SKETCH_WIDTH"))
        for w in sorted({int(strategy.global_configs[t]["output_dim"])
                         for t in stream_tids}):
            rows = geom.phys_cap[w] * _pack_factor(w)
            stream_bytes += 2 * rows * 4 + depth * buckets * 4

    # the online runtime's RCU double-buffer (see the `online` arg):
    # 2x params (published + in-flight) + 1x opt (frozen, shared) +
    # 2x streaming state — exactly what SnapshotPublisher keeps live
    snap_bytes = (2 * alloc_rank + opt_rank + 2 * stream_bytes
                  if online else 0)

    # the process-isolated serving transport (see the `isolated` arg):
    # shm.py's exact region arithmetic over the host-pickled GLOBAL
    # payload — the gathered packed slabs plus streaming leaves across
    # every rank (the supervisor publishes global state; the worker
    # re-shards on ingest)
    shm_bytes = 0
    if isolated:
        from ..utils import shm as shm_mod

        payload_len = world * (alloc_rank + stream_bytes)
        shm_bytes = shm_mod.region_bytes(
            shm_mod.slack_capacity(payload_len))

    spec = CHIP_SPECS[chip]
    per_rank = []
    for r in range(world):
        total = alloc_rank + opt_rank + a2a_buf + stream_bytes + snap_bytes
        per_rank.append(RankBudget(
            rank=r, tables=tables_rank[r],
            live_param_bytes=live_rank[r],
            alloc_param_bytes=alloc_rank,
            opt_state_bytes=opt_rank,
            a2a_buffer_bytes=a2a_buf,
            total_bytes=total,
            hbm_frac=total / spec.hbm_bytes,
            streaming_state_bytes=stream_bytes,
            snapshot_bytes=snap_bytes,
            shm_region_bytes=shm_bytes))

    # update rows a step scatters into each width slab on one rank, by the
    # plan's own count (parallel/apply.py builds these streams): a row an id
    # of every source's block, or a small table's dense block
    stream = {w: 0 for w in geom.widths}
    stream.update(plan.stream_rows())
    slabs = []
    for w in geom.widths:
        rb = geom.phys_cap[w] * geom.phys_w[w] * p_isz
        form, ms = _scatter_price(stream[w], rb)
        slabs.append(SlabBudget(
            width=w, phys_rows=geom.phys_cap[w], phys_width=geom.phys_w[w],
            rank_bytes=rb, stream_rows=stream[w], scatter_form=form,
            scatter_ms=ms))

    # per-step off-chip payloads — the exact step_metrics formulas, so
    # the prediction is checkable against the on-device *_a2a_bytes
    off = max(world - 1, 0)
    id_a2a = off * plan.l_max * id_dtype_bytes if dp_input else 0
    out_a2a = off * b_local * plan.s_max * c_isz
    live_cols = sum(plan.out_width(inst) for inst in plan.instances)
    pad_frac = (1.0 - live_cols / (world * plan.s_max)
                if plan.s_max else 0.0)
    mean_live = sum(live_rank) / world if world else 0.0
    imbalance = (max(live_rank) / mean_live) if mean_live else float("inf")

    n_sliced = sum(len(t) for t in strategy.table_ids_list)
    report = PlanReport(
        label=label or f"{strategy.strategy}/world{world}",
        chip=chip, world=world, strategy=strategy.strategy,
        dp_input=bool(dp_input), global_batch=int(global_batch),
        local_batch=b_local,
        param_dtype=_dtype_name(param_dtype),
        comm_dtype=_dtype_name(comm_dtype),
        optimizer=model.name,
        n_tables=len(strategy.global_configs),
        n_sliced_tables=n_sliced,
        n_groups=len(plan.groups), l_max=plan.l_max, s_max=plan.s_max,
        groups=[{"kind": g.kind, "width": g.width, "hot": g.hot,
                 "slots": g.n, "block_len": g.blen,
                 "block_rows": list(g.block)} for g in plan.groups],
        dense_slots=plan.dense_slots, dense_rows=plan.dense_rows,
        per_rank=per_rank, slabs=slabs,
        id_a2a_bytes_per_step=int(id_a2a),
        out_a2a_bytes_per_step=int(out_a2a),
        grad_a2a_bytes_per_step=int(out_a2a),
        total_a2a_bytes_per_step=int(id_a2a + 2 * out_a2a),
        imbalance_ratio=float(imbalance),
        out_pad_frac=float(pad_frac),
        n_streaming_tables=len(stream_tids))
    if contract is not None:
        check_contract(report, contract, strategy=strategy)
    return report


def audit_plan_spec(spec: Dict[str, Any],
                    *,
                    optimizer="sgd",
                    param_dtype="float32",
                    chip: str = "v5e",
                    contract: Optional[PlanContract] = None,
                    label: Optional[str] = None) -> PlanReport:
    """Capacity-only audit of a bare :meth:`DistEmbeddingStrategy.
    plan_spec` dict (e.g. read back from a checkpoint's ``meta.json``).
    The spec carries slice geometry but no input routing, so exchange
    payloads, groups and the slabs' scatters price at zero — the HBM
    contract still applies (pair with :func:`audit_plan` for the full model)."""

    class _SpecView:
        """Duck-typed strategy view over the spec's ``local_tables``."""

        def __init__(self, s):
            self.world_size = int(s["world_size"])
            self.strategy = s.get("strategy", "?")
            self.local_configs_list = [
                [{"input_dim": rows, "output_dim": width}
                 for (_tid, rows, width, _rb, _cs) in rank]
                for rank in s["local_tables"]]
            self.table_ids_list = [[t[0] for t in rank]
                                   for rank in s["local_tables"]]
            self.global_configs = [None] * (max(
                (t[0] for rank in s["local_tables"] for t in rank),
                default=-1) + 1)
            self.input_table_map = []

    view = _SpecView(spec)
    world = view.world_size
    geom = slab_geometry(view)
    p_isz = _dtype_bytes(param_dtype)
    model = optimizer_model(optimizer)
    alloc_rank = geom.rank_param_bytes(p_isz)
    opt_rank = (model.slots * alloc_rank
                + model.aux_bytes_per_slab * len(geom.widths))
    chip_spec = CHIP_SPECS[chip]
    live_rank = [sum(int(c["input_dim"]) * int(c["output_dim"]) * p_isz
                     for c in cfgs) for cfgs in view.local_configs_list]
    per_rank = [RankBudget(
        rank=r, tables=len(view.local_configs_list[r]),
        live_param_bytes=live_rank[r], alloc_param_bytes=alloc_rank,
        opt_state_bytes=opt_rank, a2a_buffer_bytes=0,
        total_bytes=alloc_rank + opt_rank,
        hbm_frac=(alloc_rank + opt_rank) / chip_spec.hbm_bytes)
        for r in range(world)]
    slabs = []
    for w in geom.widths:
        rb = geom.phys_cap[w] * geom.phys_w[w] * p_isz
        slabs.append(SlabBudget(w, geom.phys_cap[w], geom.phys_w[w], rb,
                                0, "", 0.0))
    mean_live = sum(live_rank) / world if world else 0.0
    report = PlanReport(
        label=label or f"spec/{view.strategy}/world{world}",
        chip=chip, world=world, strategy=view.strategy, dp_input=True,
        global_batch=0, local_batch=0,
        param_dtype=_dtype_name(param_dtype),
        comm_dtype=_dtype_name(param_dtype),
        optimizer=model.name, n_tables=len(view.global_configs),
        n_sliced_tables=sum(len(t) for t in view.table_ids_list),
        n_groups=0, l_max=0, s_max=0, groups=[], per_rank=per_rank,
        slabs=slabs, id_a2a_bytes_per_step=0, out_a2a_bytes_per_step=0,
        grad_a2a_bytes_per_step=0, total_a2a_bytes_per_step=0,
        imbalance_ratio=(max(live_rank) / mean_live) if mean_live
        else float("inf"),
        out_pad_frac=0.0)
    if contract is not None:
        # exchange/group limits are unknowable from a bare spec
        capacity_only = dataclasses.replace(
            contract, max_a2a_bytes_per_step=None, max_groups=None)
        check_contract(report, capacity_only, strategy=view)
    return report


# --------------------------------------------------------------------------
# calibration + planner ranking
# --------------------------------------------------------------------------


def compare_with_memory(report: PlanReport,
                        mem_report: Dict[str, Any]) -> Dict[str, Any]:
    """Drift of the jax-free byte model against
    :func:`.memory.table_memory_report`'s ``eval_shape`` accounting (the
    calibration target). Returns fractional drifts per component plus
    ``max_abs_drift``; the CLI's strict mode requires ~exact agreement
    (the two compute the same layout — drift means the mirror broke)."""
    totals = mem_report["totals"]
    world = mem_report["world"]

    def drift(pred, target):
        if not target:
            return 0.0 if not pred else float("inf")
        return (pred - target) / target

    pred_alloc = sum(r.alloc_param_bytes for r in report.per_rank)
    pred_live = sum(r.live_param_bytes for r in report.per_rank)
    pred_opt = sum(r.opt_state_bytes for r in report.per_rank)
    out = {
        "param_alloc_drift": drift(pred_alloc,
                                   totals["param_bytes_allocated"]),
        "param_live_drift": drift(pred_live, totals["param_bytes_live"]),
        "opt_state_drift": (
            drift(pred_opt, totals["opt_state_bytes"])
            if totals.get("opt_state_bytes") is not None else 0.0),
        "world": world,
    }
    out["max_abs_drift"] = max(abs(v) for k, v in out.items()
                               if k.endswith("_drift"))
    return out


def price_int8_serving(target,
                       global_batch: int,
                       *,
                       param_dtype="float32",
                       comm_dtype=None,
                       scale_bytes: int = 4,
                       chip: str = "v5e",
                       encodings: Optional[Sequence[tuple]] = None,
                       cat_inputs: Optional[Sequence[Any]] = None,
                       dp_input: Optional[bool] = None,
                       label: Optional[str] = None) -> Dict[str, Any]:
    """Price an int8-rows-with-per-row-scales SERVING variant of a plan
    — pricing only, nothing materializes (the quantized table itself is
    a future PR; this is its capacity case and the input to the hot-row
    cache sizing of ROADMAP item 1).

    The variant: frozen inference tables store each logical row as
    ``width`` int8 codes plus one ``scale_bytes``-wide per-row scale,
    dequantized after the gather. Two effects priced here:

    * **per-rank HBM** — the serving table bill drops from
      ``rows x width x itemsize`` to ``rows x (width + scale_bytes)``
      (~4x for fp32 tables, ~2x for bf16, minus the per-row scale tax
      that bites narrow widths hardest); no optimizer state exists at
      serve time, so tables ARE the resident bill.
    * **out-a2a payload** — when the exchange ships the quantized rows
      (one scale per routed slot) and dequantizes on the receiving
      side, the activation payload shrinks by the same code/scale
      arithmetic — fewer off-chip bytes per request on exactly the
      exchange the serving runtime's latency rides.

    Returns a plain JSON-able dict next to a baseline
    :func:`audit_plan` run (optimizer ``"sgd"`` — zero slots, the
    inference bill).
    """
    strategy = (target if hasattr(target, "local_configs_list")
                else target.strategy)
    base = audit_plan(target, global_batch, optimizer="sgd",
                      param_dtype=param_dtype, comm_dtype=comm_dtype,
                      encodings=encodings, cat_inputs=cat_inputs,
                      dp_input=dp_input, chip=chip, label=label)
    p_isz = _dtype_bytes(param_dtype)
    c_isz = _dtype_bytes(base.comm_dtype)
    geom = slab_geometry(strategy)
    base_table = geom.rank_param_bytes(p_isz)
    int8_table = sum(geom.rows_cap[w] * (w + scale_bytes)
                     for w in geom.widths)
    # one scale per routed (sample, slot) pair rides the quantized
    # exchange next to the int8 codes; s_max counts padded columns, the
    # group slot counts the scales
    n_slots = sum(g["slots"] for g in base.groups)
    off = max(base.world - 1, 0)
    int8_out = off * base.local_batch * (base.s_max + n_slots * scale_bytes)
    spec = CHIP_SPECS[chip]
    return {
        "label": base.label,
        "world": base.world,
        "param_dtype": base.param_dtype,
        "scale_bytes": int(scale_bytes),
        "table_bytes_per_rank": int(base_table),
        "int8_table_bytes_per_rank": int(int8_table),
        "table_bytes_ratio": (base_table / int8_table
                              if int8_table else 0.0),
        "hbm_frac": base_table / spec.hbm_bytes,
        "int8_hbm_frac": int8_table / spec.hbm_bytes,
        "out_a2a_bytes_per_step": int(base.out_a2a_bytes_per_step),
        "int8_out_a2a_bytes_per_step": int(int8_out),
        "out_a2a_ratio": (base.out_a2a_bytes_per_step / int8_out
                          if int8_out else 0.0),
        "comm_dtype_bytes": int(c_isz),
        "note": "pricing only — the quantized serving table is a "
                "future PR; dequantize-after-gather assumed, "
                "one scale per logical row / per routed slot",
    }


def rank_strategies(configs,
                    world: int,
                    global_batch: int,
                    strategies: Sequence[str] = ("basic", "memory_balanced",
                                                 "memory_optimized",
                                                 "comm_balanced"),
                    column_slice_threshold: Optional[int] = None,
                    row_slice_threshold: Optional[int] = None,
                    input_table_map=None,
                    input_hotness=None,
                    **audit_kw) -> List[Tuple[str, PlanReport]]:
    """Plan every candidate strategy and rank them by predicted cost —
    the planner-side cost hook (``telemetry_balanced`` is excluded by
    default: it needs measured ``table_loads``).

    Sort key: contract-violating plans last, then max per-rank bytes,
    then total a2a payload — "fits first, cheapest exchange among those
    that fit". Returns ``[(strategy_name, PlanReport)]`` best first.
    """
    from ..parallel.strategy import DistEmbeddingStrategy

    contract = audit_kw.pop("contract", None)
    out = []
    for name in strategies:
        st = DistEmbeddingStrategy(
            configs, world, strategy=name,
            input_table_map=input_table_map,
            column_slice_threshold=column_slice_threshold,
            row_slice_threshold=row_slice_threshold,
            input_hotness=input_hotness)
        rep = audit_plan(st, global_batch, label=f"{name}/world{world}",
                         contract=contract, **audit_kw)
        out.append((name, rep))
    out.sort(key=lambda kv: (len(kv[1].violations),
                             kv[1].max_rank_bytes,
                             kv[1].total_a2a_bytes_per_step))
    return out


def report_to_jsonl(report: PlanReport) -> str:
    """One-line JSON form (sidecar-friendly)."""
    return json.dumps(report.to_json(), sort_keys=True)
