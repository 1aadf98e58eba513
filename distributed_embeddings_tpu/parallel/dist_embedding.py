"""Hybrid-parallel distributed embedding over a TPU mesh.

TPU-native re-design of the reference's ``DistributedEmbedding``
(``distributed_embeddings/python/layers/dist_model_parallel.py:199-505``).
The capability surface is the same — model-parallel tables + data-parallel
dense layers stitched by two all-to-alls per step — but the execution model is
JAX SPMD instead of Horovod MPMD:

* **One program, W mesh positions.** The reference runs one Python process per
  GPU, each building only its local tables. Here a single program runs on every
  device inside ``jax.shard_map``; per-rank table heterogeneity is *data*, not
  program: the exchange is laid out as rank-uniform group regions at static
  offsets, and small per-rank plan tensors (``parallel/plan.py``) indexed by
  ``lax.axis_index`` tell each device which table rows its slots read. One
  compiled program serves every rank — O(#groups) heavy HLO ops, independent
  of world size and table count (an earlier design's ``lax.switch`` over
  rank-specialized branches compiled O(world x tables) HLO and hit a
  compile-time cliff at the 2002-table colossal scale).
* **Parameters as width-grouped, lane-packed stacked tables.** Each rank's
  tables of width ``w`` stack row-major into one 2-D slab, and narrow widths
  pack ``p = 128//w`` logical rows per 128-lane physical row
  (``ops/packed_slab.py``): the global parameter is a dict
  ``{width: [world, phys_cap_w, phys_w]}`` sharded over the mesh axis, where
  ``phys_w = 128`` for ``w < 128`` and ``w`` otherwise. Full-tile rows are
  the layout XLA's TPU backend has fast row-gather/scatter paths for
  (measured ~10/15 ns per row vs ~22/100 ns for sub-tile rows — see
  ``docs/perf_tpu.md``), and the width grouping gives SPMD-uniform pytree
  shapes across ranks (padding rows absorb imbalance). This replaces the
  reference's per-rank ``tf.Variable`` lists.
* **Collectives.** ``hvd.alltoall(splits=...)`` (variable splits,
  ``dist_model_parallel.py:282``) has no ragged JAX primitive on every backend,
  so id blocks are padded to the max per-rank split and exchanged with
  ``lax.all_to_all`` — ids are cheap. The mp→dp output exchange
  (``dist_model_parallel.py:301``) pads widths to the max per-rank output width.
  Autodiff of ``all_to_all`` provides the backward exchange exactly like
  Horovod's registered alltoall gradient.

Input contract (distributed path): per feature either a dense int array
(``[local_batch]`` or ``[local_batch, hotness]``), a static-capacity
:class:`~..ops.embedding_lookup.Ragged` (values ``[cap]``, row_splits
``[local_batch+1]``; combiner required), or a
:class:`~..ops.embedding_lookup.SparseIds` COO batch (converted to CSR on
entry — beyond the reference, whose distributed path is dense-only while its
local layers accept sparse). Identical batch and capacities on every rank. **Ids must lie in ``[0, input_dim)``** — same contract as the
reference (TF's gather on out-of-range ids is undefined on GPU). Out-of-range
ids here are clipped in the forward (a safety net so a bad id cannot read a
neighbouring table in the slab) but routed to the dropped sentinel in the
sparse backward, so a clipped id trains nothing: don't rely on the clip. Ragged features travel inside the padded id all-to-all as
``[values(cap), lengths(b)]`` blocks — the variable-hotness capability the
reference reaches through its custom kernel (``embedding_lookup_ops.py:79-80``).

**Module layout.** This file is the orchestrator: parameter/layout
ownership, input normalization, checkpointing, metrics, telemetry, and
streaming. The step's executor phases live in three sibling modules the
:class:`~.schedule.StepSchedule` names — :mod:`.exchange` (block
assembly + the three all-to-alls), :mod:`.lookup` (plan-driven gathers
and combiners), and :mod:`.apply` (the manual sparse backward + the
per-width optimizer scatters). The split is pure code motion from the
former monolith: the traced step — and therefore the compiled HLO, the
census pass budgets, and the trajectory CRCs — is bit-for-bit unchanged.
"""

from __future__ import annotations

import functools
import os
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct
from jax import lax

from ..utils import obs
from ..utils import runtime as _runtime
from ..layers.embedding import default_embeddings_init
from ..ops.embedding_lookup import Ragged, SparseIds, row_to_split
from ..ops import packed_slab as ps
from . import apply as apply_mod
from . import exchange as exchange_mod
from . import lookup as lookup_mod
from . import plan as plan_mod
from . import schedule as schedule_mod
from .strategy import DistEmbeddingStrategy

EmbedParams = Dict[str, jax.Array]

# Checkpoint streaming chunk: 128M elements, the reference's scatter-update
# chunk size (``dist_model_parallel.py:362-380``); also keeps every single
# host<->device transfer below the 2^31-element indexing cliff the reference
# engineered around (``:388-409,426-438``).
CHECKPOINT_CHUNK_ELEMS = 128 * 1024 * 1024


@functools.partial(jax.jit, donate_argnums=0)
def _write_rows(buf: jax.Array, chunk: jax.Array, start) -> jax.Array:
    """Donated row-range write into a shard buffer (in-place on backends with
    donation; at worst one transient shard copy)."""
    return lax.dynamic_update_slice(buf, chunk, (start, 0))


@struct.dataclass
class MpInputs:
    """Model-parallel input batch (``dp_input=False``).

    The reference's mp-input mode feeds each rank its *local* tables' ids for
    the full global batch, skipping the dp→mp id all-to-all entirely
    (``dist_model_parallel.py:213,267-288``; the DLRM example's default input
    path, ``examples/dlrm/main.py:57,161-190``). In SPMD form that per-rank
    block is exactly the ``ids_recv`` layout the dp path's all-to-all would
    have produced, packed once on host by :meth:`DistributedEmbedding.pack_mp_inputs`:

    * ``packed``: ``[world_dest, world_src, l_max]`` globally (shard over the
      mesh axis on dim 0; inside ``shard_map`` each device sees
      ``[1, world_src, l_max]``). Row ``[r, s]`` holds source-shard ``s``'s
      local batch of ids for every input owned by rank ``r``, laid out in the
      rank-uniform group-region format of ``parallel/plan.py`` (the same
      layout the dp path's id all-to-all produces).
    * ``hots``: static per-global-input encoding — an int (dense hotness) or
      ``("r", capacity)`` for a ragged feature. Must be globally known (the
      exchange layout is derived from it).
    * ``local_batch``: static per-shard batch size ``b``.
    """

    packed: jax.Array
    hots: tuple = struct.field(pytree_node=False)
    local_batch: int = struct.field(pytree_node=False)


def _wkey(width: int) -> str:
    return f"w{width}"


class DistributedEmbedding:
    """Shards embedding tables across a mesh axis and exchanges activations
    with two all-to-alls per step.

    Args:
      embeddings: list of :class:`...layers.Embedding` modules or config dicts
        (``input_dim``, ``output_dim``, optional ``combiner``,
        ``embeddings_initializer``).
      world_size: mesh-axis size (model-parallel positions == data-parallel
        positions, as in the reference).
      strategy: ``basic | memory_balanced | memory_optimized |
        comm_balanced | telemetry_balanced`` (``comm_balanced`` balances
        per-(width, inputs) table counts so the padded output exchange
        wastes the fewest bytes; ``telemetry_balanced`` balances measured
        per-table traffic and needs ``table_loads`` — see
        ``parallel/strategy.py``).
      column_slice_threshold: max elements per slice; larger tables are split
        width-wise into power-of-2 slices.
      row_slice: max elements per table slice for ROW-wise (vocab-range)
        slicing — the mode the reference declares but never implements
        (``dist_model_parallel.py:225,233-234``; its docstring leaves the
        type "TBD", so an int threshold mirroring
        ``column_slice_threshold`` is used here). Tables over the threshold
        split into power-of-2 row-range slices placed like any other table;
        each slice serves only ids in its range (out-of-range ids read zero
        rows forward and drop backward) and the slice outputs sum. A table
        already split by ``column_slice_threshold`` is not row-sliced.
      masked_reads: if True, out-of-range ids on NON-sliced tables read a
        ZERO row in the forward instead of clipping into the last row
        (out-of-range backward always drops). Costs one compare+select per
        gathered row; makes bad-pipeline ids visible as zeros instead of
        silently training on the clipped row's values. Row-sliced tables
        use masked reads regardless (their correctness depends on it).
      invalid_id_policy: what negative / out-of-vocab ids do — the single
        ingestion-point policy for every input path (dense, ragged,
        sparse, mp-packed):

        * ``'clamp'`` (default, the historical behavior): the forward
          READ clamps into the table (negatives read row 0, overflow
          reads the last row) and the backward drops the id — a bad id
          reads a defined row but trains nothing.
        * ``'drop'``: invalid ids contribute a ZERO row forward and drop
          backward (forces ``masked_reads``) — a bad id neither reads
          nor trains anything.
        * ``'raise'``: eager (host-visible) ingestion —
          :meth:`check_inputs`, called automatically on concrete inputs
          and by the resilient driver before each dispatch — raises
          :class:`~...utils.runtime.InvalidInputError` naming the input
          and the offending count. Inside an already-jitted step the ids
          are tracers; there the read behaves like ``'clamp'`` and the
          violation surfaces through the ``invalid_id_count`` step
          metric (which ``parallel.resilient.run_resilient`` escalates).

        All three policies surface the per-rank count of invalid live ids
        as ``invalid_id_count`` in :meth:`step_metrics`.
      ragged_overflow_raise: opt-in escalation for ragged batches whose
        claimed row lengths overflow their static capacity (ids silently
        truncated otherwise): :meth:`check_inputs` raises
        :class:`~...utils.runtime.InvalidInputError`, and the resilient
        driver escalates on a nonzero ``id_overflow`` metric.
      dp_input: if True (default) inputs are data-parallel shards
        ``[local_batch, ...]`` per global feature. If False, inputs are
        model-parallel: a :class:`MpInputs` built by :meth:`pack_mp_inputs`
        (each rank holds the full global batch of ids for its local tables;
        no id all-to-all runs).
      input_table_map: ``input[i]`` uses ``table[input_table_map[i]]``.
      input_hotness: optional per-input hotness hint; lets ``comm_balanced``
        model the exchange groups exactly (see ``strategy.py``).
      table_loads: per-table measured traffic weights for the
        ``telemetry_balanced`` strategy (see ``strategy.py``; derive them
        with :func:`...analysis.telemetry.table_loads_from_summary`).
      axis_name: mesh axis the executor runs under (inside ``shard_map``).
      compute_dtype: output/communication dtype. Embedding reads and combiner
        reductions stay in the parameter dtype; outputs are cast to
        ``compute_dtype`` *before* the mp→dp all-to-all — the reference's
        mixed-precision pre-comm cast (``dist_model_parallel.py:300,499``) —
        halving exchange bytes with bf16. Backward cotangents arrive in
        ``compute_dtype``, ride the reverse exchange, and are cast back up at
        the optimizer scatter. ``None`` keeps the parameter dtype end-to-end.
      schedule: the :class:`~.schedule.StepSchedule` the trainer's hybrid
        step executes and the schedule auditor certifies. ``None`` /
        ``"serialized"`` (default) is the honest serialized baseline
        (streaming layers declare their already-measured admission-staging
        overlap); ``"pipelined"`` — or an explicit
        :func:`~.schedule.pipelined_schedule` — opts into the K-microbatch
        software-pipelined step (``DETPU_MICROBATCH`` resolves K for the
        string form): the global batch splits into K chains inside one
        jitted step so microbatch ``k+1``'s exchanges overlap microbatch
        ``k``'s dense compute, with gradients accumulated so the applied
        update matches the serialized step (K=1 is bitwise the serialized
        program; the per-device batch must divide by K).
    """

    def __init__(self,
                 embeddings: Sequence[Any],
                 world_size: int,
                 strategy: str = "basic",
                 column_slice_threshold: Optional[int] = None,
                 row_slice: Optional[Any] = None,
                 dp_input: bool = True,
                 input_table_map: Optional[Sequence[int]] = None,
                 axis_name: str = "data",
                 compute_dtype: Optional[Any] = None,
                 input_hotness: Optional[Sequence[int]] = None,
                 masked_reads: bool = False,
                 invalid_id_policy: str = "clamp",
                 ragged_overflow_raise: bool = False,
                 table_loads: Optional[Sequence[float]] = None,
                 schedule=None):
        if row_slice is not None and (isinstance(row_slice, bool)
                                      or not isinstance(row_slice, int)):
            # bool subclasses int: row_slice=True would silently mean
            # threshold 1 (slice EVERY table world-ways)
            raise TypeError(
                "row_slice takes an int element threshold (the reference "
                "left the type 'TBD'; see the class docstring)")
        if invalid_id_policy not in ("clamp", "drop", "raise"):
            raise ValueError(
                f"invalid_id_policy must be 'clamp' | 'drop' | 'raise', "
                f"got {invalid_id_policy!r}")
        self.world_size = int(world_size)
        self.axis_name = axis_name
        self.dp_input = dp_input
        self.compute_dtype = compute_dtype
        self.invalid_id_policy = invalid_id_policy
        self.ragged_overflow_raise = bool(ragged_overflow_raise)
        # 'drop' rides the masked-read machinery: zero forward read,
        # dropped backward — exactly the drop semantics, per slot
        self.masked_reads = bool(masked_reads) or invalid_id_policy == "drop"
        self.strategy = DistEmbeddingStrategy(
            embeddings, self.world_size, strategy=strategy,
            input_table_map=input_table_map,
            column_slice_threshold=column_slice_threshold,
            input_hotness=input_hotness,
            row_slice_threshold=row_slice,
            table_loads=table_loads)
        if len(self.strategy.global_configs) < self.world_size:
            raise NotImplementedError(
                "Fewer tables than mesh positions is not supported "
                "(reference constraint, dist_model_parallel.py:252-253)")

        # slice multiplicity per global table (column slicing)
        self._slices_per_table = [0] * len(self.strategy.global_configs)
        for rank_ids in self.strategy.table_ids_list:
            for tid in rank_ids:
                self._slices_per_table[tid] += 1

        # streaming (dynamic-vocab) tables: {tid: (capacity, buckets)}.
        # The declared input_dim IS the physical slab footprint
        # (capacity slots + shared bucket rows), so every capacity/
        # checkpoint/re-shard subsystem prices and moves the table like
        # any static one; only the id INTERPRETATION changes (external
        # ids remap through the jit-carried slot map, parallel/
        # streaming.py). Sliced streaming tables are rejected — a slot
        # map cannot span slices.
        self.streaming_tables: Dict[int, tuple] = {}
        for tid, cfg in enumerate(self.strategy.global_configs):
            sc = cfg.get("streaming")
            if not sc:
                continue
            cap, nb = int(sc["capacity"]), int(sc["buckets"])
            if cap <= 0 or nb <= 0:
                raise ValueError(
                    f"table {tid}: streaming capacity/buckets must be "
                    f"positive, got {sc!r}")
            if cap + nb != int(cfg["input_dim"]):
                raise ValueError(
                    f"table {tid}: streaming capacity {cap} + buckets "
                    f"{nb} must equal input_dim {cfg['input_dim']} (the "
                    "slab holds the slots followed by the shared bucket "
                    "rows)")
            if self._slices_per_table[tid] != 1:
                raise NotImplementedError(
                    f"table {tid} is row/column-sliced "
                    f"({self._slices_per_table[tid]} slices): streaming "
                    "tables must stay unsliced (the slot map cannot span "
                    "slices) — raise the slice thresholds or shrink the "
                    "capacity")
            self.streaming_tables[tid] = (cap, nb)
        self._streaming_arrays_cache: Dict[int, list] = {}

        # Width-grouped stacked-table layout: per rank, tables of equal width
        # stack row-major into one 2-D slab; slab row capacity is the max over
        # ranks so the params pytree is SPMD-uniform. Narrow widths store
        # lane-PACKED (p = 128//w logical rows per physical 128-lane row, see
        # ops/packed_slab.py) so row gathers/scatters hit XLA's full-tile
        # fast path; each table starts at a physical-row boundary.
        widths = sorted({int(c["output_dim"])
                         for cfgs in self.strategy.local_configs_list
                         for c in cfgs})
        self.widths: List[int] = widths
        # row_offsets_list[rank][m] = first LOGICAL row of local table m
        self.row_offsets_list: List[List[int]] = []
        per_rank_rows = []  # [rank][width] -> logical rows used (aligned)
        for cfgs in self.strategy.local_configs_list:
            used = {w: 0 for w in widths}
            offsets = []
            for c in cfgs:
                w = int(c["output_dim"])
                offsets.append(used[w])
                used[w] += ps.align_rows(int(c["input_dim"]), w)
            self.row_offsets_list.append(offsets)
            per_rank_rows.append(used)
        self.rows_cap: Dict[int, int] = {
            w: max(max(max(r[w] for r in per_rank_rows), 1),
                   ps.pack_factor(w)) for w in widths}
        # physical slab geometry per width
        self.phys_cap: Dict[int, int] = {
            w: ps.packed_shape(ps.align_rows(self.rows_cap[w], w), w)[0]
            for w in widths}
        self.phys_w: Dict[int, int] = {w: ps.phys_width(w) for w in widths}
        self.rows_cap = {w: ps.align_rows(self.rows_cap[w], w)
                         for w in widths}
        # exchange plans are (input signature, batch)-dependent; built lazily
        self._plan_cache: Dict[tuple, plan_mod.ExchangePlan] = {}
        # the explicit step schedule the orchestrator runs and the
        # schedule auditor certifies (parallel/schedule.py): phase names,
        # declared ordering, declared overlap. The default is the honest
        # serialized baseline — with the one overlap streaming programs
        # ALREADY have (the admission-staging chain hides the out/grad
        # exchanges) declared when dynamic tables exist, so
        # tools/schedule_audit.py certifies it against the compiled DAG.
        # schedule="pipelined" (or a pipelined_schedule(K)) opts the
        # trainer into the K-microbatch latency-hiding step; K=1 and the
        # default trace the bitwise-identical serialized program.
        self.schedule = schedule_mod.resolve_schedule(
            schedule, streaming=bool(self.streaming_tables))

    # ------------------------------------------------------------------ params

    def _init_rank_width(self, key, rank: int, width: int, dtype) -> jax.Array:
        """One rank's PACKED slab for one width: per-table initializers
        stacked row-major at physical-row boundaries; column slices
        initialize independently like the reference's per-slice layers
        (``dist_model_parallel.py:256-259``).

        The *default* initializer (an elementwise uniform) is generated
        directly in the packed physical shape — reshaping a logical
        ``[rows, w]`` slab on device would force a lane-padded T(8,128)
        intermediate (8x memory for w=16, an instant OOM at zoo scale), and
        for an elementwise distribution the layout is immaterial. A
        *user-supplied* initializer keeps its documented contract: it is
        called with the logical ``(rows, w)`` shape (shape-dependent
        initializers like ``variance_scaling`` see the true fan-in/out) and
        the result is packed with strided slices, avoiding the padded
        reshape."""
        p = ps.pack_factor(width)
        pw = self.phys_w[width]
        cfgs = self.strategy.local_configs_list[rank]
        # tables write into a preallocated slab (in-place update chain under
        # jit) instead of list+concat: concat would hold all parts AND the
        # result live at once — 2x the slab in HBM, an OOM at uncapped
        # Criteo scale (8.7 GB of bf16 tables)
        buf = jnp.zeros((self.phys_cap[width], pw), dtype)
        pos = 0
        for m, cfg in enumerate(cfgs):
            if int(cfg["output_dim"]) != width:
                continue
            user_init = cfg.get("embeddings_initializer")
            rows = int(cfg["input_dim"])
            rows_al = ps.align_rows(rows, width)
            if user_init is None:
                t = default_embeddings_init(
                    jax.random.fold_in(key, m),
                    (rows_al // p, p * width), dtype)
            else:
                t = user_init(jax.random.fold_in(key, m), (rows, width),
                              dtype)
                if rows_al - rows:
                    t = jnp.concatenate(
                        [t, jnp.zeros((rows_al - rows, width), dtype)])
                if p > 1:  # pack: phys row i, lane j <- logical row i*p+j
                    t = jnp.concatenate([t[j::p] for j in range(p)], axis=1)
            # dynamic_update_slice would silently clamp an overrun into the
            # previous table's rows; fail loudly on planner/capacity drift
            assert pos + t.shape[0] <= self.phys_cap[width], (
                width, pos, t.shape, self.phys_cap[width])
            buf = lax.dynamic_update_slice(buf, t.astype(dtype), (pos, 0))
            pos += t.shape[0]
        return buf

    def init(self, key, dtype=jnp.float32, mesh=None) -> EmbedParams:
        """Build the global param dict ``{width: [world, rows_cap, width]}``.

        With ``mesh`` given, each device's shard is initialized by its own
        small program and assembled with
        ``jax.make_array_from_single_device_arrays`` — no single jit ever
        materializes more than one rank's slab (the reference forces huge
        inits off-accelerator for the same reason, ``embedding.py:28-38``),
        and on multi-host meshes each process initializes only its
        addressable shards.

        Fast path: a width group whose tables ALL use the default initializer
        (an elementwise uniform) is generated as ONE partitioned
        ``jax.random.uniform`` over the whole ``[world, phys_cap, phys_w]``
        slab — one small compile regardless of table count (the per-table
        path compiles O(tables) HLO per device and dominated colossal-scale
        startup). Layout padding rows/lanes then hold random values instead
        of zeros; nothing reads them (forward clips ids in-table, checkpoint
        paths slice exact row ranges).
        """
        keys = jax.random.split(key, self.world_size)

        default_widths = {
            w: all(c.get("embeddings_initializer") is None
                   for cfgs in self.strategy.local_configs_list
                   for c in cfgs if int(c["output_dim"]) == w)
            for w in self.widths}

        def fast_uniform(w, sharding=None):
            shape = (self.world_size, self.phys_cap[w], self.phys_w[w])
            fn = jax.jit(
                lambda k: default_embeddings_init(k, shape, dtype),
                **({"out_shardings": sharding} if sharding is not None else {}))
            return fn(jax.random.fold_in(key, w))

        if mesh is None:
            out = {}
            slow = [w for w in self.widths if not default_widths[w]]
            for w in self.widths:
                if default_widths[w]:
                    out[_wkey(w)] = fast_uniform(w)
            if slow:
                def build():
                    return {
                        _wkey(w): jnp.stack([
                            self._init_rank_width(keys[r], r, w, dtype)
                            for r in range(self.world_size)])
                        for w in slow}
                out.update(jax.jit(build)())
            return out

        out = {}
        for w in self.widths:
            if default_widths[w]:
                sharding = jax.sharding.NamedSharding(
                    mesh, jax.sharding.PartitionSpec(self.axis_name))
                out[_wkey(w)] = fast_uniform(w, sharding)
                continue

            def init_shard(dev, r0, r1, w=w):
                def build(ks):
                    return jnp.stack([
                        self._init_rank_width(ks[r], r, w, dtype)
                        for r in range(r0, r1)])
                with jax.default_device(dev):
                    shard = jax.jit(build)(keys)
                # default_device does not bind committed inputs (a committed
                # PRNG key would drag every shard to its own device); commit
                # the result explicitly (no-copy when already on dev)
                return jax.device_put(shard, dev)

            out[_wkey(w)] = self._assemble_sharded(mesh, w, init_shard)
        return out

    def _assemble_sharded(self, mesh, width: int, build_shard) -> jax.Array:
        """Assemble one width's global packed ``[world, phys_cap, phys_w]``
        slab from per-device shards built by ``build_shard(dev, r0, r1)`` —
        only this process's addressable shards are materialized (multi-host
        safe)."""
        shape = (self.world_size, self.phys_cap[width], self.phys_w[width])
        sharding = jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec(self.axis_name))
        arrays = []
        for dev, idx in sharding.devices_indices_map(shape).items():
            if dev.process_index != jax.process_index():
                continue
            r0, r1, _ = idx[0].indices(self.world_size)
            arrays.append(build_shard(dev, r0, r1))
        return jax.make_array_from_single_device_arrays(
            shape, sharding, arrays)

    def local_view(self, params: EmbedParams) -> EmbedParams:
        """Squeeze the leading world axis of per-device slabs
        (``[1, rows, w]`` inside shard_map / world_size==1 → ``[rows, w]``).
        Tree-mapped so nested optimizer state (e.g. Adam's ``(m, v, t)``)
        squeezes leaf-wise."""
        return jax.tree.map(
            lambda v: (v.reshape(v.shape[-2], v.shape[-1])
                       if hasattr(v, "ndim") and v.ndim == 3 else v), params)

    def stacked_view(self, params: EmbedParams) -> EmbedParams:
        """Re-add the leading world axis for P(axis) out_specs."""
        return jax.tree.map(
            lambda v: (v.reshape(1, *v.shape)
                       if hasattr(v, "ndim") and v.ndim == 2 else v), params)

    def _table_rows(self, rank: int, m: int):
        cfg = self.strategy.local_configs_list[rank][m]
        w = int(cfg["output_dim"])
        roff = self.row_offsets_list[rank][m]
        return _wkey(w), roff, int(cfg["input_dim"]), w

    # ----------------------------------------------------------------- forward

    @staticmethod
    def _dense_enc(shape, comb) -> tuple:
        """Static routing descriptor of a dense input: ``("d", hotness,
        num_slots)``. With a combiner the LAST dim is the reduced hotness
        and every lead position beyond the batch becomes its own slot (the
        reference flattens N-D inputs through its exchange and lets the
        local layer reduce the trailing dim, ``dist_model_parallel.py:
        273-288`` + ``embedding.py:115-132``); without one, every id is a
        hotness-1 slot."""
        dims = tuple(int(d) for d in shape[1:])
        if comb:
            h = dims[-1] if dims else 1
            ns = int(np.prod(dims[:-1], dtype=np.int64)) if len(dims) > 1 \
                else 1
            return ("d", h, ns)
        ns = int(np.prod(dims, dtype=np.int64)) if dims else 1
        return ("d", 1, ns)

    @staticmethod
    def _enc_of_hot(h) -> tuple:
        """MpInputs ``hots`` entry -> routing descriptor: an int is a 2-D
        dense hotness; tuples pass through (``("r"|"rw", cap)`` ragged,
        ``("d", hot, num_slots)`` N-D dense)."""
        if isinstance(h, (tuple, list)):
            k = h[0]
            if k == "d":
                return ("d", int(h[1]), int(h[2]) if len(h) > 2 else 1)
            return (k, int(h[1]))
        return ("d", int(h), 1)

    @staticmethod
    def _weight_bits(weights, cap: int, comm_dtype) -> jax.Array:
        """Per-id float weights -> int payload that rides the id exchange
        (bitcast f32->i32; widening to an int64 block preserves the bits)."""
        w = jnp.asarray(weights).astype(jnp.float32).reshape(cap)
        return lax.bitcast_convert_type(w, jnp.int32).astype(comm_dtype)

    def check_inputs(self, inputs) -> Optional[int]:
        """Eager (host-side) ingestion validation — the enforcement point
        of ``invalid_id_policy='raise'`` and ``ragged_overflow_raise``.

        Counts negative / out-of-vocab ids per input against the GLOBAL
        table vocab, and ragged row lengths claiming more ids than their
        static capacity. Under the ``'raise'`` policy any invalid id
        raises :class:`~...utils.runtime.InvalidInputError` naming the
        input and the offending range; with ``ragged_overflow_raise`` any
        capacity overflow does too. ``None`` entries (multi-host
        ``pack_mp_inputs`` partial batches) are skipped.

        Returns the total invalid-id count, or ``None`` when any input is
        a tracer — inside a jitted step nothing can be read eagerly; there
        the in-step ``invalid_id_count`` / ``id_overflow`` metrics carry
        the signal and the resilient driver escalates on the host.

        Cost: one device→host fetch per input when ids live on device —
        the price the ``'raise'`` policy opts into (call it from the input
        pipeline, where ids are still host numpy, to pay nothing).
        """
        if isinstance(inputs, MpInputs):
            # already validated id-by-id inside pack_mp_inputs (host
            # numpy); the packed block cannot be re-attributed to inputs
            return None
        if len(inputs) != self.strategy.num_inputs:
            raise ValueError(
                f"Expected {self.strategy.num_inputs} inputs, "
                f"got {len(inputs)}")
        total = 0
        for i, inp in enumerate(inputs):
            if inp is None:
                continue
            tid = self.strategy.input_table_map[i]
            vocab = int(self.strategy.global_configs[tid]["input_dim"])
            if isinstance(inp, SparseIds):
                arrs = (inp.values, inp.indices)
                values, splits, cap = inp.values, None, None
            elif isinstance(inp, Ragged):
                arrs = (inp.values, inp.row_splits)
                values, splits = inp.values, inp.row_splits
                cap = int(np.shape(inp.values)[0])
            else:
                arrs = (inp,)
                values, splits, cap = inp, None, None
            if any(isinstance(a, jax.core.Tracer) for a in arrs):
                return None
            ids = np.asarray(values)
            if isinstance(inp, SparseIds):
                # padding positions are marked by row >= dense_shape[0]
                # and carry ARBITRARY values (the SparseIds contract) —
                # only live positions are checkable
                rows_coo = np.asarray(inp.indices)
                if rows_coo.ndim == 2:
                    rows_coo = rows_coo[:, 0]
                ids = ids[rows_coo < inp.dense_shape[0]]
            if splits is not None:
                sp = np.asarray(splits)
                nnz = int(sp.reshape(-1)[-1])
                if nnz > cap:
                    total += nnz - cap
                    if self.ragged_overflow_raise:
                        raise _runtime.InvalidInputError(
                            f"input {i}: ragged row lengths claim {nnz} "
                            f"ids > static capacity {cap} — "
                            f"{nnz - cap} id(s) would be silently "
                            "truncated (ragged_overflow_raise)")
                ids = ids.reshape(-1)[:min(nnz, cap)]
            if tid in self.streaming_tables:
                # streaming tables accept the UNBOUNDED external id
                # space by design (the slot map hashes them in-range);
                # only negatives are invalid
                bad = int((ids < 0).sum())
            else:
                bad = int(((ids < 0) | (ids >= vocab)).sum())
            if bad:
                total += bad
                if self.invalid_id_policy == "raise":
                    raise _runtime.InvalidInputError(
                        f"input {i} (table {tid}): {bad} id(s) outside "
                        f"[0, {vocab}) — min {int(ids.min())}, max "
                        f"{int(ids.max())} — under invalid_id_policy="
                        "'raise'")
        return total

    def _normalize_inputs(self, inputs):
        """Promote to a common int dtype; dense inputs flatten to 2-D
        ``[batch, -1]``, :class:`~..ops.embedding_lookup.Ragged` inputs
        become ``("r"|"rw", values [cap], lengths [batch][, weight_bits])``
        records. Returns ``(entries, encs, shapes)`` where ``encs[i]`` is
        the static routing descriptor (``("d", hotness, num_slots)`` /
        ``("r"|"rw", capacity)``, the key the exchange plan is built from)
        and ``shapes[i]`` is the original dense shape (``None`` for
        ragged) so single-worker lookups preserve the reference's local
        output ranks."""
        if len(inputs) != self.strategy.num_inputs:
            raise ValueError(
                f"Expected {self.strategy.num_inputs} inputs, got {len(inputs)}")
        if self.invalid_id_policy == "raise" or self.ragged_overflow_raise:
            # the single ingestion point: eager callers (and the mp pack)
            # get host-side raises; traced callers fall through to the
            # invalid_id_count / id_overflow metrics (check_inputs
            # returns None on tracers)
            self.check_inputs(inputs)
        # COO sparse rides the ragged path: row ids -> CSR row_splits, the
        # same conversion the op layer's dispatcher does
        # (ops/embedding_lookup.py:row_to_split; reference
        # embedding_lookup_ops.py:90-96)
        inputs = [
            Ragged(values=inp.values,
                   row_splits=row_to_split(inp.indices, inp.dense_shape[0],
                                           dtype=inp.values.dtype),
                   weights=inp.weights)
            if isinstance(inp, SparseIds) else inp
            for inp in inputs]
        comm_dtype = jnp.int32
        for inp in inputs:
            arrs = ((inp.values, inp.row_splits) if isinstance(inp, Ragged)
                    else (inp,))
            if any(jnp.asarray(a).dtype == jnp.int64 for a in arrs):
                comm_dtype = jnp.int64
        out, encs, shapes = [], [], []
        for i, inp in enumerate(inputs):
            tid = self.strategy.input_table_map[i]
            comb = self.strategy.global_configs[tid].get("combiner")
            if isinstance(inp, Ragged):
                if not comb:
                    raise ValueError(
                        f"Ragged input {i} requires its table to have a "
                        "combiner (reference routes multi-hot ragged through "
                        "the combining kernel, embedding_lookup_ops.py:79-80)")
                values = jnp.asarray(inp.values).astype(comm_dtype)
                splits = jnp.asarray(inp.row_splits)
                lengths = (splits[1:] - splits[:-1]).astype(comm_dtype)
                cap = int(values.shape[0])
                if inp.weights is not None:
                    out.append(("rw", values, lengths,
                                self._weight_bits(inp.weights, cap,
                                                  comm_dtype)))
                    encs.append(("rw", cap))
                else:
                    out.append(("r", values, lengths))
                    encs.append(("r", cap))
                shapes.append(None)
            else:
                inp = jnp.asarray(inp).astype(comm_dtype)
                shapes.append(tuple(inp.shape))
                encs.append(self._dense_enc(inp.shape, comb))
                out.append(inp.reshape(inp.shape[0], -1) if inp.ndim != 1
                           else inp[:, None])
        return out, encs, shapes

    def pack_mp_inputs(self, inputs, dtype=None, mesh=None,
                       hots: Optional[Sequence[Any]] = None,
                       local_batch: Optional[int] = None,
                       as_numpy: bool = False) -> MpInputs:
        """Pack per-feature global-batch ids into :class:`MpInputs`.

        ``inputs[i]`` is ``[global_batch]`` / ``[global_batch, hotness]``
        dense ids, or a :class:`~..ops.embedding_lookup.Ragged` over the
        *global* batch (values ``[cap]``, row_splits ``[global_batch+1]``),
        ordered by data-parallel shard (shard ``s`` owns rows
        ``s*b:(s+1)*b``) — the natural order of a global batch. Host-side
        numpy; with ``mesh`` given the packed array is laid out sharded over
        ``axis_name`` so each device receives only its own block.

        On a multi-host data pipeline each process only needs the features its
        ranks own (reference ``examples/dlrm/main.py:166-176`` reads only the
        local tables' ``cat_*.bin``); entries for other ranks' features may be
        ``None`` — their packed blocks live on other processes' devices. In
        that case pass ``hots`` (per-input encoding of ALL inputs: an int
        hotness for dense, ``("r", per_shard_capacity)`` for ragged) and, if
        every entry is None, ``local_batch`` too: the packed layout must be
        identical on every process, so it cannot be inferred from local
        arrays alone.

        Ragged per-shard capacity: by default a global-batch ``Ragged`` input
        is packed with per-shard capacity equal to its *global* capacity
        (always safe; padded). Pass ``("r", cap)`` in ``hots`` to use a
        tighter static capacity — it must be the same on every process and
        every batch, and each shard's actual nnz must fit it (checked).

        Args:
          dtype: id dtype of the packed block; default promotes like the dp
            path (int64 if any provided array is int64, else int32).
          as_numpy: return the packed block as host numpy (no device
            conversion) — for pipeline benchmarking/staging where the
            caller owns placement. Mutually exclusive with ``mesh``.
        """
        if as_numpy and mesh is not None:
            raise ValueError("as_numpy=True returns a host array; it "
                             "cannot also be laid out on a mesh")
        world = self.world_size
        arrs = []
        for x in inputs:
            if x is None or isinstance(x, Ragged):
                arrs.append(x)
            else:
                a = np.asarray(x)
                arrs.append(a[:, None] if a.ndim == 1 else a)
        if len(arrs) != self.strategy.num_inputs:
            raise ValueError(
                f"Expected {self.strategy.num_inputs} inputs, got {len(arrs)}")
        if self.invalid_id_policy == "raise" or self.ragged_overflow_raise:
            # mp ingestion point: ids are host numpy here, so the 'raise'
            # policy costs nothing extra (None entries skipped)
            self.check_inputs(arrs)

        def glen(a):
            return (a.row_splits.shape[0] - 1 if isinstance(a, Ragged)
                    else a.shape[0])

        some = next((a for a in arrs if a is not None), None)
        if some is None:
            if local_batch is None or hots is None:
                raise ValueError(
                    "pack_mp_inputs with all-None inputs needs explicit "
                    "hots= and local_batch= (layout must match the owning "
                    "processes)")
            b = int(local_batch)
        else:
            gb = glen(some)
            if gb % world:
                raise ValueError(
                    f"Global batch {gb} not divisible by world size {world}")
            b = gb // world
            if local_batch is not None and int(local_batch) != b:
                raise ValueError(
                    f"local_batch={local_batch} contradicts inputs ({b})")
            for i, a in enumerate(arrs):
                if a is not None and glen(a) != gb:
                    raise ValueError(
                        f"Input {i} batch {glen(a)} != {gb}")
        def is64(a):
            if isinstance(a, Ragged):
                # same promotion rule as the dp path's _normalize_inputs
                return any(np.asarray(x).dtype == np.int64
                           for x in (a.values, a.row_splits))
            return a.dtype == np.int64

        if dtype is None:
            dtype = (jnp.int64 if any(a is not None and is64(a) for a in arrs)
                     else jnp.int32)

        # per-input encodings, hots-validated
        if hots is None and any(a is None for a in arrs):
            raise ValueError(
                "pack_mp_inputs with None entries needs explicit hots= "
                "(the encoding of every input must be globally known)")
        encs = []
        for i, a in enumerate(arrs):
            comb = self.strategy.global_configs[
                self.strategy.input_table_map[i]].get("combiner")
            if hots is not None:
                enc = self._enc_of_hot(hots[i])
            elif isinstance(a, Ragged):
                enc = (("rw" if a.weights is not None else "r"),
                       int(a.capacity))
            else:
                enc = self._dense_enc(a.shape, comb)
            if a is not None:
                if isinstance(a, Ragged) != (enc[0] in ("r", "rw")):
                    raise ValueError(
                        f"Input {i} encoding {enc} does not match the "
                        f"provided value type")
                if isinstance(a, Ragged) and \
                        (a.weights is not None) != (enc[0] == "rw"):
                    raise ValueError(
                        f"Input {i}: weighted ragged needs an ('rw', cap) "
                        f"hots entry, got {enc}")
                if enc[0] == "d":
                    canon = self._dense_enc(a.shape, comb)
                    # plan-equivalence, not tuple equality: without a
                    # combiner ("d", h, ns) and ("d", 1, h*ns) build the
                    # same hotness-1 slot layout (the legacy int-hots form)
                    ok = (enc[1:] == canon[1:] if comb
                          else enc[1] * enc[2] == canon[1] * canon[2])
                    if not ok:
                        raise ValueError(
                            f"Input {i} shape {a.shape} does not match "
                            f"hots[{i}]={hots[i] if hots else enc}")
            encs.append(enc)

        plan = self._get_plan(encs, b)
        np_dtype = np.dtype(jnp.dtype(dtype).name)
        packed_np = np.zeros((world, world, plan.l_max), np_dtype)
        for inst in plan.instances:
            a = arrs[inst.input_id]
            if a is None:
                continue
            g = plan.groups[inst.group]
            p0 = g.goff + inst.slot0 * g.blen
            span = inst.num_slots * g.blen
            if g.kind in ("r", "rw"):
                values = np.asarray(a.values)
                splits = np.asarray(a.row_splits)
                cap = g.hot
                for s in range(world):
                    lo, hi = int(splits[s * b]), int(splits[(s + 1) * b])
                    if hi - lo > cap:
                        raise ValueError(
                            f"Input {inst.input_id}: shard {s} nnz {hi - lo} "
                            f"exceeds per-shard capacity {cap}")
                    blk = np.zeros(g.blen, np_dtype)
                    blk[:hi - lo] = values[lo:hi]
                    blk[cap:cap + b] = np.diff(splits[s * b:(s + 1) * b + 1])
                    if g.kind == "rw":  # bitcast f32 weights into the block
                        wb = np.zeros(cap, np.float32)
                        wb[:hi - lo] = np.asarray(a.weights, np.float32
                                                  )[lo:hi]
                        blk[cap + b:] = wb.view(np.int32)
                    packed_np[inst.rank, s, p0:p0 + span] = blk
            else:
                # one vectorized slice-assign for all shards (a per-shard
                # python loop measured 10.5 ms/batch at the v5e-16 bench
                # shapes; this form is one numpy memcpy per feature)
                if inst.transposed:  # slot-major within each shard block
                    flat = (a.reshape(world, b, inst.num_slots, g.hot)
                            .transpose(0, 2, 1, 3).reshape(world, -1))
                else:
                    flat = a.reshape(world, -1)
                packed_np[inst.rank, :, p0:p0 + span] = flat
        if as_numpy:
            # host-side packing only (pipeline benchmarking / staging):
            # the caller owns the device placement
            packed = packed_np
        elif mesh is not None:
            sharding = jax.sharding.NamedSharding(
                mesh, jax.sharding.PartitionSpec(self.axis_name))
            # callback-per-shard works on multi-host meshes too: each process
            # materializes only its addressable blocks
            packed = jax.make_array_from_callback(
                packed_np.shape, sharding, lambda idx: packed_np[idx])
        else:
            packed = jnp.asarray(packed_np)
        hots_out = tuple(
            (enc[1] if enc[2] == 1 else enc) if enc[0] == "d" else enc
            for enc in encs)
        return MpInputs(packed=packed, hots=hots_out, local_batch=b)

    def __call__(self, params: EmbedParams, inputs) -> List[jax.Array]:
        """Forward pass.

        * ``world_size == 1``: plain local lookups, original output ranks
          preserved (reference ``call``, ``:493-500``).
        * distributed: must run inside ``shard_map`` with ``axis_name`` bound;
          ``params`` are this device's slabs (pass the global dict through
          ``in_specs=P(axis_name)``).
        """
        return self.forward_with_residuals(params, inputs)[0]

    def forward_with_residuals(self, params: EmbedParams, inputs,
                               streaming=None, phase_tag: str = ""):
        """Forward pass that also returns the routing residuals needed by
        :meth:`sparse_apply_gradients` (the manual sparse backward).

        Residuals carry the *model-parallel-side* ids (post-exchange), so the
        backward never re-runs the id all-to-all — mirroring how the reference
        backward reuses the forward op's inputs
        (``embedding_lookup_ops.py:116-122``).

        ``streaming``: dynamic-vocab mode (:mod:`.streaming`) —
        ``(config, state)`` remaps every streaming-table slot's external
        ids through this device's jit-carried slot map right after the
        id exchange (slot-map hits read their admitted slot, everything
        else reads its shared hash bucket) and STAGES this step's
        admission/eviction transitions; the return grows a third
        element, the per-width ``pending`` dict the trainer hands to
        :func:`.streaming.commit` next to the nan-guard.
        ``(config, state, False)`` is the read-only form (eval): remap
        only, no transitions, 2-tuple return.
        ``(config, state, "serve")`` is the pipelined trainer's
        per-microbatch form: read-only remap (each microbatch's lookup
        depends only on its own id exchange, never on the admission
        staging) PLUS a third return element — the raw per-width
        external-id :class:`~.streaming.WidthStream`\\ s of this call,
        which the trainer concatenates across microbatches and hands to
        :meth:`streaming_stage` for the ONE staging pass whose decisions
        are bitwise the serialized step's. The residuals carry the
        REMAPPED block, so the sparse backward, step metrics, and
        telemetry all operate on in-range internal rows.

        ``phase_tag`` suffixes every phase scope of this forward (the
        pipelined step's ``_mb{k}`` microbatch instances); empty (the
        default) leaves the serialized program's scopes — and therefore
        its compiled text — byte-identical to before.
        """
        params = self.local_view(params)

        if self.world_size == 1:
            # Single worker runs the SAME plan-driven lookup, minus the
            # exchanges: one gather+combine per (width, hotness) group
            # instead of a per-table loop (tiny zoo: 57 chains -> 4; the
            # batched ops amortize the per-chain pipeline overheads) and one
            # shared code path with the distributed executor. Reference
            # parity of output ranks (``call``, ``:493-500``) is restored
            # from the plan's flat [b, h*w] slots below.
            if isinstance(inputs, MpInputs):
                raise ValueError(
                    "world_size == 1 takes a plain input list (mp and dp "
                    "input coincide)")
            entries, encs, shapes = self._normalize_inputs(inputs)
            b = (entries[0][2].shape[0] if isinstance(entries[0], tuple)
                 else entries[0].shape[0])
            comm_dtype = (entries[0][1].dtype if isinstance(entries[0], tuple)
                          else entries[0].dtype)
            plan = self._get_plan(encs, b)
            ids_recv = exchange_mod.build_send_blocks(self, plan, entries,
                                                      comm_dtype)
            ids_recv, spending = self._streaming_remap(plan, ids_recv,
                                                       streaming,
                                                       tag=phase_tag)
            # slot-major group outputs: per-instance outputs are plain
            # slices, skipping the exchange-row transpose the single
            # worker never needs (only multi-slot instances pay a small
            # per-instance transpose)
            reds = lookup_mod.plan_lookup_groups(self, plan, params,
                                                 ids_recv, tag=phase_tag)
            outs = []
            for inst in plan.instances:  # worker order == input order here
                g = plan.groups[inst.group]
                red = reds[inst.group]  # [1, n, b, w]
                if inst.num_slots == 1:
                    o = red[0, inst.slot0]
                else:
                    o = lax.slice(
                        red, (0, inst.slot0, 0, 0),
                        (1, inst.slot0 + inst.num_slots, b, g.width)
                    )[0].transpose(1, 0, 2).reshape(b, -1)
                enc = encs[inst.input_id]
                shape = shapes[inst.input_id]
                # single-worker parity with the reference's local `call`
                # (:493-500): dense outputs keep the input's rank —
                # no combiner: shape[1:] + (w,); combiner: the lead dims
                # survive the trailing-dim reduction
                if enc[0] == "d" and shape is not None and len(shape) >= 2:
                    comb = self.strategy.global_configs[
                        self.strategy.input_table_map[inst.input_id]
                    ].get("combiner")
                    lead = shape[1:] if comb is None else shape[1:-1]
                    if comb is None or lead:
                        o = o.reshape((b,) + tuple(lead) + (g.width,))
                outs.append(o)
            result = [outs[i] for i in self.strategy.rev_global_input_ids]
            res = ("dist", ids_recv, tuple(encs), b)
            return ((result, res, spending) if spending is not None
                    else (result, res))

        world = self.world_size
        if self.dp_input:
            entries, encs, _ = self._normalize_inputs(inputs)

            def batch_of(e):
                return e[2].shape[0] if isinstance(e, tuple) else e.shape[0]

            b = batch_of(entries[0])
            for e in entries:
                if batch_of(e) != b:
                    raise ValueError("All inputs must share the batch dimension")
            comm_dtype = (entries[0][1].dtype if isinstance(entries[0], tuple)
                          else entries[0].dtype)
            plan = self._get_plan(encs, b)

            # --- dp -> mp id exchange (schedule phase "id_all_to_all",
            # parallel/exchange.py) -----------------------------------------
            ids_recv = exchange_mod.exchange_ids(self, plan, entries,
                                                 comm_dtype, tag=phase_tag)
        else:
            # --- model-parallel input: this rank already holds the global
            # batch of ids for its local tables; no id exchange runs
            # (reference :213,267: mp input skips the alltoall entirely).
            if not isinstance(inputs, MpInputs):
                raise ValueError(
                    "dp_input=False requires an MpInputs batch; build one "
                    "with pack_mp_inputs()")
            if len(inputs.hots) != self.strategy.num_inputs:
                raise ValueError(
                    f"Expected {self.strategy.num_inputs} hotness entries, "
                    f"got {len(inputs.hots)}")
            encs = [self._enc_of_hot(h) for h in inputs.hots]
            b = int(inputs.local_batch)
            plan = self._get_plan(encs, b)
            ids_recv = inputs.packed
            if ids_recv.ndim == 3:  # [1, world, l_max] shard inside shard_map
                ids_recv = ids_recv.reshape(ids_recv.shape[-2],
                                            ids_recv.shape[-1])
            if ids_recv.shape != (world, plan.l_max):
                raise ValueError(
                    f"MpInputs packed shape {ids_recv.shape} does not match "
                    f"the plan layout {(world, plan.l_max)}; repack with "
                    "pack_mp_inputs() from this DistributedEmbedding")
            if not jnp.issubdtype(ids_recv.dtype, jnp.integer):
                ids_recv = ids_recv.astype(jnp.int32)

        # --- streaming remap (dynamic-vocab tables) ------------------------
        ids_recv, spending = self._streaming_remap(plan, ids_recv, streaming,
                                                   tag=phase_tag)

        # --- rank-uniform local lookup (schedule phase family
        # "lookup_*", parallel/lookup.py) -----------------------------------
        mp_out = lookup_mod.plan_lookup(self, plan, params, ids_recv,
                                        tag=phase_tag)  # [world, b, s_max]

        # --- mp -> dp output exchange (schedule phase "out_all_to_all",
        # parallel/exchange.py) ---------------------------------------------
        dp_recv = exchange_mod.exchange_outputs(self, mp_out, tag=phase_tag)
        # dp_recv[r] = this rank's batch as computed by source rank r.

        # --- unpack (static slices), reorder, concat column slices ---------
        worker_order: List[jax.Array] = []
        for inst in plan.instances:
            g = plan.groups[inst.group]
            c0 = g.col + inst.slot0 * g.width
            ow = inst.num_slots * g.width
            worker_order.append(
                lax.slice(dp_recv, (inst.rank, 0, c0),
                          (inst.rank + 1, b, c0 + ow)).reshape(b, ow))
        result = [worker_order[i] for i in self.strategy.rev_global_input_ids]
        # reassemble slices in ascending input order (in-place collapse
        # invariant, strategy.create_sliced_configs): column slices
        # concatenate; row slices SUM (out-of-range reads were zeroed)
        ranges = (
            [(s, e, "cat") for s, e in self.strategy.sliced_out_ranges]
            + [(s, e, "sum")
               for s, e in self.strategy.row_sliced_out_ranges])
        for start, end, kind in sorted(ranges):
            if kind == "cat":
                result[start:end] = [
                    jnp.concatenate(result[start:end], axis=-1)]
            else:
                total = result[start]
                for part in result[start + 1:end]:
                    total = total + part
                result[start:end] = [total]
        res = ("dist", ids_recv, tuple(encs), b)
        return ((result, res, spending) if spending is not None
                else (result, res))

    # ------------------------------------------------- plan-driven executor

    def _get_plan(self, encs, b: int) -> plan_mod.ExchangePlan:
        key = (tuple(encs), int(b))
        p = self._plan_cache.get(key)
        if p is None:
            p = plan_mod.build_plan(self.strategy, self.row_offsets_list,
                                    encs, int(b))
            self._plan_cache[key] = p
        return p

    def _my_rank(self):
        """Mesh position under shard_map; static 0 for a single worker
        (which runs outside any mesh axis)."""
        return (lax.axis_index(self.axis_name) if self.world_size > 1 else 0)

    def _vary(self, x: jax.Array) -> jax.Array:
        """Mark a constant device-varying over the mesh axis so it can join
        varying values in collectives/switch branches under shard_map's VMA
        typing; identity for the single-worker (no mesh axis) path."""
        if self.world_size == 1:
            return x
        return lax.pcast(x, self.axis_name, to="varying")

    def _plan_row(self, arr: np.ndarray, my) -> jax.Array:
        """This device's row of a ``[world, n]`` plan tensor. The tensor is a
        baked program constant; indexing it by ``lax.axis_index`` is what
        replaces rank-specialized branches."""
        c = self._vary(jnp.asarray(arr))
        return lax.dynamic_index_in_dim(c, my, keepdims=False)

    # ------------------------------------------------------ sparse backward

    def sparse_apply_gradients(self, params: EmbedParams, opt_state, residuals,
                               out_grads, optimizer, lr, scale=None,
                               enable=None):
        """Manual sparse backward + in-place optimizer update.

        Replaces autodiff w.r.t. the parameter slabs: ``out_grads`` are the
        cotangents of this layer's *outputs* (obtained by differentiating the
        dense model w.r.t. the embedding activations), routed back through the
        reverse output all-to-all and applied as per-row scatter updates —
        never materializing dense table gradients. This is the IndexedSlices
        pipeline of the reference (``dist_model_parallel.py:526-567`` + the
        grad kernel) in SPMD form.

        Args:
          params: this device's slabs (any leading world axis squeezed).
          opt_state: optimizer slab state from ``optimizer.init``.
          residuals: second output of :meth:`forward_with_residuals`.
          out_grads: list of cotangents matching the forward outputs.
          optimizer: :class:`~.optimizers.SparseSGD` /
            :class:`~.optimizers.SparseAdagrad`.
          lr: learning rate (scalar or traced).
          scale: gradient pre-scale; defaults to ``1/world_size``, matching the
            reference's mp-gradient scaling (``dist_model_parallel.py:542-546``)
            under a pmean-averaged data-parallel loss.
          enable: optional traced scalar bool — when False the whole update
            is skipped with slabs and slab-shaped optimizer state bitwise
            unchanged (every update row routes to the dropped sentinel; see
            :func:`~.apply.apply_width_streams`). The trainer's non-finite
            guard
            passes its finiteness verdict here.

        Returns:
          ``(new_params, new_opt_state)``.
        """
        return apply_mod.sparse_apply_gradients(
            self, params, opt_state, residuals, out_grads, optimizer,
            lr, scale=scale, enable=enable)

    # --------------------------------------------------------- observability

    def step_metrics(self, residuals, out_dtype=None) -> Dict[str, jax.Array]:
        """On-device exchange/overflow metrics of one forward, derived from
        the :meth:`forward_with_residuals` residuals — a handful of sums
        over tensors the step already holds (near-zero cost), jit-safe.

        Returns a plain dict (see :data:`~..utils.obs.STEP_METRIC_KEYS` for
        the full step-metrics schema; the grad-norm/loss/step entries are
        added by the trainer, which holds those values). Every entry is a
        per-device ``[1]`` array so that under ``shard_map`` with
        ``out_specs=P(axis_name)`` the rows concatenate into per-rank
        ``[world]`` vectors:

        * ``ids_routed`` — live (non-padding) ids this rank received
          through the id exchange: the static dense-slot count plus the
          dynamic ragged totals (claimed lengths clamped to capacity).
        * ``id_overflow`` — ragged ids CLAIMED by the row lengths beyond
          the slot's static capacity: every unit here is an id the lookup
          silently dropped (the "ragged ids silently overflow ``CAP``"
          failure made visible). Zero on healthy batches.
        * ``invalid_id_count`` — negative / out-of-vocab ids among the
          live ids this rank received (what the ``invalid_id_policy``
          clamped or dropped; row-sliced slots excluded — each id is
          in-range on exactly one slice). Zero on healthy batches.
        * ``id_a2a_bytes`` / ``out_a2a_bytes`` / ``grad_a2a_bytes`` —
          bytes leaving this chip per step for the dp→mp id exchange, the
          mp→dp activation exchange, and the reverse cotangent exchange
          (static consequences of the plan layout, included so a metrics
          record prices the padded exchange exactly like
          ``analysis.plan_audit.audit_plan`` does).
        * ``out_pad_frac`` — dead-column fraction of this rank's rows in
          the output exchange (the placement-imbalance signal
          ``comm_balanced`` minimizes).

        Args:
          residuals: second output of :meth:`forward_with_residuals`.
          out_dtype: dtype of the exchanged activations (the trainer
            passes the cotangent dtype); defaults to ``compute_dtype``
            or float32.
        """
        _, ids_recv, encs, b = residuals
        plan = self._get_plan(list(encs), b)
        world = self.world_size
        my = self._my_rank()
        id_bytes = jnp.dtype(ids_recv.dtype).itemsize
        out_bytes = jnp.dtype(out_dtype or self.compute_dtype
                              or jnp.float32).itemsize

        # static per-rank tallies baked from the plan (indexed by
        # lax.axis_index like every other plan tensor)
        dense_live = np.zeros((world, 1), np.int32)
        live_cols = np.zeros((world, 1), np.int32)
        for inst in plan.instances:
            g = plan.groups[inst.group]
            live_cols[inst.rank, 0] += plan.out_width(inst)
            if g.kind == "d":
                dense_live[inst.rank, 0] += world * b * inst.num_slots * g.hot
        routed = self._plan_row(dense_live, my).astype(jnp.int32)
        overflow = routed * 0  # zero that inherits routed's varying type
        invalid = routed * 0
        for gi, g in enumerate(plan.groups):
            region = lax.slice(ids_recv, (0, g.goff),
                               (world, g.goff + g.n * g.blen))
            rows = self._plan_row(plan.rows[gi], my)  # [n] per-slot vocab
            # invalid-id counting skips dead slots (their zero-filled ids
            # would compare against rows=0) and row-sliced slots (a valid
            # id is in-range on exactly ONE of its k slices — per-slot
            # counting would tally k-1 phantom invalids per id)
            slot_ok = ((self._plan_row(plan.valid[gi], my) > 0)
                       & (self._plan_row(plan.rsliced[gi], my) == 0))
            if g.kind == "d":
                ids = region.reshape(world, g.n, b, g.hot)
                bad = (((ids < 0) | (ids >= rows[None, :, None, None]))
                       & slot_ok[None, :, None, None])
                invalid = invalid + jnp.sum(bad, dtype=jnp.int32).reshape(1)
                continue
            r3 = region.reshape(world, g.n, g.blen)
            values = r3[:, :, :g.hot]
            lengths = r3[:, :, g.hot:g.hot + b]
            tot = jnp.sum(lengths, axis=2, dtype=jnp.int32)  # [world, n]
            # dead slots carry zero lengths by construction (senders fill
            # dead cells with zeros), so no valid-mask is needed here
            routed = routed + jnp.sum(jnp.minimum(tot, g.hot)).reshape(1)
            overflow = overflow + jnp.sum(
                jnp.maximum(tot - g.hot, 0)).reshape(1)
            # live ragged positions are packed from position 0 (senders
            # zero-fill past nnz), so a position index < clamped total
            # marks a real id
            live = (jnp.arange(g.hot, dtype=jnp.int32)[None, None, :]
                    < jnp.minimum(tot, g.hot)[:, :, None])
            bad = (((values < 0) | (values >= rows[None, :, None]))
                   & live & slot_ok[None, :, None])
            invalid = invalid + jnp.sum(bad, dtype=jnp.int32).reshape(1)
        off_chip = float(world - 1)
        return {
            "ids_routed": routed,
            "id_overflow": overflow,
            "invalid_id_count": invalid,
            "id_a2a_bytes": self._vary(jnp.full(
                (1,), off_chip * plan.l_max * id_bytes, jnp.float32)),
            "out_a2a_bytes": self._vary(jnp.full(
                (1,), off_chip * b * plan.s_max * out_bytes, jnp.float32)),
            "grad_a2a_bytes": self._vary(jnp.full(
                (1,), off_chip * b * plan.s_max * out_bytes, jnp.float32)),
            "out_pad_frac": 1.0 - (
                self._plan_row(live_cols, my).astype(jnp.float32)
                / float(max(plan.s_max, 1))),
        }

    def update_telemetry(self, tstate, residuals, config):
        """Fold one forward's routed ids into jit-carried access
        telemetry (:mod:`~..analysis.telemetry`): per width slab, the
        count-min sketch + top-k hot-row merge over the live logical
        slab rows this rank received; plus the rank's cumulative
        routed-id load. Pure jax ops on tensors the step already holds
        — no collectives, no host interop, static shapes (zero
        steady-state recompiles).

        One emission point per ``(width, kind)`` exchange group, each
        under its own ``obs.scope`` so a profile prices telemetry per
        group; groups of equal width fold into one sketch update.

        Args:
          tstate: this device's telemetry state
            (:func:`~..analysis.telemetry.local_state` view).
          residuals: second output of :meth:`forward_with_residuals` —
            or a LIST of them (the pipelined step's per-microbatch
            residuals): the per-width id streams of every residual
            concatenate into ONE sketch fold and ONE top-k merge, so the
            counted traffic matches the serialized step's (the count-min
            scatter-add is associative; a per-microbatch fold would
            merge candidates against partially-folded estimates).
          config: a :class:`~..analysis.telemetry.TelemetryConfig`
            (trace-time static).

        Returns:
          the updated telemetry state (same structure).
        """
        from ..analysis import telemetry as tel

        res_list = ([residuals] if residuals and residuals[0] == "dist"
                    else list(residuals))
        world = self.world_size
        my = self._my_rank()
        per_width: Dict[int, tuple] = {}
        for residuals in res_list:
            _, ids_recv, encs, b = residuals
            plan = self._get_plan(list(encs), b)
            for gi, g in enumerate(plan.groups):
                with obs.scope(f"telemetry_w{g.width}_{g.kind}"):
                    region = lax.slice(ids_recv, (0, g.goff),
                                       (world, g.goff + g.n * g.blen))
                    rows = self._plan_row(plan.rows[gi], my)
                    roff = self._plan_row(plan.roff[gi], my)
                    slot_ok = self._plan_row(plan.valid[gi], my) > 0
                    rbase = (self._plan_row(plan.rbase[gi], my)
                             if plan.rsliced[gi].any() else None)
                    if g.kind == "d":
                        ids = region.reshape(world, g.n, b, g.hot)
                        loc = (ids - rbase[None, :, None, None]
                               if rbase is not None else ids)
                        # live = in-range on THIS slot: row-sliced slots
                        # count each id on exactly the slice that owns
                        # it, dead and out-of-vocab ids drop (they train
                        # nothing either)
                        live = ((loc >= 0)
                                & (loc < rows[None, :, None, None])
                                & slot_ok[None, :, None, None])
                        grow = loc + roff[None, :, None, None]
                    else:
                        r3 = region.reshape(world, g.n, g.blen)
                        values = r3[:, :, :g.hot]
                        lengths = r3[:, :, g.hot:g.hot + b]
                        tot = jnp.sum(lengths, axis=2, dtype=jnp.int32)
                        pos_live = (
                            jnp.arange(g.hot, dtype=jnp.int32)[None, None,
                                                               :]
                            < jnp.minimum(tot, g.hot)[:, :, None])
                        loc = (values - rbase[None, :, None]
                               if rbase is not None else values)
                        live = (pos_live & (loc >= 0)
                                & (loc < rows[None, :, None])
                                & slot_ok[None, :, None])
                        grow = loc + roff[None, :, None]
                    acc = per_width.setdefault(g.width, ([], []))
                    acc[0].append(grow.astype(jnp.int32).reshape(-1))
                    acc[1].append(live.reshape(-1))
        new = dict(tstate)
        total = jnp.zeros((1,), jnp.float32)
        for w in sorted(per_width):
            idl, livel = per_width[w]
            ids = jnp.concatenate(idl)
            live = jnp.concatenate(livel)
            with obs.scope(f"telemetry_update_w{w}"):
                new[_wkey(w)] = tel.record_ids(tstate[_wkey(w)], ids,
                                               live, config)
            total = total + jnp.sum(live, dtype=jnp.float32).reshape(1)
        new["steps"] = tstate["steps"] + 1
        new["ids_total"] = tstate["ids_total"] + total
        return new

    # -------------------------------------------------- streaming vocab

    def _streaming_plan_arrays(self, plan) -> list:
        """Per-group ``[world, n]`` plan tensors of the streaming remap
        (``parallel/streaming.py``): per slot, whether its table is
        dynamic, the slot capacity, the shared-bucket count, and the
        (plan-invariant hash salt) global table id. Baked once per plan
        like every other plan tensor — plans are cached for the process
        lifetime, so ``id(plan)`` is a stable cache key."""
        key = id(plan)
        cached = self._streaming_arrays_cache.get(key)
        if cached is not None:
            return cached
        world = self.world_size
        out = [(np.zeros((world, g.n), np.int32),
                np.ones((world, g.n), np.int32),
                np.ones((world, g.n), np.int32),
                np.zeros((world, g.n), np.int32))
               for g in plan.groups]
        for inst in plan.instances:
            tid = self.strategy.input_table_map[inst.input_id]
            info = self.streaming_tables.get(tid)
            if info is None:
                continue
            dyn_a, cap_a, nb_a, tid_a = out[inst.group]
            sl = slice(inst.slot0, inst.slot0 + inst.num_slots)
            dyn_a[inst.rank, sl] = 1
            cap_a[inst.rank, sl] = info[0]
            nb_a[inst.rank, sl] = info[1]
            tid_a[inst.rank, sl] = tid
        self._streaming_arrays_cache[key] = out
        return out

    def _streaming_remap(self, plan, ids_recv, streaming, tag: str = ""):
        """Remap every streaming-table slot's external ids in the
        received block through the jit-carried slot map
        (:func:`.streaming.remap_width`) and, in update mode, stage the
        admission/eviction transitions.

        ``streaming`` is ``None`` (no-op), ``(config, state)`` (train:
        remap + stage), ``(config, state, False)`` (read-only remap —
        the eval path admits nothing), or ``(config, state, "serve")``
        (the pipelined per-microbatch form: read-only remap that ALSO
        returns this call's raw per-width external-id streams, under
        ``streaming_serve_w{w}{tag}`` scopes so each microbatch's serve
        chain stays a distinct phase). Returns ``(ids_recv, pending)``
        with ``pending`` a ``{width: (new_wstate, scrub_rows, stats)}``
        dict in update mode, a ``{width: WidthStream}`` dict in serve
        mode (feed :meth:`streaming_stage`), else ``None``. Pure jax on
        tensors the step already holds; static shapes throughout (0
        steady-state recompiles); only the modified group regions are
        rewritten (static-offset ``dynamic_update_slice``)."""
        if streaming is None:
            return ids_recv, None
        from . import streaming as streaming_mod

        if not self.streaming_tables:
            raise ValueError(
                "streaming= passed but no table declares a 'streaming' "
                "config entry")
        if len(streaming) == 2:
            config, sstate = streaming
            update = True
        else:
            config, sstate, update = streaming
        serve = update == "serve"
        if serve:
            update = False
        arrays = self._streaming_plan_arrays(plan)
        world = self.world_size
        my = self._my_rank()
        b = plan.b
        per_width: Dict[int, list] = {}
        sites = []  # (gi, width, start-within-width-stream, original vals,
        #             write-back mask, region tail or None)
        for gi, g in enumerate(plan.groups):
            dyn_a, cap_a, nb_a, tid_a = arrays[gi]
            if not dyn_a.any():
                continue
            with obs.scope(f"streaming_remap_w{g.width}_{g.kind}{tag}"):
                region = lax.slice(ids_recv, (0, g.goff),
                                   (world, g.goff + g.n * g.blen))
                dyn = self._plan_row(dyn_a, my)
                cap = self._plan_row(cap_a, my)
                nb = self._plan_row(nb_a, my)
                tid = self._plan_row(tid_a, my)
                roff = self._plan_row(plan.roff[gi], my)
                if g.kind == "d":
                    vals = region.reshape(world, g.n, b, g.hot)
                    bshape = vals.shape
                    dynm = jnp.broadcast_to(
                        dyn[None, :, None, None] > 0, bshape)
                    ex = (cap[None, :, None, None],
                          nb[None, :, None, None],
                          tid[None, :, None, None],
                          roff[None, :, None, None])
                    tail = None
                else:
                    r3 = region.reshape(world, g.n, g.blen)
                    vals = r3[:, :, :g.hot]
                    lengths = r3[:, :, g.hot:g.hot + b]
                    tot = jnp.sum(lengths, axis=2, dtype=jnp.int32)
                    pos_live = (
                        jnp.arange(g.hot, dtype=jnp.int32)[None, None, :]
                        < jnp.minimum(tot, g.hot)[:, :, None])
                    bshape = vals.shape
                    dynm = pos_live & (dyn[None, :, None] > 0)
                    ex = (cap[None, :, None], nb[None, :, None],
                          tid[None, :, None], roff[None, :, None])
                    tail = r3[:, :, g.hot:]
                capb, nbb, tidb, roffb = (
                    jnp.broadcast_to(x, bshape) for x in ex)
                acc = per_width.setdefault(g.width, [])
                start = sum(p[0].size for p in acc)
                acc.append((vals.reshape(-1), dynm.reshape(-1),
                            capb.reshape(-1), nbb.reshape(-1),
                            tidb.reshape(-1), roffb.reshape(-1)))
                sites.append((gi, g.width, start, vals, dynm, tail))

        remapped: Dict[int, jax.Array] = {}
        pending: Dict[int, tuple] = {}
        for w in sorted(per_width):
            pieces = per_width[w]
            stream = streaming_mod.WidthStream(
                ext=jnp.concatenate([p[0] for p in pieces]),
                live=jnp.concatenate([p[1] for p in pieces]),
                cap=jnp.concatenate([p[2] for p in pieces]),
                nbuckets=jnp.concatenate([p[3] for p in pieces]),
                tid=jnp.concatenate([p[4] for p in pieces]),
                roff=jnp.concatenate([p[5] for p in pieces]))
            # the serve half runs under its own (per-microbatch) phase in
            # pipelined steps — it feeds this microbatch's lookup, so it
            # must never share the staging phase the schedule declares
            # independent of the out/grad exchanges
            scope_name = (f"streaming_serve_w{w}{tag}" if serve
                          else f"streaming_admit_w{w}")
            with obs.scope(scope_name):
                local_rows, pend = streaming_mod.remap_width(
                    sstate[_wkey(w)], stream, self.rows_cap[w], config,
                    update=update)
            remapped[w] = local_rows
            if serve:
                pending[w] = stream
            elif pend is not None:
                pending[w] = pend

        for gi, w, start, vals, dynm, tail in sites:
            g = plan.groups[gi]
            new = lax.slice(remapped[w], (start,),
                            (start + vals.size,)).reshape(vals.shape)
            # write-back keeps non-streaming slots (which may share the
            # group), dead positions, and negative ids byte-identical —
            # the remap never widens/narrows the block dtype
            new_vals = jnp.where(dynm & (vals >= 0),
                                 new.astype(vals.dtype), vals)
            if tail is None:
                region_new = new_vals.reshape(world, g.n * g.blen)
            else:
                region_new = jnp.concatenate(
                    [new_vals, tail], axis=2).reshape(world, g.n * g.blen)
            ids_recv = lax.dynamic_update_slice(ids_recv, region_new,
                                                (0, g.goff))
        return ids_recv, (pending if (update or serve) else None)

    def streaming_stage(self, width_streams, config, sstate):
        """The pipelined step's ONE admission-staging pass: concatenate
        the per-microbatch raw external-id streams (the ``"serve"``-mode
        third return of :meth:`forward_with_residuals`, one dict per
        microbatch) and run :func:`.streaming.remap_width` in update
        mode over the combined stream — exactly the serialized step's
        staging input, so the sketch fold, admission estimates, and
        deterministic claim resolution are BITWISE the serialized
        decisions (the max-scatter tie-breaks are order-independent for
        duplicate ids, and the count-min fold is a plain scatter-add).
        Runs under the same ``streaming_admit_w{w}`` scopes as the
        serialized staging, so the schedule's declared overlap names one
        phase in both programs. Returns the ``pending`` dict
        :func:`.streaming.commit` consumes."""
        from . import streaming as streaming_mod

        widths = sorted({w for ws in width_streams for w in ws})
        pending: Dict[int, tuple] = {}
        for w in widths:
            parts = [ws[w] for ws in width_streams if w in ws]
            stream = streaming_mod.WidthStream(
                *(jnp.concatenate([getattr(p, f) for p in parts])
                  for f in streaming_mod.WidthStream._fields))
            with obs.scope(f"streaming_admit_w{w}"):
                _, pend = streaming_mod.remap_width(
                    sstate[_wkey(w)], stream, self.rows_cap[w], config,
                    update=True)
            pending[w] = pend
        return pending

    # ------------------------------------------------------------- checkpoint

    def _slice_plan(self):
        """Per-(rank, local table) checkpoint routing: ``plan[rank][m] =
        (table_id, slab_row_offset, rows, col_start, width, row_base)``
        where ``col_start`` is the slice's first column in the full
        (unsliced) source table — column slices are consumed in rank order,
        the reference's ``_slice_weight_for_rank`` math
        (``dist_model_parallel.py:346-361``) — and ``row_base`` is the
        slice's first global row (0 except for row slices, whose columns
        always span the full width)."""
        col_pos = {tid: 0 for tid in range(len(self.strategy.global_configs))}
        plan: List[List[tuple]] = []
        for r, cfgs in enumerate(self.strategy.local_configs_list):
            rank_plan = []
            for m, cfg in enumerate(cfgs):
                _, roff, rows, w = self._table_rows(r, m)
                tid = self.strategy.table_ids_list[r][m]
                if tid in self.strategy.row_sliced_tables:
                    rank_plan.append(
                        (tid, roff, rows, 0, w, int(cfg["_row_base"])))
                else:
                    rank_plan.append((tid, roff, rows, col_pos[tid], w, 0))
                    col_pos[tid] += w
            plan.append(rank_plan)
        return plan

    def _fetch_rows(self, v, rank: int, start: int, n: int,
                    to_host: bool = True) -> Optional[np.ndarray]:
        """Host copy of ``v[rank, start:start+n, :]`` without materializing
        anything bigger. For non-addressable shards (multi-host) the slice is
        jit-extracted with a fully-replicated out-sharding — the chunked
        allgather of the reference's ``get_weights``
        (``dist_model_parallel.py:441-447``) — so every process gets it.
        ``to_host=False`` still executes the collective fetch (every process
        must, SPMD) but skips the device->host copy and returns ``None``
        (the ``all_ranks=False`` mode of :meth:`get_weights`)."""
        if isinstance(v, np.ndarray):
            return np.asarray(v[rank, start:start + n, :]) if to_host \
                else None
        w = v.shape[2]
        if v.is_fully_addressable:
            # Slice on the owning shard's device — a single-device program
            # that transfers only the chunk (a dynamic_slice on the *global*
            # array would make GSPMD materialize a full replica per call).
            for shard in v.addressable_shards:
                r0, r1, _ = shard.index[0].indices(v.shape[0])
                if not (r0 <= rank < r1):
                    continue
                key = ("fetch_shard", shard.data.shape, v.dtype, n)
                fn = self._ckpt_jit_cache.get(key)
                if fn is None:
                    fn = jax.jit(lambda a, r, s: lax.dynamic_slice(
                        a, (r, s, 0), (1, n, w))[0])
                    self._ckpt_jit_cache[key] = fn
                res = fn(shard.data, rank - r0, start)
                return np.asarray(res) if to_host else None
            raise AssertionError("fully-addressable array with no owner shard")
        # Multi-host: every process needs the chunk but no process holds all
        # shards. A masked psum inside shard_map moves exactly one chunk over
        # the network — the reference's chunked allgather
        # (``dist_model_parallel.py:441-447``) — never a full replica.
        mesh = v.sharding.mesh
        axis = self.axis_name
        key = ("fetch_global", v.shape, v.dtype, n, id(mesh))
        fn = self._ckpt_jit_cache.get(key)
        if fn is None:
            P = jax.sharding.PartitionSpec
            blk = v.shape[0] // mesh.shape[axis]

            def local(ab, r, s):
                my = lax.axis_index(axis)
                rel = r - my * blk
                hit = (rel >= 0) & (rel < blk)
                rows = lax.dynamic_slice(
                    ab, (jnp.clip(rel, 0, blk - 1), s, 0), (1, n, w))[0]
                return lax.psum(jnp.where(hit, rows, 0), axis)

            fn = jax.jit(jax.shard_map(
                local, mesh=mesh, in_specs=(P(axis), P(), P()),
                out_specs=P()))
            self._ckpt_jit_cache[key] = fn
        res = fn(v, jnp.asarray(rank), jnp.asarray(start))
        return np.asarray(res) if to_host else None

    def get_weights(self, params: EmbedParams,
                    chunk_elems: int = CHECKPOINT_CHUNK_ELEMS,
                    all_ranks: bool = True) -> Optional[List[np.ndarray]]:
        """Reassemble the full (unsliced) global tables on host, streaming
        row chunks of at most ``chunk_elems`` elements.

        Equivalent of the reference's chunked-allgather ``get_weights``
        (``dist_model_parallel.py:411-485``): peak transient host memory is
        one chunk, not one model; tables over 2^31 elements stream fine; on
        multi-host meshes every process receives the full tables by default.

        Args:
          all_ranks: with ``False`` (the reference's rank-0-only mode,
            ``dist_model_parallel.py:411,419``) only process 0 assembles and
            returns the tables; other processes still participate in every
            collective fetch (SPMD requires it) but skip the device->host
            copy and the host-side buffers, and return ``None``. On a pod
            this keeps the full-model host footprint confined to the
            checkpoint-writing process.
        """
        keep = all_ranks or jax.process_index() == 0
        out = [self.get_table(params, tid, chunk_elems=chunk_elems,
                              all_ranks=all_ranks)
               for tid in range(len(self.strategy.global_configs))]
        return out if keep else None

    def get_table(self, params: EmbedParams, tid: int,
                  chunk_elems: int = CHECKPOINT_CHUNK_ELEMS,
                  all_ranks: bool = True) -> Optional[np.ndarray]:
        """Reassemble ONE global table on host (streamed like
        :meth:`get_weights`, which delegates here). Lets checkpoint writers
        cap host memory at one table instead of the whole model."""
        if not hasattr(self, "_ckpt_jit_cache"):
            self._ckpt_jit_cache = {}
        keep = all_ranks or jax.process_index() == 0
        params = self.stacked_view(params)
        cfg = self.strategy.global_configs[tid]
        out: Optional[np.ndarray] = None
        for r, rank_plan in enumerate(self._slice_plan()):
            for t2, roff, rows, c0, w, rb in rank_plan:
                if t2 != tid:
                    continue
                v = params[_wkey(w)]
                if keep and out is None:
                    out = np.empty(
                        (int(cfg["input_dim"]), int(cfg["output_dim"])),
                        v.dtype)
                p = ps.pack_factor(w)
                chunk_rows = max(p, (int(chunk_elems) // max(w, 1)) // p * p)
                for s in range(0, rows, chunk_rows):
                    n = min(chunk_rows, rows - s)
                    phys = self._fetch_rows(
                        v, r, (roff + s) // p, -(-n // p), to_host=keep)
                    if keep:
                        out[rb + s:rb + s + n, c0:c0 + w] = \
                            ps.unpack_rows_np(phys, w)[:n]
        return out if keep else None

    def _build_shard(self, loaded, dev, width: int, r0: int, r1: int,
                     dtype, chunk_elems: int) -> jax.Array:
        """Stream one device's packed slab shard ``[r1-r0, phys_cap,
        phys_w]``: zeros on-device, then donated row-range writes of at most
        ``chunk_elems`` elements read straight from the (possibly mmap'd)
        sources — never a host copy bigger than one chunk. Chunks are packed
        host-side at physical-row granularity."""
        p = ps.pack_factor(width)
        pw = self.phys_w[width]
        with jax.default_device(dev):
            buf = jnp.zeros((r1 - r0, self.phys_cap[width], pw), dtype)
        # commit to dev (no-copy) so later ops can't migrate an unwritten
        # buffer back to the default device
        buf = jax.device_put(buf, dev)
        shape3 = buf.shape
        buf = buf.reshape(-1, pw)
        plan = self._slice_plan()
        chunk_rows = max(p, (int(chunk_elems) // max(width, 1)) // p * p)
        for r in range(r0, r1):
            base = (r - r0) * self.phys_cap[width]
            for tid, roff, rows, c0, w, rb in plan[r]:
                if w != width:
                    continue
                src = loaded[tid]
                # exact-size check (a looser bound would let an oversized
                # source load silently truncated): row slices must tile the
                # declared global vocab, plain tables must equal it
                full = int(self.strategy.global_configs[tid]["input_dim"])
                if src.shape[0] != full:
                    raise ValueError(
                        f"Table {tid}: expected {full} rows, got "
                        f"{src.shape[0]}")
                for s in range(0, rows, chunk_rows):
                    n = min(chunk_rows, rows - s)
                    host = np.ascontiguousarray(
                        src[rb + s:rb + s + n, c0:c0 + w], dtype=dtype)
                    if n % p:  # pad into the table's alignment padding
                        host = np.concatenate(
                            [host, np.zeros((p - n % p, w), host.dtype)])
                    buf = _write_rows(buf, jax.device_put(
                        ps.pack_rows_np(host, width), dev),
                        base + (roff + s) // p)
        return buf.reshape(shape3)

    @staticmethod
    def _uid_lock_path() -> str:
        """Lock file for ``set_weights(use_lock=True)``: ONE lock per uid,
        so every concurrent load by this user serializes — the reference's
        ``use_lock`` likewise serializes ranks globally, not per
        checkpoint (``dist_model_parallel.py:329-331``). Scoped per uid
        because a fixed world-shared /tmp name would collide with, or be
        blocked by, other users' pre-existing lock files on a shared host
        (ADVICE r4). A per-checkpoint name was considered and rejected:
        one restore streams several component directories (tables/,
        emb_opt/*) whose loads must ALL serialize against other
        processes' — a directory-derived name would hand them different
        locks."""
        import tempfile
        return os.path.join(tempfile.gettempdir(),
                            f"detpu_set_weights_{os.getuid()}.lock")

    def set_weights(self, weights: Sequence[Any], mesh=None,
                    dtype=jnp.float32,
                    chunk_elems: int = CHECKPOINT_CHUNK_ELEMS,
                    use_lock: bool = False,
                    src_dtype=None) -> EmbedParams:
        """Build the sharded slab dict from full global tables (numpy arrays
        or ``np.load``-able paths, mmap'd like the reference,
        ``dist_model_parallel.py:337-339``).

        ``use_lock=True`` serializes the host-side shard building across
        processes — the reference's ``set_weights(..., use_lock=True)``,
        which rank-serializes globally via ``broadcast_object``
        (``dist_model_parallel.py:329-331,383-385``), for loading models
        whose per-process transient host footprint could not otherwise
        coexist. Two layers: co-located processes serialize on a per-uid
        file lock, and on a multi-process ``jax.distributed`` job the
        processes additionally take strict turns (process 0 first), gated
        by a cross-host barrier after each turn — full cross-rank
        serialization like the reference, machine boundaries included.
        The streaming chunked design mostly obviates the need (peak
        transient host memory is one chunk), but page-cache pressure from
        several processes mmap-reading the same checkpoint can still merit
        it.

        Streams per-slice row chunks directly into per-device shard buffers
        — the reference's 128M-element chunked ``scatter_update``
        (``dist_model_parallel.py:362-380``) — so peak transient host memory
        is one chunk regardless of model size, and >2^31-element tables never
        hit a single oversized transfer. On multi-host meshes each process
        builds only its addressable shards.

        ``src_dtype``: the dtype ``.npy`` sources were SAVED in. ``np.save``
        of an extension dtype (bfloat16) writes an opaque void descriptor
        that ``np.load`` cannot map back — such sources load as ``|V<n>``
        and are re-viewed as ``src_dtype`` here (required for bf16
        checkpoints; ``utils.checkpoint`` records it in ``meta.json``)."""
        from ..utils import runtime as _runtime

        _runtime.fault_point("checkpoint_read")
        loaded = [np.load(w, mmap_mode="r") if isinstance(w, str)
                  else np.asarray(w) for w in weights]
        if any(a.dtype.kind == "V" for a in loaded):
            if src_dtype is None:
                raise ValueError(
                    "sources carry an opaque (void) dtype — np.save of an "
                    "extension dtype like bfloat16 does not round-trip "
                    "through np.load; pass src_dtype= with the dtype they "
                    "were saved in")
            sdt = jnp.dtype(src_dtype)  # np.dtype instance (ml_dtypes-aware)
            loaded = [a.view(sdt) if a.dtype.kind == "V" else a
                      for a in loaded]
        if len(loaded) != len(self.strategy.global_configs):
            raise ValueError("set_weights needs one array per global table")
        for tid, (src, cfg) in enumerate(
                zip(loaded, self.strategy.global_configs)):
            want = (int(cfg["input_dim"]), int(cfg["output_dim"]))
            if tuple(src.shape) != want:
                # a narrower source would silently zero-fill under
                # dynamic_update_slice — reject shape drift up front
                raise ValueError(
                    f"Table {tid}: expected shape {want}, got {src.shape}")

        def build():
            out = {}
            for w in self.widths:
                if mesh is None:
                    # honor an active jax.default_device context (e.g.
                    # staging a bigger-than-HBM model on host), like the old
                    # asarray path
                    dev = jax.config.jax_default_device or jax.devices()[0]
                    if isinstance(dev, str):  # context also accepts
                        dev = jax.devices(dev)[0]  # platform names
                    out[_wkey(w)] = self._build_shard(
                        loaded, dev, w, 0, self.world_size, dtype,
                        chunk_elems)
                    continue
                out[_wkey(w)] = self._assemble_sharded(
                    mesh, w,
                    lambda dev, r0, r1, w=w: self._build_shard(
                        loaded, dev, w, r0, r1, dtype, chunk_elems))
            return out

        if not use_lock:
            return build()

        import fcntl

        def locked_build():
            # the file lock wraps ONLY this process's own build turn: held
            # across a barrier wait it would deadlock two co-located
            # processes of one job (A holds the lock waiting for B's
            # barrier; B waits on the lock)
            lock_file = open(self._uid_lock_path(), "w")
            fcntl.flock(lock_file, fcntl.LOCK_EX)
            try:
                return build()
            finally:
                fcntl.flock(lock_file, fcntl.LOCK_UN)
                lock_file.close()

        if jax.process_count() > 1:
            # strict process turns with a cross-host barrier after each —
            # the reference's broadcast_object rank serialization
            # (dist_model_parallel.py:329-331,383-385) across machine
            # boundaries, where a file lock cannot reach. Every process
            # joins every barrier (collective), sandwiching its own build
            # at its process index.
            from jax.experimental import multihost_utils
            me = jax.process_index()
            for p in range(me):
                multihost_utils.sync_global_devices(
                    f"detpu_set_weights_turn_{p}")
            out = locked_build()
            for p in range(me, jax.process_count()):
                multihost_utils.sync_global_devices(
                    f"detpu_set_weights_turn_{p}")
            return out
        return locked_build()
