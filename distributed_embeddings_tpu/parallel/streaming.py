"""Streaming vocab: frequency-gated admission and approximate-LFU
eviction for capacity-bounded dynamic embedding tables.

The reference library (and every static plan in this repo) assumes a
fixed ``[vocab, dim]`` table per feature — but production recommender
traffic is non-stationary: new users and items appear continuously, and
a static vocab either OOMs as it grows or silently degrades as unseen
ids collide. This module is the dynamic-table mode of
:class:`~.dist_embedding.DistributedEmbedding` (ROADMAP item 5, the
scenario-diversity flagship): external ids from an UNBOUNDED id space
are served out of a fixed-capacity slab, with three-state semantics per
id:

* **tracked** — every live id folds into a count-min sketch (the PR 5
  telemetry sketches of :mod:`~..analysis.telemetry`, reused verbatim as
  the admission oracle) and, until admitted, reads/trains a **shared
  hash bucket** row: cold and never-seen ids degrade gracefully into
  ``buckets`` shared rows instead of crashing, evicting hot rows, or
  silently clipping into a neighbour.
* **admitted** — once an id's sketch estimate crosses
  ``admit_min_count`` (``DETPU_ADMIT_MIN_COUNT``) it claims its
  direct-mapped slot (``hash(id) % capacity``). The claimed row is
  zeroed (fresh embedding) at the claim step and the id is served from
  it on every later occurrence.
* **evicted** — a claim on an occupied slot only succeeds when the
  incoming estimate beats the occupant's recorded frequency by
  ``evict_margin`` (``DETPU_EVICT_MARGIN``) — approximate LFU: the
  colder row loses. The evicted id transparently degrades back to its
  shared hash bucket (its next occurrence simply misses the slot map).

Everything runs INSIDE the jitted step: the slot map, frequency
estimates, and sketch are carried as donated pytree leaves (like the
telemetry state) and updated with pure, static-shaped jax ops — no host
round-trips, 0 steady-state recompiles (enforced by the existing
audits). All scatters that decide admission use associative
``max``-reductions with explicit tie-breaks, so the transition is
DETERMINISTIC even under duplicate batch ids — the property the
checkpoint-CRC-identity drills (``tools/check_streaming.py``,
``tests/test_streaming_checkpoint.py``) assert.

Table declaration: a config dict grows a ``"streaming"`` entry::

    {"input_dim": capacity + buckets, "output_dim": dim,
     "streaming": {"capacity": 1 << 16, "buckets": 512}}

``input_dim`` must equal ``capacity + buckets`` — the slab physically
holds the slots followed by the shared bucket rows, so every existing
subsystem (checkpoint streaming, plan audit, re-shard, HLO census)
prices and moves the dynamic table like any other table of that size.
Row/column-sliced streaming tables are rejected (a slot map cannot span
slices).

State is **part of the recoverable trajectory**: :func:`encode_state`
converts the carried (slab-row-space) state to a plan-agnostic
per-table form that ``utils.checkpoint.save_train_state(aux_states=)``
persists CRC-manifested inside the checkpoint, :func:`decode_state`
rebuilds it under the restoring model's plan (re-shard included), and
the resilient driver's generalized aux-rewind restores it from the SAME
ring candidate a rollback picks — an interrupted-and-resumed streaming
run is checkpoint-CRC-identical to an uninterrupted one.

Like :mod:`~..analysis.telemetry`, the math here is pure jax on state
the step already holds; the emission point is
:meth:`~.dist_embedding.DistributedEmbedding.forward_with_residuals`
(``streaming=``) and the threading lives in
:func:`~.trainer.make_hybrid_train_step` (``dynamic=``).
"""

from __future__ import annotations

from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..utils import envvars
from ..analysis.telemetry import cms_query, cms_update

#: free-slot marker in the carried slot map (fingerprints are >= 0)
SLOT_FREE = -1

# odd multipliers for the slot/bucket/fingerprint hashes — disjoint from
# the telemetry sketch's _MULTS so slot placement and sketch buckets
# decorrelate even for equal geometry
_H_SLOT = np.uint32(0x7FEB352D)
_H_BUCKET = np.uint32(0x846CA68B)
_H_FP = np.uint32(0x9E3779B1)
_H_SALT = np.uint32(0x85EBCA77)


class StreamingConfig(NamedTuple):
    """Static (trace-time) admission/eviction policy. Hashable so step
    builders can close over it; every field is a compile-time constant."""

    admit_min_count: int = 2   #: sketch estimate gating slot admission
    evict_margin: int = 1      #: incoming est must beat occupant freq by this
    depth: int = 4             #: admission-sketch rows (independent hashes)
    buckets: int = 4096        #: admission-sketch columns per row


def config_from_env() -> StreamingConfig:
    """The env-configured policy (``DETPU_ADMIT_MIN_COUNT`` /
    ``DETPU_EVICT_MARGIN`` / ``DETPU_ADMIT_SKETCH_DEPTH`` /
    ``DETPU_ADMIT_SKETCH_WIDTH``)."""
    return StreamingConfig(
        admit_min_count=max(1, envvars.get_int("DETPU_ADMIT_MIN_COUNT")),
        evict_margin=max(0, envvars.get_int("DETPU_EVICT_MARGIN")),
        depth=max(1, envvars.get_int("DETPU_ADMIT_SKETCH_DEPTH")),
        buckets=max(2, envvars.get_int("DETPU_ADMIT_SKETCH_WIDTH")))


def resolve_config(dynamic) -> Optional[StreamingConfig]:
    """Normalize a step builder's ``dynamic=`` argument: ``None``/
    ``False`` is off, ``True`` is the env-configured policy, a
    :class:`StreamingConfig` passes through. Like ``telemetry=``, this is
    an EXPLICIT opt-in at step-build time — it changes the step's call
    arity, so no env variable may flip it under an unsuspecting call
    site."""
    if dynamic is None or dynamic is False:
        return None
    if dynamic is True:
        return config_from_env()
    if isinstance(dynamic, StreamingConfig):
        return dynamic
    raise TypeError(
        f"dynamic= takes None | bool | StreamingConfig, got "
        f"{type(dynamic).__name__}")


# ------------------------------------------------------------------- state


def _wkey(width: int) -> str:
    return f"w{width}"


def streaming_widths(de) -> List[int]:
    """Widths whose slab holds at least one streaming table."""
    out = set()
    for tid, _ in de.streaming_tables.items():
        out.add(int(de.strategy.global_configs[tid]["output_dim"]))
    return sorted(out)


def init_streaming(de, config: Optional[StreamingConfig] = None,
                   mesh=None) -> Dict[str, Any]:
    """Fresh streaming-vocab state for ``de``: a plain-dict pytree whose
    leaves all carry a leading ``[world]`` axis (``local_state`` squeezes
    it inside the step, mirroring the slab/telemetry convention), laid
    out over ``mesh`` when given.

    Per width slab with a streaming table: the slot map (31-bit id
    fingerprint per logical slab row; :data:`SLOT_FREE` = free), the
    per-slot frequency record (the occupant's sketch estimate at its
    last admission/hit), and the admission count-min sketch. Top-level:
    the step counter and the cumulative admission / eviction /
    bucket-service / hit counters (the step metrics integrate these)."""
    if not de.streaming_tables:
        raise ValueError(
            "init_streaming: no table declares a 'streaming' config "
            "entry — nothing to carry")
    config = config or config_from_env()
    world = de.world_size

    def stacked(shape, dtype, fill=0):
        return jnp.full((world,) + shape, fill, dtype)

    state: Dict[str, Any] = {
        "steps": stacked((1,), jnp.int32),
        "admitted": stacked((1,), jnp.float32),
        "evicted": stacked((1,), jnp.float32),
        "bucket_ids": stacked((1,), jnp.float32),
        "hit_ids": stacked((1,), jnp.float32),
    }
    for w in streaming_widths(de):
        rows = de.rows_cap[w]
        state[_wkey(w)] = {
            "slot_fp": stacked((rows,), jnp.int32, SLOT_FREE),
            "slot_freq": stacked((rows,), jnp.int32),
            "cms": stacked((config.depth, config.buckets), jnp.int32),
        }
    if mesh is not None:
        sharding = jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec(de.axis_name))
        state = jax.tree.map(lambda a: jax.device_put(a, sharding), state)
    return state


def local_state(state):
    """Strip the leading world axis (``[1, ...]`` per-device leaves
    inside ``shard_map`` / world 1) — the streaming twin of
    ``de.local_view``."""
    return jax.tree.map(lambda v: v[0], state)


def stacked_state(state):
    """Re-add the leading world axis for ``P(axis)`` out_specs."""
    return jax.tree.map(lambda v: v[None], state)


def fresh_like(state):
    """A pristine state with the SAME structure/shapes/placement as
    ``state`` — the aux-rewind fallback when a rollback candidate
    predates streaming aux persistence (slot maps then warm up again,
    which only degrades ids back to their buckets, never corrupts)."""
    def leaf(path, v):
        fill = SLOT_FREE if path[-1].key == "slot_fp" else 0
        return jnp.full(v.shape, fill, v.dtype)

    return jax.tree_util.tree_map_with_path(leaf, state)


# ------------------------------------------------------------- hash helpers


def _mix(ids: jax.Array, salt: jax.Array, mult: np.uint32) -> jax.Array:
    """xxhash-style avalanche of ``ids`` salted per-position (the table
    id, so one table's stream never aliases another's) — uint32 output.
    64-bit ids fold their high word in first: a bare uint32 cast would
    make ids congruent mod 2^32 alias COMPLETELY (same slot, same
    fingerprint, same sketch cell) — systematic identity collapse for
    structured ids carrying type/hash bits up top, not the documented
    ~2^-31 fingerprint collision."""
    if jnp.dtype(ids.dtype).itemsize > 4:
        ids = ids ^ (ids >> 32)
    h = ids.astype(jnp.uint32) ^ (salt.astype(jnp.uint32) * _H_SALT)
    h = h * mult
    h = h ^ (h >> 15)
    h = h * np.uint32(0x2C1B3C6D)
    h = h ^ (h >> 13)
    return h


def _fingerprint(ext: jax.Array, tid: jax.Array) -> jax.Array:
    """31-bit non-negative id fingerprint stored in the slot map. Two
    distinct external ids collide with probability ~2^-31 per slot — an
    approximate structure by design (like the sketch it gates on)."""
    return (_mix(ext, tid, _H_FP) >> np.uint32(1)).astype(jnp.int32)


def sketch_key(ext: jax.Array, tid: jax.Array) -> jax.Array:
    """Non-negative int32 count-min key of an external id, salted by its
    (plan-invariant) table id — the admission oracle's input. Exposed so
    tests can query the sketch the way the step does."""
    return _fingerprint(ext, tid)


# --------------------------------------------------------- the core update


class WidthStream(NamedTuple):
    """One width slab's flattened id stream for one step (built by the
    executor's plan traversal): every leaf ``[n]`` over the positions of
    that width's streaming-table slots."""

    ext: jax.Array    #: raw external ids (pre-remap region values)
    live: jax.Array   #: bool — position holds a real id on a live slot
    cap: jax.Array    #: per-position slot capacity of the owning table
    nbuckets: jax.Array  #: per-position shared-bucket count
    tid: jax.Array    #: per-position global table id (the hash salt)
    roff: jax.Array   #: per-position table row offset inside the slab


def remap_width(wstate: Dict[str, jax.Array], stream: WidthStream,
                rows_cap: int, config: StreamingConfig,
                update: bool = True):
    """Serve one width slab's external-id stream out of the slot map and
    (``update=True``) stage this step's admission/eviction transitions.

    Returns ``(local_rows, pending)`` where ``local_rows [n]`` is the
    table-LOCAL row each position reads (slot for map hits, shared
    bucket otherwise; positions with ``live=False`` return the raw
    value unchanged), and ``pending`` is ``None`` for read-only remaps
    or ``(new_wstate, scrub_rows, stats)``:

    * ``new_wstate`` — the updated slot map / freq / sketch (NOT yet
      gated by the nan-guard verdict; :func:`commit` selects),
    * ``scrub_rows [n]`` — logical slab rows claimed this step (the
      rows :func:`commit` zeroes so admitted ids train from fresh
      embeddings), ``rows_cap`` sentinel elsewhere — at most one live
      entry per claimed row (deterministic tie-broken),
    * ``stats`` — per-step scalar counts (admitted, evicted,
      bucket_ids, hit_ids).

    Freshly admitted ids are still served from their bucket THIS step
    (their slot row is only zeroed at commit, after the optimizer
    scatter); from the next occurrence they hit the slot map. The
    decision chain uses only ``max``-scatters with explicit
    estimate-then-fingerprint-then-position tie-breaks, so duplicate
    batch ids and colliding claims resolve deterministically.
    """
    ext = stream.ext.reshape(-1)
    live = stream.live.reshape(-1)
    cap = stream.cap.reshape(-1).astype(jnp.int32)
    nb = stream.nbuckets.reshape(-1).astype(jnp.int32)
    tid = stream.tid.reshape(-1).astype(jnp.int32)
    roff = stream.roff.reshape(-1).astype(jnp.int32)
    n = ext.shape[0]
    live = live & (ext >= 0)

    key = sketch_key(ext, tid)
    cms = wstate["cms"]
    if update:
        cms = cms_update(cms, key, live)
    est = cms_query(cms, key)

    cap_s = jnp.maximum(cap, 1)
    nb_s = jnp.maximum(nb, 1)
    slot = (_mix(ext, tid, _H_SLOT)
            % cap_s.astype(jnp.uint32)).astype(jnp.int32)
    bucket = (_mix(ext, tid, _H_BUCKET)
              % nb_s.astype(jnp.uint32)).astype(jnp.int32)
    row = roff + slot                      # logical slab row of the slot
    rowc = jnp.where(live, row, 0)         # gather-safe
    fp = _fingerprint(ext, tid)

    occ = wstate["slot_fp"][rowc]
    hit = live & (occ == fp)
    local = jnp.where(hit, slot, cap + bucket)
    local_rows = jnp.where(live, local, ext.astype(jnp.int32))

    if not update:
        return local_rows, None

    free = occ == SLOT_FREE
    occ_freq = wstate["slot_freq"][rowc]
    admit = live & ~hit & (est >= config.admit_min_count)
    claim = admit & (free | (est >= occ_freq + config.evict_margin))

    # deterministic winner per claimed row: max estimate, then max
    # fingerprint, then max stream position — pure associative
    # max-scatters, so duplicate ids and colliding claims cannot make
    # the transition order-dependent (the CRC-identity drills rely on
    # this)
    neg = jnp.full((rows_cap,), -1, jnp.int32)
    best_est = neg.at[rowc].max(jnp.where(claim, est, -1))
    cand = claim & (est == best_est[rowc])
    best_fp = neg.at[rowc].max(jnp.where(cand, fp, -1))
    cand = cand & (fp == best_fp[rowc])
    pos = jnp.arange(n, dtype=jnp.int32)
    best_pos = neg.at[rowc].max(jnp.where(cand, pos, -1))
    scrub = cand & (best_pos[rowc] == pos)  # exactly once per claimed row

    sent = jnp.asarray(rows_cap, jnp.int32)
    scrub_rows = jnp.where(scrub, row, sent)  # OOB scatters drop
    hit_rows = jnp.where(hit, row, sent)
    new_fp = wstate["slot_fp"].at[scrub_rows].set(fp)
    new_freq = wstate["slot_freq"].at[scrub_rows].set(est)
    # a map hit refreshes the occupant's recorded frequency from the
    # (monotone) sketch — the approximate-LFU signal evictions compare
    # against; max dedups duplicate hits deterministically
    new_freq = new_freq.at[hit_rows].max(est)

    stats = {
        "admitted": jnp.sum(scrub, dtype=jnp.float32).reshape(1),
        "evicted": jnp.sum(scrub & ~free, dtype=jnp.float32).reshape(1),
        "bucket_ids": jnp.sum(live & ~hit,
                              dtype=jnp.float32).reshape(1),
        "hit_ids": jnp.sum(hit, dtype=jnp.float32).reshape(1),
    }
    new_wstate = {"slot_fp": new_fp, "slot_freq": new_freq, "cms": cms}
    return local_rows, (new_wstate, scrub_rows, stats)


def commit(de, params: Dict[str, jax.Array], pending, old_state,
           enable=None, opt_state=None, optimizer=None):
    """Apply one step's staged streaming transitions — called by the
    trainer AFTER the optimizer scatter, next to the nan-guard so a
    skipped step leaves the slot map, sketch, counters AND slabs
    bitwise-unchanged (the rollback/quarantine machinery requires the
    guard's skip to be total).

    * claimed slab rows are ZEROED in the (post-apply) width slabs via an
      O(claims) lane-masked scatter (gather current lanes, add the
      negative) — never a slab-wide pass; with ``enable=False`` the rows
      route to the dropped sentinel exactly like the optimizer skip;
    * with ``opt_state``/``optimizer`` given, every SLAB-SHAPED optimizer
      state leaf is reset on the claimed rows in the same commit scatter
      machinery, to the optimizer's declared fresh-row value
      (``fresh_row_fill``: Adagrad's ``initial_accumulator_value``, zero
      for momentum/Adam moments) — an admitted id's moments start
      exactly like a freshly initialized table's, not as the evictee's
      leftovers. Non-slab leaves (Adam's step count) are untouched;
    * the new slot-map/sketch state is where-selected against the old
      (streaming state is MBs, not GBs — a select is cheap);
    * cumulative counters advance by the (gated) per-step stats.

    Returns ``(params, new_state, step_stats)`` — or ``(params,
    opt_state, new_state, step_stats)`` when ``opt_state`` is given —
    where ``step_stats`` is the gated per-step counter dict the trainer
    surfaces as the ``stream_*`` step metrics.
    """
    from ..ops import packed_slab as ps
    from ..utils import obs

    new_state = dict(old_state)
    if opt_state is not None:
        opt_state = dict(opt_state)
    totals = {k: jnp.zeros((1,), jnp.float32)
              for k in ("admitted", "evicted", "bucket_ids", "hit_ids")}
    for w, (new_wstate, scrub_rows, stats) in sorted(pending.items()):
        k = _wkey(w)
        with obs.scope(f"streaming_commit_w{w}"):
            rows = scrub_rows
            if enable is not None:
                rows = jnp.where(enable, rows,
                                 jnp.asarray(de.rows_cap[w], rows.dtype))
            slab = params[k]
            cur = ps.packed_gather(slab, jnp.minimum(
                rows, de.rows_cap[w] - 1), w)
            # sentinel rows expand to physical ids past the slab and the
            # scatter drops them — the same O(ids) skip the optimizer uses
            phys, pvals = ps.expand_update_rows(-cur, rows, w)
            params = dict(params)
            params[k] = slab.at[phys].add(pvals)
            if opt_state is not None:
                # moment hygiene: reset slab-shaped optimizer state on
                # the claimed rows with the SAME gather/expand/scatter
                # machinery (O(claims), guard-gated through `rows`);
                # matching on shape keeps mixed dtypes (fp32 accumulators
                # over bf16 slabs) and tuple states (Adam) leaf-exact
                fill = float(getattr(optimizer, "fresh_row_fill", 0.0))
                slab_shape = tuple(slab.shape)

                def scrub_leaf(leaf, rows=rows, w=w, fill=fill,
                               slab_shape=slab_shape):
                    if tuple(getattr(leaf, "shape", ())) != slab_shape:
                        return leaf
                    c = ps.packed_gather(leaf, jnp.minimum(
                        rows, de.rows_cap[w] - 1), w)
                    # zero-then-add, NOT add(fill - cur): x + (-x) is
                    # exactly 0 and 0 + fill exactly fill, so the reset
                    # row is BITWISE the fresh-init value regardless of
                    # the evictee's magnitude (fill - cur would leave a
                    # rounding residue, or cancel fill entirely under a
                    # huge accumulator)
                    ph, pv = ps.expand_update_rows(-c, rows, w)
                    leaf = leaf.at[ph].add(pv)
                    if fill:
                        _, pf = ps.expand_update_rows(
                            jnp.full_like(c, fill), rows, w)
                        leaf = leaf.at[ph].add(pf)
                    return leaf

                opt_state[k] = jax.tree.map(scrub_leaf, opt_state[k])
            if enable is None:
                new_state[k] = new_wstate
            else:
                new_state[k] = jax.tree.map(
                    lambda a, b: jnp.where(enable, a, b),
                    new_wstate, old_state[k])
            for name, v in stats.items():
                gated = (v if enable is None
                         else jnp.where(enable, v, 0.0))
                totals[name] = totals[name] + gated
    one = jnp.ones((1,), jnp.int32)
    if enable is not None:
        one = jnp.where(enable, one, 0)
    new_state["steps"] = old_state["steps"] + one
    for name, v in totals.items():
        new_state[name] = old_state[name] + v
    if opt_state is not None:
        return params, opt_state, new_state, totals
    return params, new_state, totals


# ------------------------------------------------------ state persistence


def encode_state(de, state) -> Dict[str, np.ndarray]:
    """Host-side, plan-AGNOSTIC encoding of a carried streaming state for
    ``utils.checkpoint.save_train_state(aux_states=)``: per streaming
    table, its slot fingerprints and frequencies as ``[capacity]``
    arrays (slab-row-space decoded through the layout the checkpoint
    plan already knows), plus each width's admission sketch and the
    per-rank counters. ``decode_state`` inverts it under ANY plan whose
    logical tables match — the dynamic form re-shards exactly like the
    tables themselves (``tools/reshard.py`` copies the aux file
    byte-identically; only a changed world size resets the per-rank
    sketches/counters, logged as a warm-up degradation)."""
    host = jax.tree.map(np.asarray, state)
    out: Dict[str, np.ndarray] = {
        "world": np.asarray([de.world_size], np.int32),
    }
    for name in ("steps", "admitted", "evicted", "bucket_ids", "hit_ids"):
        out[f"c_{name}"] = np.asarray(host[name])
    for tid, (cap, _) in sorted(de.streaming_tables.items()):
        r, roff, w = _table_home(de, tid)
        ws = host[_wkey(w)]
        out[f"t{tid}_fp"] = np.asarray(ws["slot_fp"][r, roff:roff + cap])
        out[f"t{tid}_freq"] = np.asarray(
            ws["slot_freq"][r, roff:roff + cap])
    for w in streaming_widths(de):
        out[f"w{w}_cms"] = np.asarray(host[_wkey(w)]["cms"])
    return out


def decode_state(de, template, encoded: Optional[Dict[str, np.ndarray]]):
    """Rebuild a carried streaming state from :func:`encode_state` output
    under ``de``'s (possibly different) plan, using ``template`` (an
    :func:`init_streaming` result for the SAME config) for structure and
    placement. ``None``/empty input returns a pristine
    :func:`fresh_like` state — streaming aux must never block a restore
    (cold slot maps only degrade ids back to their buckets)."""
    import logging

    log = logging.getLogger(__name__)
    # np.array (not asarray): jax-array views are read-only, and the
    # per-table writes below mutate in place
    state = jax.tree.map(np.array, fresh_like(template))
    if not encoded:
        return jax.tree.map(jnp.asarray, state)
    try:
        same_world = (int(np.asarray(encoded["world"]).reshape(-1)[0])
                      == de.world_size)
        for tid, (cap, _) in sorted(de.streaming_tables.items()):
            r, roff, w = _table_home(de, tid)
            for field, key in (("slot_fp", f"t{tid}_fp"),
                               ("slot_freq", f"t{tid}_freq")):
                src = np.asarray(encoded[key])
                if src.shape != (cap,):
                    raise ValueError(
                        f"{key}: saved shape {src.shape} != ({cap},) — "
                        "streaming capacity drift")
                arr = state[_wkey(w)][field]
                arr[r, roff:roff + cap] = src
        for name in ("steps", "admitted", "evicted", "bucket_ids",
                     "hit_ids"):
            src = encoded.get(f"c_{name}")
            if src is not None and same_world \
                    and src.shape == state[name].shape:
                state[name] = np.asarray(src).astype(state[name].dtype)
        for w in streaming_widths(de):
            src = encoded.get(f"w{w}_cms")
            tgt = state[_wkey(w)]["cms"]
            if src is not None and same_world and src.shape == tgt.shape:
                state[_wkey(w)]["cms"] = np.asarray(src).astype(tgt.dtype)
            elif src is not None:
                log.warning(
                    "streaming decode: admission sketch w%d re-shards "
                    "from world/geometry %s to %s — resetting (warm-up "
                    "degradation; slot maps carried over intact)", w,
                    src.shape, tgt.shape)
    except Exception:  # noqa: BLE001 - see docstring: never block a restore
        log.exception("streaming state decode failed; starting fresh")
        state = jax.tree.map(np.array, fresh_like(template))
    out = jax.tree.map(jnp.asarray, state)
    # restore the template leaves' device placement (mesh-sharded runs)
    def place(t, v):
        sharding = getattr(t, "sharding", None)
        return (jax.device_put(v, sharding) if sharding is not None
                else v)
    return jax.tree.map(place, template, out)


def _table_home(de, tid: int) -> Tuple[int, int, int]:
    """``(rank, slab row offset, width)`` of an (unsliced) streaming
    table — the placement encode/decode translate through."""
    for r, tids in enumerate(de.strategy.table_ids_list):
        for m, t in enumerate(tids):
            if t == tid:
                return (r, de.row_offsets_list[r][m],
                        int(de.strategy.local_configs_list[r][m]
                            ["output_dim"]))
    raise ValueError(f"streaming table {tid} placed on no rank")


# --------------------------------------------------------- host analysis


def occupancy(de, state) -> Dict[str, Any]:
    """Host summary of a streaming state: per-table slot occupancy and
    the cumulative admission/eviction/bucket counters — the streaming
    analogue of ``telemetry.load_balance`` (``tools/check_streaming.py``
    and ``tools/check_online.py`` read this)."""
    host = jax.tree.map(np.asarray, state)
    tables = []
    for tid, (cap, nb) in sorted(de.streaming_tables.items()):
        r, roff, w = _table_home(de, tid)
        fp = np.asarray(host[_wkey(w)]["slot_fp"][r, roff:roff + cap])
        tables.append({
            "table_id": int(tid), "capacity": int(cap),
            "buckets": int(nb),
            "occupied": int((fp != SLOT_FREE).sum()),
            "occupancy_frac": float((fp != SLOT_FREE).mean()),
        })
    def c(name):
        return float(np.asarray(host[name]).sum())
    return {
        "steps": int(np.asarray(host["steps"]).reshape(-1).max()),
        "admitted": c("admitted"), "evicted": c("evicted"),
        "bucket_ids": c("bucket_ids"), "hit_ids": c("hit_ids"),
        "tables": tables,
    }
