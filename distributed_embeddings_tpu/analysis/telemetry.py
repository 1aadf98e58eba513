"""On-device embedding access telemetry: hot-row sketches and per-rank
load accounting, carried as explicit jit state.

The paper's design shards tables because memory dominates and exchanges
activations because communication dominates — but the repo's existing
observability (``utils/obs.py`` step metrics) only says *how many* ids a
rank received per step, never *which rows* are hot or *how skewed* the
per-rank load is over time. Every placement optimization the ROADMAP
names (hot-row caching, skew-aware placement, table re-sharding) needs
exactly that signal, and it must come from inside the compiled step:
fetching ids to the host per step would serialize the input pipeline and
a ``pure_callback`` would put a device→host sync in the hot path (the
step auditor rejects both).

This module is the state + math of that telemetry; the emission points
live in :meth:`~..parallel.dist_embedding.DistributedEmbedding.
update_telemetry` (one per ``(width, kind)`` exchange group, each under
its own ``obs.scope``), and the threading lives in
:func:`~..parallel.trainer.make_hybrid_train_step` (``telemetry=``).
Three properties are load-bearing:

* **jit-carried** — the telemetry state is an ordinary pytree argument
  of the step (donated, like the train state), updated with pure jax
  ops: count-min-sketch scatter-adds and a top-k merge. No host
  callbacks, no recompiles after warmup (the state's shapes are static).
* **per-table top-k hot rows** — a count-min sketch per width slab
  (``[depth, buckets]`` int32; estimates never undercount) plus a
  carried top-k candidate buffer merged every step: the current batch's
  unique ids are scored against the sketch and the best ``k`` survive.
  Ids are *logical slab rows*, mapped back to ``(table, row)`` on host
  by :func:`hot_rows` via the layout the strategy already knows.
* **per-rank load accounting** — cumulative live routed ids per rank
  (total and per width), the time-integrated version of the per-step
  ``ids_routed`` metric: the imbalance signal placement decisions need.

Accuracy: a count-min sketch only ever OVER-estimates (collisions add),
so a row reported cold is truly cold; hot-row estimates are exact up to
collision noise ``~ total_ids / buckets`` per bucket. Counts saturate at
int32; long runs should read the top-k *ranking*, not absolute counts.

Like :mod:`.audit`, nothing here touches a backend at import.
"""

from __future__ import annotations

from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..utils import envvars

#: dead slot marker in the carried top-k id buffer
TOPK_EMPTY = -1
#: unique() fill marker for padding candidates (sorts after all real ids)
_CAND_PAD = np.iinfo(np.int32).max

# xxhash/murmur-style odd multipliers; depth d uses _MULTS[d % len]
# xor-folded with d so depths beyond len(_MULTS) stay distinct
_MULTS = np.array([0x9E3779B1, 0x85EBCA77, 0xC2B2AE3D, 0x27D4EB2F,
                   0x165667B1, 0xD3A2646D, 0xFD7046C5, 0xB55A4F09],
                  dtype=np.uint32)
_MIX = np.uint32(0x2C1B3C6D)


class TelemetryConfig(NamedTuple):
    """Static (trace-time) telemetry geometry. Hashable so step builders
    can close over it; every field is a compile-time constant."""

    depth: int = 4        #: count-min sketch rows (independent hashes)
    buckets: int = 2048   #: count-min sketch columns per row
    topk: int = 32        #: hot-row slots carried per width slab
    candidates: int = 128  #: per-step unique-id candidates merged into top-k


def telemetry_enabled() -> bool:
    """Whether ``DETPU_TELEMETRY`` asks for access telemetry (read at
    step-build time, trace-time static — like ``with_metrics``)."""
    return envvars.enabled("DETPU_TELEMETRY")


def config_from_env() -> TelemetryConfig:
    """The env-configured geometry (``DETPU_TELEMETRY_SKETCH_DEPTH`` /
    ``_SKETCH_WIDTH`` / ``_TOPK`` / ``_CANDIDATES``; 0 candidates means
    ``4 * topk``)."""
    topk = max(1, envvars.get_int("DETPU_TELEMETRY_TOPK"))
    cand = envvars.get_int("DETPU_TELEMETRY_CANDIDATES")
    return TelemetryConfig(
        depth=max(1, envvars.get_int("DETPU_TELEMETRY_SKETCH_DEPTH")),
        buckets=max(2, envvars.get_int("DETPU_TELEMETRY_SKETCH_WIDTH")),
        topk=topk,
        candidates=cand if cand > 0 else 4 * topk)


def resolve_config(telemetry) -> Optional[TelemetryConfig]:
    """Normalize a step builder's ``telemetry=`` argument: ``None``/
    ``False`` is off, ``True`` is the env-configured geometry, a
    :class:`TelemetryConfig` passes through.

    Telemetry is an EXPLICIT opt-in at step-build time — unlike
    ``with_metrics`` (which only grows the return tuple), telemetry
    changes the step's *call* arity, so an env variable must never flip
    it under an unsuspecting 3-arg call site. ``DETPU_TELEMETRY`` is
    consumed by the telemetry-aware entry points instead (the dlrm
    example, ``tools/obs_report.py``),
    which pass ``telemetry=``/the carried state together.
    """
    if telemetry is None or telemetry is False:
        return None
    if telemetry is True:
        return config_from_env()
    if isinstance(telemetry, TelemetryConfig):
        return telemetry
    raise TypeError(
        f"telemetry= takes None | bool | TelemetryConfig, got "
        f"{type(telemetry).__name__}")


# ------------------------------------------------------------------- state


def _wkey(width: int) -> str:
    return f"w{width}"


def init_telemetry(de, config: Optional[TelemetryConfig] = None,
                   mesh=None) -> Dict[str, Any]:
    """Fresh telemetry state for ``de``: a plain-dict pytree whose leaves
    all carry a leading ``[world]`` axis (``local_state`` squeezes it
    inside the step, mirroring the slab convention), laid out over
    ``mesh`` when given so ``shard_map`` receives it pre-sharded.

    Per width slab: the count-min sketch, the top-k (ids, estimates)
    carry, and the width's cumulative live-id count; top-level: the step
    counter and the rank's cumulative routed-id total."""
    config = config or config_from_env()
    world = de.world_size

    def stacked(shape, dtype, fill=0):
        return jnp.full((world,) + shape, fill, dtype)

    state: Dict[str, Any] = {
        "steps": stacked((1,), jnp.int32),
        "ids_total": stacked((1,), jnp.float32),
    }
    for w in de.widths:
        state[_wkey(w)] = {
            "cms": stacked((config.depth, config.buckets), jnp.int32),
            "topk_ids": stacked((config.topk,), jnp.int32, TOPK_EMPTY),
            "topk_est": stacked((config.topk,), jnp.int32),
            "ids": stacked((1,), jnp.float32),
        }
    if mesh is not None:
        sharding = jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec(de.axis_name))
        state = jax.tree.map(lambda a: jax.device_put(a, sharding), state)
    return state


def local_state(state):
    """Strip the leading world axis (``[1, ...]`` per-device leaves inside
    ``shard_map`` / world 1) — the telemetry twin of ``de.local_view``."""
    return jax.tree.map(lambda v: v[0], state)


def stacked_state(state):
    """Re-add the leading world axis for ``P(axis)`` out_specs."""
    return jax.tree.map(lambda v: v[None], state)


# -------------------------------------------------------------- sketch math


def _buckets_of(ids: jax.Array, depth: int, buckets: int) -> jax.Array:
    """``[depth, n]`` sketch columns for ``ids [n]`` (int32, >= 0): one
    multiply-xorshift hash per depth row. Uint32 arithmetic wraps mod
    2^32, which is exactly the mixing these constants are built for."""
    h0 = ids.astype(jnp.uint32)[None, :]
    d_ix = np.arange(depth)
    mults = jnp.asarray(_MULTS[d_ix % len(_MULTS)]
                        ^ d_ix.astype(np.uint32))[:, None]
    h = h0 * mults
    h = h ^ (h >> 15)
    h = h * _MIX
    h = h ^ (h >> 13)
    return (h % jnp.uint32(buckets)).astype(jnp.int32)


def cms_update(cms: jax.Array, ids: jax.Array,
               live: jax.Array) -> jax.Array:
    """Scatter-add ``live`` (bool/int ``[n]``) into ``cms [depth,
    buckets]`` at each depth's bucket of ``ids [n]`` (masked positions
    add 0 — no branching, SPMD-uniform)."""
    depth, buckets = cms.shape
    cols = _buckets_of(jnp.where(live, ids, 0), depth, buckets)
    rows = jnp.arange(depth, dtype=jnp.int32)[:, None]
    flat = (rows * buckets + cols).reshape(-1)
    inc = jnp.broadcast_to(live.astype(jnp.int32)[None, :],
                           cols.shape).reshape(-1)
    return cms.reshape(-1).at[flat].add(inc).reshape(depth, buckets)


def cms_query(cms: jax.Array, ids: jax.Array) -> jax.Array:
    """Count-min estimate ``[n]`` for ``ids [n]``: min over depth rows
    (never undercounts; collisions only inflate)."""
    depth, buckets = cms.shape
    cols = _buckets_of(jnp.maximum(ids, 0), depth, buckets)
    rows = jnp.arange(depth, dtype=jnp.int32)[:, None]
    return cms.reshape(-1)[(rows * buckets + cols).reshape(-1)] \
        .reshape(depth, -1).min(axis=0)


def record_ids(wstate: Dict[str, jax.Array], ids: jax.Array,
               live: jax.Array, config: TelemetryConfig
               ) -> Dict[str, jax.Array]:
    """Fold one step's id stream for one width slab into its telemetry
    state: sketch update, then a top-k merge of the step's unique ids
    (scored by the *updated* sketch) against the carried candidates.

    ``ids [n]`` are logical slab rows (garbage where ``live [n]`` is
    False); everything is static-shaped — the unique() is size-bounded by
    ``config.candidates`` and padded with a sentinel.
    """
    ids = ids.astype(jnp.int32).reshape(-1)
    live = live.reshape(-1)
    cms = cms_update(wstate["cms"], ids, live)

    # Candidate set: the step's hottest DISTINCT live ids by sketch
    # count. Two naive choices fail on a rank holding several tables in
    # one width slab: unique(size=K) keeps the K *smallest* ids (jnp
    # truncates in sorted order), so hot rows in later tables never get
    # nominated; and a plain top_k over per-position estimates saturates
    # all K slots with duplicates of the single hottest id. So: sort the
    # ids (dead positions to the pad sentinel), score only each id's
    # FIRST occurrence with its estimate, and top_k that — K distinct
    # ids, hottest first.
    ids_live = jnp.where(live, ids, _CAND_PAD)
    est_all = jnp.where(live, cms_query(cms, ids), -1)
    order = jnp.argsort(ids_live)
    sids = ids_live[order]
    first = jnp.concatenate(
        [jnp.ones((1,), bool), sids[1:] != sids[:-1]])
    score = jnp.where(first, est_all[order], -1)
    k_pool = min(config.candidates, int(score.shape[0]))
    pool_est, pool_ix = jax.lax.top_k(score, k_pool)
    pool = jnp.where(pool_est >= 0, sids[pool_ix], _CAND_PAD)
    cand = jnp.unique(pool, size=config.candidates, fill_value=_CAND_PAD)
    old_ids = wstate["topk_ids"]
    dup = (cand[:, None] == old_ids[None, :]).any(axis=1)
    cand_ok = (cand != _CAND_PAD) & ~dup
    cand_est = jnp.where(cand_ok, cms_query(cms, cand), -1)
    # carried slots re-query so their estimates keep growing; the carried
    # estimate is a floor (the sketch is monotone, so this only matters
    # at int32 saturation — and keeps the carried buffer load-bearing
    # instead of jit-dropped dead state)
    old_est = jnp.where(old_ids >= 0,
                        jnp.maximum(cms_query(cms, old_ids),
                                    wstate["topk_est"]),
                        -1)

    all_ids = jnp.concatenate([old_ids, cand])
    all_est = jnp.concatenate([old_est, cand_est])
    top_est, top_ix = jax.lax.top_k(all_est, config.topk)
    top_ids = jnp.where(top_est >= 0, all_ids[top_ix], TOPK_EMPTY)
    return {
        "cms": cms,
        "topk_ids": top_ids,
        "topk_est": jnp.maximum(top_est, 0),
        "ids": wstate["ids"] + jnp.sum(live, dtype=jnp.float32).reshape(1),
    }


# ------------------------------------------------------ state persistence


def save_telemetry_state(path: str, state) -> None:
    """Persist the raw carried state (atomic tmp+rename ``.npz``) so a
    resumed run can CONTINUE the accumulation — the sketch/top-k arrays
    themselves, not just the summary. Leaves are saved in pytree-flatten
    order (the structure is deterministic for a given model config)."""
    import os

    leaves = jax.tree_util.tree_leaves(state)
    arrays = {f"leaf_{i}": np.asarray(v) for i, v in enumerate(leaves)}
    tmp = path + ".tmp"
    with open(tmp, "wb") as f:
        np.savez(f, **arrays)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)


def restore_telemetry_state(path: str, fresh_state):
    """Rebuild a carried state from :func:`save_telemetry_state` output,
    using ``fresh_state`` (an :func:`init_telemetry` result for the SAME
    model + config) as the structure/placement template. On any mismatch
    (config drift, torn file) the fresh state is returned unchanged —
    telemetry is auxiliary and must never block a resume."""
    try:
        with np.load(path) as loaded:
            leaves, treedef = jax.tree_util.tree_flatten(fresh_state)
            if len(loaded.files) != len(leaves):
                raise ValueError(
                    f"{len(loaded.files)} saved leaves != "
                    f"{len(leaves)} expected (telemetry config drift?)")
            out = []
            for i, leaf in enumerate(leaves):
                arr = loaded[f"leaf_{i}"]
                if arr.shape != leaf.shape or \
                        arr.dtype != np.asarray(leaf).dtype:
                    raise ValueError(
                        f"leaf {i}: saved {arr.shape}/{arr.dtype} != "
                        f"expected {leaf.shape}")
                sharding = getattr(leaf, "sharding", None)
                out.append(jax.device_put(arr, sharding)
                           if sharding is not None else jnp.asarray(arr))
            return jax.tree_util.tree_unflatten(treedef, out)
    except Exception:  # noqa: BLE001 - see docstring: never block a resume
        import logging

        logging.getLogger(__name__).exception(
            "telemetry state restore from %s failed; starting fresh", path)
        return fresh_state


# ------------------------------------------------------------ host analysis


def _fetch(state) -> Dict[str, Any]:
    """Host numpy copy of a telemetry state (single-host; on a pod call
    this on fully-addressable or process-allgathered state)."""
    return jax.tree.map(np.asarray, state)


def _slab_row_to_table(de, rank: int, width: int,
                       row: int) -> Optional[Tuple[int, int]]:
    """Map a logical slab row back to ``(global_table_id, table_row)``
    via the same layout the checkpoint plan uses (``row_offsets_list`` +
    per-rank local configs; row slices add their ``_row_base``)."""
    from ..ops import packed_slab as ps

    cfgs = de.strategy.local_configs_list[rank]
    for m, cfg in enumerate(cfgs):
        if int(cfg["output_dim"]) != width:
            continue
        roff = de.row_offsets_list[rank][m]
        span = ps.align_rows(int(cfg["input_dim"]), width)
        if roff <= row < roff + span:
            local = row - roff
            if local >= int(cfg["input_dim"]):
                return None  # alignment padding row (nothing live reads it)
            return (de.strategy.table_ids_list[rank][m],
                    local + int(cfg.get("_row_base", 0)))
    return None


def hot_rows(de, state, topk: Optional[int] = None
             ) -> Dict[int, List[Tuple[int, int]]]:
    """Per-global-table hot rows ``{table_id: [(row, est_count), ...]}``
    (descending estimate), decoded from every rank's carried top-k.

    Column-sliced tables surface the same ``(table, row)`` on several
    ranks (each slice sees every id); duplicates keep the MAX estimate —
    summing would multiply a hot row's count by its slice fan-out.
    """
    host = _fetch(state)
    per_table: Dict[int, Dict[int, int]] = {}
    for w in de.widths:
        ws = host[_wkey(w)]
        for r in range(de.world_size):
            for row, est in zip(ws["topk_ids"][r], ws["topk_est"][r]):
                if row < 0 or est <= 0:
                    continue
                hit = _slab_row_to_table(de, r, w, int(row))
                if hit is None:
                    continue
                tid, trow = hit
                tab = per_table.setdefault(tid, {})
                tab[trow] = max(tab.get(trow, 0), int(est))
    out: Dict[int, List[Tuple[int, int]]] = {}
    for tid, rows in per_table.items():
        ranked = sorted(rows.items(), key=lambda kv: (-kv[1], kv[0]))
        out[tid] = ranked[:topk] if topk else ranked
    return out


def load_balance(state) -> Dict[str, Any]:
    """Per-rank cumulative routed-id load + the imbalance ratio
    (max/mean; 1.0 is perfectly balanced — the number skew-aware
    placement wants to drive down)."""
    host = _fetch(state)
    loads = np.asarray(host["ids_total"]).reshape(-1).astype(float)
    mean = float(loads.mean()) if loads.size else 0.0
    return {
        "per_rank_ids": [float(x) for x in loads],
        "imbalance_ratio": (float(loads.max() / mean) if mean > 0
                            else 1.0),
        "steps": int(np.asarray(host["steps"]).reshape(-1)[0]),
    }


def zipf_alpha(counts: List[int]) -> Optional[float]:
    """Least-squares Zipf exponent of a descending count ranking
    (slope of ``log(count)`` on ``log(rank)``, negated): ~1 is classic
    recommender skew, ~0 is uniform. ``None`` below 3 usable points."""
    c = np.asarray([x for x in counts if x > 0], dtype=float)
    if c.size < 3:
        return None
    x = np.log(np.arange(1, c.size + 1, dtype=float))
    y = np.log(c)
    slope = np.polyfit(x, y, 1)[0]
    return float(-slope)


def table_loads_from_summary(summary: Dict[str, Any],
                             num_tables: int) -> List[float]:
    """Per-global-table traffic weights for the ``telemetry_balanced``
    planner (``parallel/strategy.py``), derived from a
    :func:`summarize_telemetry` dict (e.g. the ``<ckpt>.telemetry.json``
    the resilient driver flushes).

    The weight of a table is the sum of its surfaced hot-row count
    estimates — an under-count of total traffic (only the carried top-k
    surfaces), but under the Zipfian skew that motivates re-sharding the
    top-k holds most of the mass, and the planner only needs *relative*
    weights. Tables that never surfaced a hot row weigh 0 and fall back
    to byte balancing via the planner's tie-break."""
    loads = [0.0] * num_tables
    for t in summary.get("tables", []):
        tid = int(t.get("table_id", -1))
        if 0 <= tid < num_tables:
            loads[tid] = float(sum(int(c) for _, c in t.get("top_rows", [])))
    return loads


def summarize_telemetry(de, state, topk: Optional[int] = None
                        ) -> Dict[str, Any]:
    """JSON-able run summary: per-table hot rows (with a per-table Zipf
    exponent estimate), per-rank loads + imbalance ratio, per-width id
    totals, step count. The host half of the observatory —
    ``tools/obs_report.py`` renders it and the resilient driver flushes
    it alongside checkpoints."""
    host = _fetch(state)
    hot = hot_rows(de, host, topk=topk)
    tables = []
    for tid in sorted(hot):
        ranked = hot[tid]
        tables.append({
            "table_id": int(tid),
            "rows": int(de.strategy.global_configs[tid]["input_dim"]),
            "width": int(de.strategy.global_configs[tid]["output_dim"]),
            "top_rows": [[int(r), int(c)] for r, c in ranked],
            "zipf_alpha": zipf_alpha([c for _, c in ranked]),
        })
    per_width = {
        _wkey(w): [float(x) for x in
                   np.asarray(host[_wkey(w)]["ids"]).reshape(-1)]
        for w in de.widths}
    return dict(load_balance(host), tables=tables,
                per_width_ids=per_width)
