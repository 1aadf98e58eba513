"""Full train-state checkpoint/resume for hybrid-parallel training.

The reference checkpoints only embedding tables (``get_weights`` +
``np.savez``, ``examples/dlrm/main.py:246-248``) — "no optimizer-state or
step checkpointing" (SURVEY §5). Here the WHOLE
:class:`~.parallel.trainer.HybridTrainState` round-trips:

* embedding tables stream through
  :meth:`~.parallel.DistributedEmbedding.get_weights` /
  :meth:`~.parallel.DistributedEmbedding.set_weights` (chunked, multi-host
  safe, mmap restore) into per-table ``.npy`` files;
* sparse-optimizer slab state rides the SAME path — the optimizer states
  are width-keyed slab dicts shaped exactly like the params
  (:class:`~.parallel.optimizers.SparseAdagrad` accumulators,
  :class:`~.parallel.optimizers.SparseMomentum` traces) or tuples of them
  plus small counters (:class:`~.parallel.optimizers.SparseAdam`), so each
  component reassembles to per-table arrays;
* the replicated dense params / dense optimizer state / step counter
  serialize with ``flax.serialization`` msgpack.

Layout under ``path/``::

    tables/table_000.npy ...
    emb_opt/<component>/table_000.npy ...   # slab-shaped components
    emb_opt/<component>.npy                 # non-slab leaves (Adam counts)
    dense.msgpack                           # dense params+opt+step

Multi-host: every process calls both functions (the streamed fetches are
collective); only process 0 writes, and restore reads are per-process.

Fault tolerance (``utils.runtime``): a killed process mid-checkpoint and a
torn file on disk are normal operating conditions, not fatal errors.

* **Atomic writes.** Every file goes through tmp-file + fsync + rename,
  and the whole checkpoint is staged in ``<path>.staging`` then swapped
  into ``<path>`` in one directory rename — a reader never observes a
  half-written (torn) checkpoint at ``<path>``. One narrow window exists:
  the swap is two renames (old → ``.prev``, staging → ``path``), so a
  crash exactly between them leaves ``path`` absent while the old
  checkpoint sits COMPLETE at ``<path>.prev`` (and the new one at
  ``<path>.staging``) — :func:`restore_train_state`'s default fallback
  recovers from ``.prev`` automatically; only torn state is impossible.
* **Self-validation.** ``meta.json`` records a CRC32 per file; it is
  written last, so its presence certifies the set. :func:`verify_checkpoint`
  re-hashes on load and raises
  :class:`~distributed_embeddings_tpu.utils.runtime.CheckpointCorrupt` on
  any mismatch (truncation, bit rot, partial external copy).
* **Previous-checkpoint fallback.** The swap keeps the displaced
  checkpoint at ``<path>.prev``; :func:`restore_train_state` falls back to
  it (with a clear log line) instead of loading torn state.
* ``DETPU_FAULT=die:checkpoint_write`` kills the process inside the write
  path, and ``DETPU_FAULT=corrupt@ckpt`` flips bytes in a just-committed
  shard file (silent bit rot the CRC manifest must catch), so the whole
  story is testable on CPU (see ``tests/test_checkpoint_atomic.py``).

Elastic topology (the logical-table codec): every array in a checkpoint is
a **full logical table** — ``save_train_state`` reassembles each table
(params and every slab-shaped optimizer component) from its slices via the
strategy's row-offset/column-slice metadata before writing, and restore
re-slices it under the restoring model's plan through the streaming
``set_weights``. The on-disk format therefore carries NO sharding: a
checkpoint written on a v5e-16 under ``memory_balanced`` restores on 8
chips under a ``telemetry_balanced`` plan, table by table, with peak host
memory one table. ``meta.json`` records the *plan fingerprint*
(``DistEmbeddingStrategy.plan_spec``) purely so restore can TELL the
topologies apart: ``restore_train_state(on_mismatch=...)`` either raises a
named :class:`~.runtime.CheckpointMismatch` (``"error"``) or re-shards in
place (``"reshard"``), logging the degradation (old plan, new plan,
per-rank byte deltas) through :mod:`.obs`. :func:`reshard_checkpoint` is
the offline half — it rewrites a checkpoint to a new plan/world size
without touching a device (``tools/reshard.py`` is the CLI).
"""

from __future__ import annotations

import json
import logging
import os
import shutil
import zlib
from typing import TYPE_CHECKING, Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from flax import serialization

from . import runtime

if TYPE_CHECKING:  # function-local at run time: a module-scope import of
    # parallel.trainer from here would close an import cycle the moment a
    # parallel module imports utils.obs (utils/__init__ -> checkpoint ->
    # parallel -> dist_embedding -> utils, mid-initialization)
    from ..parallel.trainer import HybridTrainState

logger = logging.getLogger(__name__)


# ------------------------------------------------------- atomic file layer


def _crc32_file(path: str, chunk_bytes: int = 1 << 20) -> int:
    """Streaming CRC32 of a file (constant memory; tables can be GBs)."""
    crc = 0
    with open(path, "rb") as f:
        while True:
            block = f.read(chunk_bytes)
            if not block:
                break
            crc = zlib.crc32(block, crc)
    return crc & 0xFFFFFFFF


def _fsync_dir(path: str) -> None:
    """Best-effort directory fsync so renames inside it are durable."""
    try:
        fd = os.open(path, os.O_RDONLY)
    except OSError:
        return
    try:
        os.fsync(fd)
    finally:
        os.close(fd)


class _CRCWriter:
    """File proxy accumulating a CRC32 over sequential writes, so multi-GB
    table dumps don't need a full re-read to build the manifest. A writer
    that seeks back (zipfile patching local headers in ``np.savez``)
    invalidates the running CRC — ``dirty`` flags it and the caller falls
    back to the streaming re-read."""

    def __init__(self, f):
        self._f = f
        self.crc = 0
        self.dirty = False

    def write(self, data):
        self.crc = zlib.crc32(data, self.crc)
        return self._f.write(data)

    def seek(self, *args, **kwargs):
        self.dirty = True
        return self._f.seek(*args, **kwargs)

    def __getattr__(self, name):
        return getattr(self._f, name)


def _atomic_file(path: str, writer: Callable[[Any], None]) -> int:
    """Write ``path`` via tmp + flush + fsync + rename; returns the file's
    CRC32 (accumulated during the write — see :class:`_CRCWriter`).
    ``fault_point('checkpoint_write')`` fires first, so an injected death
    leaves at most a ``.tmp`` orphan — never a torn final file."""
    runtime.fault_point("checkpoint_write")
    tmp = path + ".tmp"
    with open(tmp, "wb") as f:
        proxy = _CRCWriter(f)
        writer(proxy)
        f.flush()
        os.fsync(f.fileno())
    crc = _crc32_file(tmp) if proxy.dirty else proxy.crc
    os.replace(tmp, path)
    return crc


def previous_checkpoint_path(path: str) -> str:
    """Where the swap parks the displaced checkpoint (restore fallback)."""
    return path.rstrip(os.sep) + ".prev"


def ring_dir(path: str) -> str:
    """Directory holding the checkpoint ring (last-good checkpoints older
    than ``<path>.prev``), one subdirectory per retained save."""
    return path.rstrip(os.sep) + ".ring"


def _meta_field(path: str, key: str):
    """One field of a checkpoint's manifest (``None`` when the manifest
    is unreadable or the field absent)."""
    try:
        with open(os.path.join(path, "meta.json"), encoding="utf-8") as f:
            return json.load(f).get(key)
    except (OSError, json.JSONDecodeError, UnicodeDecodeError):
        return None


def _meta_step(path: str) -> Optional[int]:
    """The ``step`` a checkpoint's manifest records (``None`` for
    pre-ring checkpoints or an unreadable manifest)."""
    step = _meta_field(path, "step")
    return int(step) if step is not None else None


def meta_run_id(path: str) -> Optional[str]:
    """The run-lineage id a checkpoint's manifest records (``None`` for
    pre-lineage checkpoints). The resilient driver stamps every save
    with its lineage (fresh runs mint one, resumes inherit the restored
    checkpoint's) and the rollback refuses candidates from a DIFFERENT
    lineage — a fresh run in a dirty directory must never roll back into
    a previous run's parameters."""
    rid = _meta_field(path, "run_id")
    return str(rid) if rid is not None else None


def ring_entries(path: str) -> list:
    """The checkpoint ring of ``path``, newest first: ``[(step, dir),
    ...]``. Entries are listed, not validated — a rollback consumer CRC-
    verifies the one it picks (:func:`verify_checkpoint`) and moves on to
    the next on corruption."""
    d = ring_dir(path)
    if not os.path.isdir(d):
        return []
    out = []
    for name in os.listdir(d):
        if not name.startswith("step_"):
            continue
        try:
            step = int(name[len("step_"):])
        except ValueError:
            continue
        out.append((step, os.path.join(d, name)))
    out.sort(key=lambda e: e[0], reverse=True)
    return out


def prune_ring(path: str, keep_last_n: int) -> None:
    """Drop the oldest ring entries beyond ``keep_last_n``."""
    for _, entry in ring_entries(path)[max(0, keep_last_n):]:
        shutil.rmtree(entry, ignore_errors=True)


def rollback_candidates(path: str) -> list:
    """Every restorable checkpoint generation of ``path``, newest first:
    ``[(step, dir), ...]`` across ``path`` itself, ``<path>.prev``, and
    the ring. ``step`` is the manifest-recorded step counter (``None``
    for pre-ring checkpoints, which a step-aware rollback skips). Nothing
    is CRC-validated here — the consumer verifies its pick."""
    out = []
    for p in (path, previous_checkpoint_path(path)):
        if os.path.isfile(os.path.join(p, "meta.json")):
            out.append((_meta_step(p), p))
    out.extend(ring_entries(path))
    # newest first; step-less (pre-ring) checkpoints sort last
    out.sort(key=lambda e: (e[0] is not None, e[0] or 0), reverse=True)
    return out


def _archive_to_ring(path: str, prev: str, keep_last_n: int) -> None:
    """Move the about-to-be-deleted second-newest checkpoint (``prev``)
    into the ring instead of dropping it, then prune. Checkpoints whose
    manifest predates step recording cannot be placed in the ring (their
    position is unknowable) and are dropped as before."""
    step = _meta_step(prev)
    if step is None:
        logger.debug("checkpoint ring: %s has no recorded step "
                     "(pre-ring format); dropping instead of archiving",
                     prev)
        shutil.rmtree(prev)
        return
    entry = os.path.join(ring_dir(path), f"step_{step:012d}")
    os.makedirs(ring_dir(path), exist_ok=True)
    if os.path.isdir(entry):  # same-step re-save: newest wins
        shutil.rmtree(entry)
    os.replace(prev, entry)
    prune_ring(path, keep_last_n)


def _commit_staging(staging: str, path: str,
                    keep_previous: bool = True, ring_n: int = 0) -> None:
    """Swap a fully written staging directory into ``path`` (one directory
    rename; the displaced valid checkpoint survives at ``<path>.prev``
    when ``keep_previous``, and with ``ring_n > 0`` the checkpoint THAT
    displaces — the former ``.prev`` — rotates into ``<path>.ring/``
    instead of being deleted, keeping the newest ``ring_n`` generations
    restorable), then honor a ``DETPU_FAULT=corrupt@ckpt``
    drill by flipping bytes mid-file in the committed checkpoint's first
    table shard — AFTER the commit, so the manifest certifies a file the
    disk then silently diverges from (the scenario CRC validation
    exists for)."""
    runtime.fault_point("checkpoint_commit")
    prev = previous_checkpoint_path(path)
    if os.path.isdir(path):
        if keep_previous and os.path.isfile(
                os.path.join(path, "meta.json")):
            if os.path.isdir(prev):
                if ring_n > 0 and os.path.isfile(
                        os.path.join(prev, "meta.json")):
                    _archive_to_ring(path, prev, ring_n)
                else:
                    shutil.rmtree(prev)
            os.replace(path, prev)
        else:  # invalid leftovers (or fallback disabled): drop them
            shutil.rmtree(path)
    os.replace(staging, path)
    _fsync_dir(os.path.dirname(os.path.abspath(path)))
    if runtime.corrupt_ckpt_requested():
        target = os.path.join(path, "tables", "table_000.npy")
        if os.path.isfile(target):
            with open(target, "r+b") as f:
                f.seek(max(0, os.path.getsize(target) // 2))
                byte = f.read(1) or b"\x00"
                f.seek(-len(byte), os.SEEK_CUR)
                f.write(bytes([byte[0] ^ 0xFF]))
            logger.error("DETPU_FAULT=corrupt@ckpt: flipped a byte in %s",
                         target)
            from . import obs  # lazy: obs is jax-free but keep parity with
            # runtime's own lazy pattern
            obs.record_fault("ckpt_corrupt")


def _staging_path(path: str) -> str:
    return path.rstrip(os.sep) + ".staging"


def verify_checkpoint(path: str) -> Dict[str, Any]:
    """Validate a checkpoint directory; returns its parsed ``meta.json``.

    Raises :class:`~.runtime.CheckpointCorrupt` when the manifest is
    missing/torn, a listed file is absent, or a CRC32 mismatches. Pre-CRC
    checkpoints (no ``files`` manifest) pass with a debug note — their
    files simply cannot be validated.
    """
    meta_path = os.path.join(path, "meta.json")
    if not os.path.isfile(meta_path):
        raise runtime.CheckpointCorrupt(
            f"no checkpoint at {path!r} (missing meta.json)")
    try:
        with open(meta_path, encoding="utf-8") as f:
            meta = json.load(f)
    except (json.JSONDecodeError, UnicodeDecodeError) as e:
        raise runtime.CheckpointCorrupt(
            f"torn manifest at {meta_path!r}: {e}") from e
    files = meta.get("files")
    if files is None:
        logger.debug("checkpoint %s predates CRC manifests; skipping "
                     "content validation", path)
        return meta
    for rel, crc in files.items():
        fp = os.path.join(path, rel)
        if not os.path.isfile(fp):
            raise runtime.CheckpointCorrupt(
                f"checkpoint {path!r} is missing {rel!r}")
        actual = _crc32_file(fp)
        if actual != crc:
            raise runtime.CheckpointCorrupt(
                f"CRC mismatch for {rel!r} in {path!r}: manifest "
                f"{crc:#010x}, on disk {actual:#010x} (torn write?)")
    return meta


def validate_checkpoint_model(path: str, meta: Dict[str, Any], de) -> None:
    """Check that a (whole, CRC-valid) checkpoint structurally matches the
    model it is being restored into: table count and every table's
    (vocab, dim) against ``de.strategy.global_configs``.

    Raises :class:`~.runtime.CheckpointMismatch` naming the first
    offending table with expected-vs-found shapes — the alternative is a
    scatter-shape traceback from deep inside ``set_weights`` hours into a
    resumed run. Shapes come from the ``tables`` manifest entry when
    present; older checkpoints fall back to the ``.npy`` headers (an mmap
    open reads only the header)."""
    want = de.strategy.global_configs
    n = int(meta.get("num_tables", -1))
    if n != len(want):
        raise runtime.CheckpointMismatch(
            f"checkpoint at {path!r} holds {n} table(s) but the model "
            f"declares {len(want)} — wrong checkpoint or changed model "
            "config")
    saved = meta.get("tables")
    for t, cfg in enumerate(want):
        exp = (int(cfg["input_dim"]), int(cfg["output_dim"]))
        if saved is not None:
            got = tuple(int(x) for x in saved[t])
        else:
            fp = os.path.join(path, "tables", f"table_{t:03d}.npy")
            try:
                got = tuple(np.load(fp, mmap_mode="r").shape)
            except (OSError, ValueError) as e:
                raise runtime.CheckpointCorrupt(
                    f"cannot read table header {fp!r}: {e}") from e
        if got != exp:
            raise runtime.CheckpointMismatch(
                f"table {t}: checkpoint at {path!r} was saved with "
                f"vocab x dim {got}, the model expects {exp} — fix the "
                "embedding configs or point at the matching checkpoint")


def _plan_tools():
    """Lazy import of the plan-fingerprint helpers. Function-local for the
    same reason ``parallel.trainer`` is (module docstring): a module-scope
    ``..parallel`` import from here would close an import cycle while
    ``utils`` is mid-initialization."""
    from ..parallel.strategy import plan_diff, plans_equal

    return plans_equal, plan_diff


def _check_plan(path: str, meta: Dict[str, Any], de,
                on_mismatch: str) -> bool:
    """Compare the checkpoint's recorded plan fingerprint against ``de``'s.
    Returns True when they differ and ``on_mismatch='reshard'`` authorizes
    re-slicing (the degradation is recorded through ``obs.record_event``
    and a warning log); raises :class:`~.runtime.CheckpointMismatch` under
    ``'error'``. Pre-manifest checkpoints (no recorded plan) compare as
    matching — there is nothing to diff."""
    saved = meta.get("plan")
    if saved is None:
        return False
    plans_equal, plan_diff = _plan_tools()
    current = de.strategy.plan_spec()
    if plans_equal(saved, current):
        return False
    param_bytes = jnp.dtype(
        meta.get("dtypes", {}).get("tables", "float32")).itemsize
    diff = plan_diff(saved, current, param_bytes=param_bytes)
    desc = (f"world {diff['world_size'][0]} -> {diff['world_size'][1]}, "
            f"strategy {diff['strategy'][0]!r} -> {diff['strategy'][1]!r}, "
            f"{len(diff['moved_tables'])} table(s) change ranks")
    if on_mismatch != "reshard":
        raise runtime.CheckpointMismatch(
            f"checkpoint at {path!r} was written under a different "
            f"sharding plan ({desc}). Pass on_mismatch='reshard' to "
            "re-slice it under the current plan on the fly, or rewrite it "
            "offline with tools/reshard.py")
    from . import obs

    obs.record_event("checkpoint_reshard", path=path,
                     old_plan=saved, new_plan=current, diff=diff)
    logger.warning(
        "restore_train_state: re-sharding checkpoint %s onto a different "
        "topology (%s; per-rank byte deltas on common ranks: %s)",
        path, desc, diff["per_rank_byte_deltas"])
    return True


def _is_slab_dict(tree, params) -> bool:
    """True when ``tree`` is a width-keyed dict of arrays shaped like the
    param slabs (Adagrad accumulators, momentum traces)."""
    if not isinstance(tree, dict) or set(tree) != set(params):
        return False
    return all(
        hasattr(v, "shape") and tuple(v.shape) == tuple(params[k].shape)
        for k, v in tree.items())


def _components(opt_state, params):
    """Split an embedding-optimizer state into named checkpointable
    components: ``(slab_components, aux_components)`` where slab components
    are ``{wkey: slab}`` dicts (table-reassemblable) and aux components are
    small arrays saved verbatim."""
    if _is_slab_dict(opt_state, params):
        return {"state": opt_state}, {}
    if isinstance(opt_state, dict) and set(opt_state) == set(params):
        vals = list(opt_state.values())
        if all(isinstance(v, tuple) for v in vals):
            ln = {len(v) for v in vals}
            if len(ln) == 1:
                n = ln.pop()
                slabs, aux = {}, {}
                for i in range(n):
                    comp = {k: opt_state[k][i] for k in opt_state}
                    if _is_slab_dict(comp, params):
                        slabs[f"state{i}"] = comp
                    else:
                        aux[f"state{i}"] = comp
                return slabs, aux
        if all(v == () or v == [] for v in vals):  # SparseSGD
            return {}, {}
    raise ValueError(
        "Unrecognized embedding-optimizer state structure; expected the "
        "slab-dict layouts of the parallel.optimizers classes")


def save_train_state(path: str, de, state: HybridTrainState,
                     is_chief: Optional[bool] = None,
                     keep_previous: bool = True,
                     keep_last_n: int = 0,
                     run_id: Optional[str] = None,
                     aux_states: Optional[Dict[str, Dict[str, Any]]]
                     = None) -> None:
    """Write the full train state under ``path`` (a directory), atomically.

    Every process must call this (the streamed table fetches are
    collective); only the chief writes files.

    The write is crash-safe end to end: files land in ``<path>.staging``
    (each via tmp + fsync + rename, CRC32s collected into the manifest,
    ``meta.json`` last) and the staging directory is swapped into ``path``
    — a process killed at any point never leaves torn state at ``path``:
    it is either the old checkpoint, the new checkpoint, or (crash exactly
    between the swap's two renames) absent with the old checkpoint whole
    at ``<path>.prev``, which restore's fallback picks up. With
    ``keep_previous`` (the default) the displaced checkpoint survives at
    ``<path>.prev`` as the restore fallback.

    ``keep_last_n`` > 0 additionally keeps a RING of older generations:
    the checkpoint the swap would have deleted (the former ``.prev``)
    rotates into ``<path>.ring/step_<step>`` and the ring is pruned to
    the newest ``keep_last_n`` entries — so at any time up to
    ``keep_last_n + 2`` whole checkpoints are restorable
    (:func:`rollback_candidates`). This is the rollback-and-replay
    recovery's supply of known-good states: when a NaN storm escalates,
    the driver restores the newest HEALTHY entry predating the poisoned
    batch window instead of dying.

    ``run_id`` stamps the manifest with a run-lineage id
    (:func:`meta_run_id`) so a rollback can tell this run's generations
    from a previous run's leftovers in the same directory.

    ``aux_states`` persists named jit-carried auxiliary state INSIDE the
    checkpoint (``aux/<name>.npz``, CRC-manifested like every other
    file): each entry is a flat ``{key: array}`` dict in a
    plan-AGNOSTIC encoding chosen by its producer (e.g. the
    streaming-vocab slot maps via
    :func:`~..parallel.streaming.encode_state`). Because every ring
    generation carries its own aux snapshot, the rollback-and-replay
    recovery rewinds aux state to EXACTLY the candidate it restores —
    not to some newer sidecar — and :func:`reshard_checkpoint` moves
    the files byte-identically (the encoding owes its plan-agnosticism
    to the producer). Read back with :func:`load_aux_state`."""
    if is_chief is None:
        is_chief = jax.process_index() == 0
    staging = _staging_path(path)
    manifest: Dict[str, int] = {}

    def put(rel, writer):
        manifest[rel] = _atomic_file(os.path.join(staging, rel), writer)

    if is_chief:
        if os.path.isdir(staging):  # leftover of an earlier killed save
            shutil.rmtree(staging)
        os.makedirs(os.path.join(staging, "tables"))
    n_tables = len(de.strategy.global_configs)

    def dump_tables(sub, comp):
        # table-at-a-time: chief host memory caps at ONE reassembled table
        if is_chief:
            os.makedirs(os.path.join(staging, sub), exist_ok=True)
        for t in range(n_tables):
            arr = de.get_table(comp, t, all_ranks=False)
            if is_chief:
                put(f"{sub}/table_{t:03d}.npy",
                    lambda f, a=arr: np.save(f, a))

    dump_tables("tables", state.emb_params)
    slabs, aux = _components(state.emb_opt_state, state.emb_params)
    for name, comp in slabs.items():
        dump_tables(f"emb_opt/{name}", comp)
    if is_chief:
        os.makedirs(os.path.join(staging, "emb_opt"), exist_ok=True)
        # aux components save per width key (one npz entry each) — stacking
        # across keys would require every key's aux leaf to have the same
        # element count, which only holds for scalar counters (ADVICE r4)
        for name, comp in aux.items():
            put(f"emb_opt/{name}.npz",
                lambda f, c=comp: np.savez(
                    f, **{k: np.asarray(v) for k, v in c.items()}))
        if aux_states:
            os.makedirs(os.path.join(staging, "aux"), exist_ok=True)
            for name, enc in sorted(aux_states.items()):
                put(f"aux/{name}.npz",
                    lambda f, c=enc: np.savez(
                        f, **{k: np.asarray(v) for k, v in c.items()}))
        dense = {"dense_params": state.dense_params,
                 "dense_opt_state": state.dense_opt_state,
                 "step": state.step}
        put("dense.msgpack",
            lambda f: f.write(serialization.to_bytes(dense)))

        def dt(tree):
            return str(jnp.dtype(next(iter(tree.values())).dtype).name)

        meta = {"num_tables": n_tables,
                # the step counter at save time: lets the ring name its
                # entries and the rollback pick a candidate that predates
                # a poisoned batch window without opening dense.msgpack
                "step": int(np.asarray(jax.device_get(state.step))),
                # per-table (vocab, dim): lets restore reject a checkpoint
                # that does not match the model with a named error instead
                # of a scatter-shape traceback (CheckpointMismatch)
                "tables": [[int(c["input_dim"]), int(c["output_dim"])]
                           for c in de.strategy.global_configs],
                # the sharding-plan fingerprint: the DATA is plan-agnostic
                # (full logical tables); this records which topology wrote
                # it so restore can tell "same layout" from "needs a
                # re-shard" and diff the two (strategy.plan_diff)
                "plan": de.strategy.plan_spec(),
                "slab_components": sorted(slabs),
                "aux_components": sorted(aux),
                # jit-carried auxiliary states riding the checkpoint
                # (aux/<name>.npz; plan-agnostic encodings — see the
                # aux_states docstring)
                "aux_states": sorted(aux_states or {}),
                # per-component saved dtypes: a bf16-tables + fp32-accumulator
                # run must restore with the SAME mixed dtypes by default
                # (ADVICE r4) — restore reads these unless overridden
                "dtypes": {"tables": dt(state.emb_params),
                           **{name: dt(comp)
                              for name, comp in slabs.items()}},
                # per-file CRC32s, manifest written LAST: its presence
                # certifies every other file hit the disk whole
                "files": dict(manifest)}
        if run_id is not None:
            # run lineage: lets the rollback refuse another run's
            # leftover generations in the same directory
            meta["run_id"] = str(run_id)
        _atomic_file(os.path.join(staging, "meta.json"),
                     lambda f: f.write(json.dumps(meta).encode()))
        _fsync_dir(staging)
        # ---- commit: one directory swap; old checkpoint -> <path>.prev
        # (and the former .prev -> the ring, under keep_last_n)
        _commit_staging(staging, path, keep_previous=keep_previous,
                        ring_n=int(keep_last_n))


def _aux_consensus(comp: Dict[str, Any]) -> float:
    """Collapse a saved aux component (per-width-slab counter arrays) to
    its single representative value. The only aux leaves the optimizer
    zoo produces are per-slab step counters (SparseAdam), which advance
    in lockstep across slabs — take the max and warn if they ever
    disagree (max keeps Adam's bias correction conservative)."""
    flat = [np.asarray(v).reshape(-1) for v in comp.values()]
    allv = np.concatenate(flat) if flat else np.zeros((1,))
    top = float(allv.max()) if allv.size else 0.0
    if allv.size and not np.all(allv == top):
        logger.warning(
            "aux optimizer component: per-slab values disagree (min %s, "
            "max %s) across the re-shard; using the max", allv.min(), top)
    return top


def _adapt_aux(name: str, comp: Dict[str, Any], wkey: str, spec,
               resharding: bool):
    """Restore one aux optimizer leaf (``emb_opt/<name>.npz`` entry
    ``wkey``). Same-plan restores reproduce the saved array exactly; a
    re-shard rebuilds the leaf at the NEW width/world geometry from the
    saved per-slab consensus (a new width group or changed world size has
    no saved twin to reshape from)."""
    arr = comp.get(wkey)
    if arr is not None:
        arr = np.asarray(arr)
        if arr.size == int(np.prod(spec.shape)):
            return jnp.asarray(arr).reshape(spec.shape).astype(spec.dtype)
        if not resharding:
            raise runtime.CheckpointMismatch(
                f"aux optimizer component {name}/{wkey}: saved shape "
                f"{arr.shape} cannot fill {spec.shape} and the checkpoint "
                "plan matches the model — corrupt aux component?")
    elif not resharding:
        raise runtime.CheckpointMismatch(
            f"aux optimizer component {name} is missing width key "
            f"{wkey!r} though the checkpoint plan matches the model")
    return jnp.full(spec.shape, _aux_consensus(comp), spec.dtype)


def restore_train_state(path: str, de, emb_optimizer, dense_template,
                        dense_tx, mesh=None, dtype=None,
                        fallback: bool = True,
                        on_mismatch: str = "error") -> HybridTrainState:
    """Rebuild a :class:`HybridTrainState` from :func:`save_train_state`
    output. ``dense_template`` supplies the dense params/opt pytree
    structure (e.g. a freshly initialized state's ``dense_params``);
    tables restore via mmap'd streaming ``set_weights``.

    ``dtype``: by default every component restores in the dtype it was
    SAVED in (recorded in ``meta.json`` — a bf16-tables + fp32-accumulator
    run resumes with the same mixed dtypes and an unchanged trajectory).
    Pass a single dtype to force it everywhere, or a dict keyed by
    component name (``"tables"``, ``"state"``, ``"state0"``, ...) for
    per-component overrides (missing keys keep their saved dtype).

    ``on_mismatch``: what to do when the checkpoint's recorded sharding
    plan (world size / placement / slicing) differs from ``de``'s:

    * ``"error"`` (default): raise :class:`~.runtime.CheckpointMismatch`
      naming both topologies — restoring onto a different mesh is an
      operator decision, not something to do silently.
    * ``"reshard"``: re-slice every logical table (params + slab-shaped
      optimizer state) under ``de``'s plan while streaming it in, adapt
      the per-slab optimizer aux leaves (Adam step counts) to the new
      width/world geometry, and record the degradation — old plan, new
      plan, per-rank byte deltas — through
      :func:`~.obs.record_event` (``"checkpoint_reshard"``) plus a
      warning log. This is the elastic-resume path
      (``parallel.resilient.run_resilient`` defaults to it).

    Checkpoints written before plan manifests existed restore as before
    (nothing to compare against).

    Validation: the checkpoint is CRC-verified against its manifest before
    anything loads. A torn checkpoint is never restored — with ``fallback``
    (the default) the previous valid checkpoint at ``<path>.prev`` is
    restored instead (clear warning logged); otherwise
    :class:`~.runtime.CheckpointCorrupt` propagates."""
    if on_mismatch not in ("error", "reshard"):
        raise ValueError(
            f"on_mismatch must be 'error' | 'reshard', got {on_mismatch!r}")
    runtime.fault_point("checkpoint_read")
    try:
        meta = verify_checkpoint(path)
    except runtime.CheckpointCorrupt as e:
        prev = previous_checkpoint_path(path)
        if not (fallback and os.path.isdir(prev)):
            raise
        logger.warning(
            "checkpoint at %s failed validation (%s); falling back to the "
            "previous valid checkpoint at %s", path, e, prev)
        meta = verify_checkpoint(prev)  # must itself be whole, or we raise
        from . import obs

        # let drivers learn WHICH generation actually restored: anything
        # restored alongside the params (the streaming aux state) must
        # come from the SAME directory, or two trajectories splice
        obs.record_event("checkpoint_prev_fallback", path=path, prev=prev)
        path = prev
    # structural match BEFORE any data streams: a mismatched-but-whole
    # checkpoint is a config error, not corruption — no .prev fallback
    validate_checkpoint_model(path, meta, de)
    resharding = _check_plan(path, meta, de, on_mismatch)
    n = meta["num_tables"]
    saved_dtypes = meta.get("dtypes", {})

    def saved(component):  # the dtype files were written in (also the view
        # hint for bf16 .npy, whose descriptor np.load cannot map back)
        return jnp.dtype(saved_dtypes.get(component, "float32"))

    def dtype_of(component):
        if isinstance(dtype, dict):
            if component in dtype:
                return dtype[component]
        elif dtype is not None:
            return dtype
        return saved(component)

    def table_paths(sub):
        return [os.path.join(path, sub, f"table_{t:03d}.npy")
                for t in range(n)]

    emb_params = de.set_weights(table_paths("tables"), mesh=mesh,
                                dtype=dtype_of("tables"),
                                src_dtype=saved("tables"))
    # inspect the optimizer-state STRUCTURE without materializing it (a
    # real init would transiently allocate full slab-sized moments)
    opt_struct = jax.eval_shape(emb_optimizer.init, emb_params)
    slab_comps = {
        name: de.set_weights(table_paths(os.path.join("emb_opt", name)),
                             mesh=mesh, dtype=dtype_of(name),
                             src_dtype=saved(name))
        for name in meta["slab_components"]}
    aux_comps = {}
    for name in meta["aux_components"]:
        npz = os.path.join(path, "emb_opt", f"{name}.npz")
        if os.path.exists(npz):
            aux_comps[name] = dict(np.load(npz))
        else:  # pre-r5 stacked format: rows in aux_wkey_order
            rows = np.load(os.path.join(path, "emb_opt", f"{name}.npy"))
            aux_comps[name] = {
                k: rows[i] for i, k in enumerate(meta["aux_wkey_order"])}
    if _is_slab_dict(opt_struct, emb_params):
        assert set(meta["slab_components"]) == {"state"}, meta
        opt_state = slab_comps["state"]
    elif meta["slab_components"] or meta["aux_components"]:
        # tuple-structured state (Adam): substitute per-position components
        new = {}
        for k in opt_struct:
            parts = []
            for i in range(len(opt_struct[k])):
                name = f"state{i}"
                if name in slab_comps:
                    parts.append(slab_comps[name][k])
                else:
                    spec = opt_struct[k][i]
                    parts.append(_adapt_aux(name, aux_comps[name], k,
                                            spec, resharding))
            new[k] = tuple(parts)
        opt_state = new
    else:
        # stateless (SparseSGD): the real init is trivially cheap
        opt_state = emb_optimizer.init(emb_params)
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P
        sharding = NamedSharding(mesh, P(de.axis_name))
        opt_state = jax.tree.map(
            lambda a: jax.device_put(a, sharding)
            if hasattr(a, "ndim") and a.ndim >= 3 else a, opt_state)

    dense = {"dense_params": dense_template,
             "dense_opt_state": dense_tx.init(dense_template),
             "step": jnp.zeros((), jnp.int32)}
    with open(os.path.join(path, "dense.msgpack"), "rb") as f:
        dense = serialization.from_bytes(dense, f.read())
    from ..parallel.trainer import HybridTrainState, replicate_on_mesh

    dense["step"] = jnp.asarray(dense["step"])
    dense = replicate_on_mesh(dense, mesh)
    return HybridTrainState(
        emb_params=emb_params, emb_opt_state=opt_state,
        dense_params=dense["dense_params"],
        dense_opt_state=dense["dense_opt_state"],
        step=dense["step"])


def load_aux_state(path: str, name: str) -> Optional[Dict[str, Any]]:
    """Read one ``aux_states`` entry written by :func:`save_train_state`
    back as a ``{key: numpy array}`` dict. ``None`` when the checkpoint
    predates aux persistence or never carried ``name`` — aux state is
    auxiliary by contract and must never block a restore (its producer
    decodes ``None`` into a pristine warm-up state)."""
    fp = os.path.join(path, "aux", f"{name}.npz")
    if not os.path.isfile(fp):
        return None
    try:
        with np.load(fp) as loaded:
            return {k: loaded[k] for k in loaded.files}
    except (OSError, ValueError, zlib.error) as e:
        logger.warning("aux state %s at %s unreadable (%s); treating as "
                       "absent", name, path, e)
        return None


# --------------------------------------------------- offline re-shard codec


def _copy_file(src: str, dst: str, chunk_bytes: int = 1 << 20) -> None:
    """Streamed copy + fsync (constant memory; tables can be GBs)."""
    with open(src, "rb") as fin, open(dst, "wb") as fout:
        shutil.copyfileobj(fin, fout, chunk_bytes)
        fout.flush()
        os.fsync(fout.fileno())


def reshard_checkpoint(src: str, dst: str, target,
                       dry_run: bool = False) -> Dict[str, Any]:
    """Rewrite the checkpoint at ``src`` to ``dst`` under ``target``'s
    sharding plan — entirely host-side (no device, no jax arrays): the
    on-disk data is full logical tables, so re-sharding copies them
    byte-identically (streamed file by file; peak memory one copy chunk)
    and rewrites only the plan-dependent pieces — the ``meta.json`` plan
    fingerprint and the per-slab optimizer aux leaves (Adam step counts),
    which are rebuilt at the target's width/world geometry from the saved
    consensus. ``dst`` then restores cleanly (no ``on_mismatch`` needed)
    into a model using the target plan, and a round trip back to the
    original plan reproduces every array bit for bit.

    Args:
      src: source checkpoint directory (CRC-verified before anything is
        read; must carry a ``files`` manifest — pre-CRC-era checkpoints
        must be re-saved first).
      dst: destination directory (atomic staging + swap, like
        :func:`save_train_state`; an existing valid checkpoint there is
        kept at ``<dst>.prev``). Must differ from ``src``.
      target: the topology to re-shard to — a
        :class:`~..parallel.strategy.DistEmbeddingStrategy` or anything
        carrying one as ``.strategy`` (a ``DistributedEmbedding``). Its
        global table shapes must match the checkpoint's.
      dry_run: diff only — nothing is written.

    Returns:
      The :func:`~..parallel.strategy.plan_diff` dict (old plan vs target
      plan: world sizes, per-rank byte loads and deltas, moved tables).
    """
    strat = target if hasattr(target, "plan_spec") else target.strategy
    if len(strat.global_configs) < int(strat.world_size):
        # mirror DistributedEmbedding's fewer-tables-than-positions limit:
        # the rewrite would succeed but no model could ever load it
        raise ValueError(
            f"target plan has {int(strat.world_size)} ranks but only "
            f"{len(strat.global_configs)} table(s) — fewer tables than "
            "mesh positions is unsupported, so the re-sharded checkpoint "
            "could never be restored")
    meta = verify_checkpoint(src)
    if meta.get("files") is None:
        raise runtime.CheckpointCorrupt(
            f"checkpoint at {src!r} predates CRC/plan manifests — re-save "
            "it with the current code before re-sharding")
    # the target must describe the SAME logical model
    saved_tables = meta.get("tables")
    want = [[int(c["input_dim"]), int(c["output_dim"])]
            for c in strat.global_configs]
    if int(meta.get("num_tables", -1)) != len(want) or (
            saved_tables is not None
            and [list(map(int, t)) for t in saved_tables] != want):
        raise runtime.CheckpointMismatch(
            f"target plan declares tables {want} but the checkpoint at "
            f"{src!r} holds {meta.get('num_tables')} table(s) "
            f"{saved_tables} — re-sharding changes the topology, never "
            "the model")
    _, plan_diff = _plan_tools()
    new_plan = strat.plan_spec()
    param_bytes = jnp.dtype(
        meta.get("dtypes", {}).get("tables", "float32")).itemsize
    diff = plan_diff(meta.get("plan"), new_plan, param_bytes=param_bytes)
    if dry_run:
        return diff
    if os.path.abspath(src) == os.path.abspath(dst):
        raise ValueError(
            "reshard_checkpoint: src and dst must differ (the staging swap "
            "would otherwise displace the source mid-copy)")

    new_world = int(strat.world_size)
    new_widths = sorted({int(c["output_dim"])
                         for cfgs in strat.local_configs_list
                         for c in cfgs})
    aux_files = {}
    for name in meta.get("aux_components", []):
        aux_files[f"emb_opt/{name}.npz"] = name
        aux_files[f"emb_opt/{name}.npy"] = name  # pre-r5 stacked format

    staging = _staging_path(dst)
    if os.path.isdir(staging):  # leftover of an earlier killed reshard
        shutil.rmtree(staging)
    manifest: Dict[str, int] = {}
    for rel, crc in meta["files"].items():
        out = os.path.join(staging, rel)
        os.makedirs(os.path.dirname(out), exist_ok=True)
        name = aux_files.get(rel)
        if name is None:
            # logical-table data (and the replicated dense state) is
            # plan-agnostic: byte-identical streamed copy, CRC carried
            # over from the just-verified source manifest
            _copy_file(os.path.join(src, rel), out)
            manifest[rel] = crc
            continue
        if rel.endswith(".npy"):  # pre-r5 stacked rows -> per-wkey dict
            rows = np.load(os.path.join(src, rel))
            comp = {k: rows[i]
                    for i, k in enumerate(meta["aux_wkey_order"])}
            rel = rel[:-len(".npy")] + ".npz"  # rewrite in the npz format
            out = os.path.join(staging, rel)
        else:
            with np.load(os.path.join(src, rel)) as loaded:
                comp = {k: loaded[k] for k in loaded.files}
        value = _aux_consensus(comp)
        tail = (np.asarray(next(iter(comp.values()))).shape[1:]
                if comp else (1, 1))
        dt = (np.asarray(next(iter(comp.values()))).dtype
              if comp else np.float32)
        rebuilt = {f"w{w}": np.full((new_world,) + tuple(tail), value, dt)
                   for w in new_widths}
        manifest[rel] = _atomic_file(
            out, lambda f, c=rebuilt: np.savez(f, **c))
    meta_new = dict(meta, plan=new_plan, files=manifest)
    _atomic_file(os.path.join(staging, "meta.json"),
                 lambda f: f.write(json.dumps(meta_new).encode()))
    _fsync_dir(staging)
    _commit_staging(staging, dst, keep_previous=True)
    logger.info(
        "reshard_checkpoint: %s -> %s (world %s -> %s, strategy %s -> %s, "
        "%d table(s) moved ranks)", src, dst, diff["world_size"][0],
        diff["world_size"][1], diff["strategy"][0], diff["strategy"][1],
        len(diff["moved_tables"]))
    return diff
