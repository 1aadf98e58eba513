"""Deadline-bounded serving runtime: coalescing, the padded-batch
ladder, deadline scheduling, overload admission control, graceful
degradation, and the read-only streaming serve path.

The semantics under test (``parallel/serving.py``):

* variable-size requests coalesce FIFO into the smallest ladder rung
  that holds them; padding rows are inert and the sliced-back
  predictions are bitwise the direct forward's;
* the scheduler flushes on max_batch OR max_wait_ms, propagates
  per-request deadlines (early flush to make them, typed ``Expired``
  past them), and the degradation ladder first shrinks the batching
  delay, then sheds lowest-priority requests with typed ``Overloaded``
  — queue growth is bounded by construction;
* a warmed ladder never recompiles, whatever request-size mix arrives;
* streaming tables serve READ-ONLY: cold/evicted ids resolve to their
  shared bucket rows, admitted ids to their slots (agreeing with the
  rows the train path writes), and the slot map/sketch are
  bitwise-unchanged by any amount of serving.
"""

import json

import numpy as np
import jax
import jax.numpy as jnp
import optax
import pytest

from distributed_embeddings_tpu.parallel import (
    DistributedEmbedding, Expired, Overloaded, Request, ServeConfig,
    Served, ServingRuntime, SparseSGD, StreamingConfig,
    init_hybrid_state, init_streaming, make_hybrid_eval_step,
    make_hybrid_train_step)
from distributed_embeddings_tpu.parallel import serving as sv
from distributed_embeddings_tpu.parallel import streaming as smod
from distributed_embeddings_tpu.utils import mplane, obs


class ManualClock:
    """Injectable clock: tests own time, so wait/deadline semantics are
    deterministic (no wall-clock sleeps anywhere)."""

    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        return self.t


def _pred_fn(dp, outs, batch):
    p = sum(jnp.sum(o, -1) for o in outs)
    if batch is not None:
        p = p + jnp.sum(batch, -1)
    return p


def _build(configs=None, world=1, mesh=None, **cfg_kw):
    configs = configs or [{"input_dim": 100, "output_dim": 4},
                          {"input_dim": 50, "output_dim": 4}]
    de = DistributedEmbedding(configs, world_size=world)
    tx = optax.sgd(0.1)
    state = init_hybrid_state(de, SparseSGD(), {"w": jnp.ones((4, 1))},
                              tx, jax.random.key(0), mesh=mesh)
    clock = ManualClock()
    cfg_kw.setdefault("max_batch", 16)
    cfg_kw.setdefault("max_wait_ms", 5)
    cfg_kw.setdefault("deadline_ms", 1000)
    cfg_kw.setdefault("max_queue", 64)
    rt = ServingRuntime(de, _pred_fn, state, mesh=mesh,
                        config=ServeConfig(**cfg_kw), clock=clock)
    return de, state, rt, clock


def _tmpl(n_inputs=2, numerical=3):
    return ([np.zeros(2, np.int32) for _ in range(n_inputs)],
            np.zeros((2, numerical), np.float32))


def _req(rng, de_sizes=(100, 50), n=3, numerical=3, **kw):
    return sv.synthetic_request(rng, list(de_sizes), n,
                                numerical=numerical, **kw)


# ------------------------------------------------------------- the ladder


def test_default_ladder_is_pow2_world_multiples():
    assert sv.resolve_rungs(ServeConfig(max_batch=64), world=1) \
        == (8, 16, 32, 64)
    # the top rung rounds DOWN to a world multiple: the ladder must
    # never exceed the configured max_batch (admission and the
    # max_queue validation bind against it)
    assert sv.resolve_rungs(ServeConfig(max_batch=100), world=8) \
        == (8, 16, 32, 64, 96)
    # a max_batch below the pow2 floor is its own single rung
    assert sv.resolve_rungs(ServeConfig(max_batch=4), world=1) == (4,)
    # ...but never below one world row
    assert sv.resolve_rungs(ServeConfig(max_batch=4, max_queue=8),
                            world=8) == (8,)


def test_explicit_rungs_validated():
    assert sv.resolve_rungs(
        ServeConfig(rungs=(16, 64)), world=8) == (16, 64)
    with pytest.raises(ValueError, match="ascending"):
        sv.resolve_rungs(ServeConfig(rungs=(64, 16)), world=1)
    with pytest.raises(ValueError, match="multiple of world"):
        sv.resolve_rungs(ServeConfig(rungs=(12,)), world=8)


def test_config_validation():
    with pytest.raises(ValueError, match="shed_frac"):
        ServeConfig(shed_frac=0.0)
    with pytest.raises(ValueError, match="max_queue"):
        ServeConfig(max_batch=64, max_queue=32)


# -------------------------------------------------- coalescing + packing


def test_coalesced_predictions_match_direct_forward():
    de, state, rt, clock = _build()
    rt.warmup(_tmpl())
    rng = np.random.default_rng(0)
    r1, r2 = _req(rng, n=3), _req(rng, n=5)
    assert rt.submit(r1, now=0.0) is None
    assert rt.submit(r2, now=0.0) is None
    assert rt.poll(now=0.0) == []          # neither full nor timed out
    clock.t = 0.006
    res = rt.poll(now=0.006)
    served = {r.rid: r for r in res if isinstance(r, Served)}
    assert len(served) == 2 and all(r.rung == 8 for r in served.values())
    for req in (r1, r2):
        direct = _pred_fn(None, de(state.emb_params,
                                   [jnp.asarray(c) for c in req.cats]),
                          jnp.asarray(req.batch))
        np.testing.assert_array_equal(
            np.asarray(served[req.rid].predictions), np.asarray(direct))
    s = rt.stats()
    assert s["flushes"] == 1 and s["pad_fraction"] == 0.0
    assert s["served_samples"] == 8


def test_multihot_and_ragged_inputs_pack():
    configs = [{"input_dim": 100, "output_dim": 4},
               {"input_dim": 60, "output_dim": 4, "combiner": "sum"},
               {"input_dim": 40, "output_dim": 4, "combiner": "sum"}]
    de, state, rt, clock = _build(configs, ragged_hotness=3)
    tmpl = ([np.zeros(2, np.int32), np.zeros((2, 2), np.int32),
             [[1], [2, 3]]], np.zeros((2, 3), np.float32))
    rt.warmup(tmpl)
    req = Request(
        cats=[np.asarray([5, 6, 7], np.int32),
              np.asarray([[1, 2], [3, 4], [5, 6]], np.int32),
              [[10, 11], [], [12, 13, 14, 15]]],  # last row clips to 3
        batch=np.ones((3, 3), np.float32))
    assert rt.submit(req, now=0.0) is None
    clock.t = 0.01
    res = rt.poll(now=0.01)
    (served,) = [r for r in res if isinstance(r, Served)]
    from distributed_embeddings_tpu.ops.embedding_lookup import Ragged
    rag = Ragged(values=jnp.asarray([10, 11, 12, 13, 14, 0, 0, 0, 0],
                                    jnp.int32),
                 row_splits=jnp.asarray([0, 2, 2, 5], jnp.int32))
    direct = _pred_fn(None, de(state.emb_params,
                               [jnp.asarray(req.cats[0]),
                                jnp.asarray(req.cats[1]), rag]),
                      jnp.ones((3, 3), jnp.float32))
    np.testing.assert_array_equal(np.asarray(served.predictions),
                                  np.asarray(direct))
    assert rt.stats()["ragged_clipped"] == 1


def _per_leaf_pack(rt, reqs, rung):
    """The reference: the padded rung-shaped arrays, one an input leaf,
    as the runtime built (and sent) them before it packed them into one
    buffer. Dense ids and batch leaves are global ``[rung, ...]``
    arrays; a ragged input is its per-shard CSR segments concatenated."""
    world = rt.world
    offsets = np.cumsum([0] + [r.n for r in reqs])[:-1]
    cats = []
    for i, (kind, hot) in enumerate(rt._input_spec):
        if kind == "d":
            buf = np.zeros((rung,) if hot == 1 else (rung, hot), np.int32)
            for r, o in zip(reqs, offsets):
                buf[o:o + r.n] = np.asarray(r.cats[i], np.int32)
            cats.append(buf)
            continue
        b_local = rung // world
        values = np.zeros((world * b_local * hot,), np.int32)
        splits = np.zeros((world * (b_local + 1),), np.int32)
        rows = [[] for _ in range(rung)]
        for r, o in zip(reqs, offsets):
            rows[o:o + r.n] = r.cats[i]
        for s in range(world):
            pos = 0
            for j in range(b_local):
                row = rows[s * b_local + j]
                at = s * b_local * hot + pos
                values[at:at + len(row)] = row
                pos += len(row)
                splits[s * (b_local + 1) + j + 1] = pos
        cats.append((values, splits))
    batch = {}
    for k, tmpl in reqs[0].batch.items():
        buf = np.zeros((rung,) + tmpl.shape[1:], tmpl.dtype)
        for r, o in zip(reqs, offsets):
            buf[o:o + r.n] = r.batch[k]
        batch[k] = buf
    return cats, batch


def _tree_pred_fn(dp, outs, batch):
    p = sum(jnp.sum(o, -1) for o in outs)
    for k in ("f", "h", "i"):
        p = p + jnp.sum(batch[k].astype(jnp.float32), -1)
    return p + batch["flag"]


@pytest.mark.parametrize("rung", [8, 16])
@pytest.mark.parametrize("world", [1, 8])
def test_packed_layout_unpacks_to_the_per_leaf_arrays(world, rung):
    """Packing N requests into the rung's one staging buffer and running
    the program's own unpack (jitted, under the mesh where there is one)
    gives bit for bit the arrays the per-leaf packer built, padding
    included: one-hot, multi-hot and ragged inputs, and a batch tree of
    float32, bfloat16 (odd count: padded to whole words), int32 and bool
    leaves; requests straddle the shards' rows at world 8."""
    from jax.sharding import Mesh, PartitionSpec as P
    from distributed_embeddings_tpu.ops.embedding_lookup import Ragged

    mesh = (Mesh(np.array(jax.devices()[:8]), ("data",)) if world > 1
            else None)
    kinds = ["one", "multi", "ragged"] * 3         # 9 inputs: >= world
    configs = [{"input_dim": 50 + i, "output_dim": 4,
                **({} if k == "one" else {"combiner": "sum"})}
               for i, k in enumerate(kinds)]
    de = DistributedEmbedding(configs, world_size=world)
    state = init_hybrid_state(de, SparseSGD(), {"w": jnp.ones((4, 1))},
                              optax.sgd(0.1), jax.random.key(0), mesh=mesh)
    rt = ServingRuntime(
        de, _tree_pred_fn, state, mesh=mesh, clock=ManualClock(),
        config=ServeConfig(rungs=(8, 16), max_wait_ms=5, deadline_ms=1000,
                           max_queue=64, ragged_hotness=3))
    rng = np.random.default_rng(rung + world)

    def request(n):
        cats = []
        for i, k in enumerate(kinds):
            if k == "one":
                cats.append(rng.integers(1, 50, n).astype(np.int32))
            elif k == "multi":
                cats.append(rng.integers(1, 50, (n, 2)).astype(np.int32))
            else:
                cats.append([list(rng.integers(1, 50, rng.integers(0, 4)))
                             for _ in range(n)])
        return Request(cats=cats, batch={
            "f": rng.normal(size=(n, 3)).astype(np.float32),
            "h": rng.normal(size=(n, 3)).astype(jnp.bfloat16),
            "i": rng.integers(-9, 9, (n, 2)).astype(np.int32),
            "flag": rng.integers(0, 2, n).astype(bool)})

    tmpl = request(2)
    rt.warmup((tmpl.cats, tmpl.batch))
    sizes = (2, 3, 1) if rung == 8 else (3, 5, 1, 4)   # 2 and 3 rows pad
    reqs = [rt._normalize(request(n), 0.0) for n in sizes]
    packed, offsets = rt._pack(reqs, rung)
    assert offsets == list(np.cumsum((0,) + sizes)[:-1])
    layout = rt._program(rung)[0]
    assert packed.shape == (world * layout.words,)
    assert packed.nbytes == layout.nbytes
    unpack = layout.unpack
    if world > 1:
        unpack = jax.shard_map(unpack, mesh=mesh, in_specs=P("data"),
                               out_specs=P("data"))
    cats, batch = jax.jit(unpack)(packed)
    want_cats, want_batch = _per_leaf_pack(rt, reqs, rung)
    assert len(cats) == len(want_cats) == 9
    for got, want in zip(cats, want_cats):
        if isinstance(want, tuple):
            assert isinstance(got, Ragged)
            got = (got.values, got.row_splits)
        for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            assert g.dtype == w.dtype and g.shape == w.shape
            np.testing.assert_array_equal(np.asarray(g), w)
    assert set(batch) == set(want_batch)
    for k, w in want_batch.items():
        g = np.asarray(batch[k])
        assert g.dtype == w.dtype and g.shape == w.shape, k
        assert g.tobytes() == w.tobytes(), k
    # and the flush that carries them answers what the per-leaf program
    # answers on the per-leaf arrays
    for r in reqs:
        assert rt.submit(Request(cats=r.cats, batch=r.batch),
                         now=0.0) is None
    served = [x for x in rt.poll(now=1.0) if isinstance(x, Served)]
    assert [x.rung for x in served] == [rung] * len(reqs)
    ev = make_hybrid_eval_step(de, _tree_pred_fn, mesh=mesh)
    direct = np.asarray(ev(
        state, [Ragged(*map(jnp.asarray, c)) if isinstance(c, tuple)
                else jnp.asarray(c) for c in want_cats],
        jax.tree.map(jnp.asarray, want_batch)))
    for x, o, n in zip(served, offsets, sizes):
        np.testing.assert_array_equal(np.asarray(x.predictions),
                                      direct[o:o + n])


def test_request_validation():
    de, state, rt, clock = _build()
    rt.warmup(_tmpl())
    with pytest.raises(ValueError, match="categorical inputs"):
        rt.submit(Request(cats=[np.zeros(2, np.int32)]), now=0.0)
    with pytest.raises(ValueError, match="largest rung"):
        rt.submit(Request(cats=[np.zeros(99, np.int32),
                                np.zeros(99, np.int32)],
                          batch=np.zeros((99, 3), np.float32)), now=0.0)
    with pytest.raises(ValueError, match="empty"):
        rt.submit(Request(cats=[np.zeros(0, np.int32),
                                np.zeros(0, np.int32)],
                          batch=np.zeros((0, 3), np.float32)), now=0.0)
    with pytest.raises(ValueError, match="samples"):
        rt.submit(Request(cats=[np.zeros(2, np.int32),
                                np.zeros(3, np.int32)],
                          batch=np.zeros((2, 3), np.float32)), now=0.0)
    # a malformed BATCH is rejected at submit, while nothing is queued —
    # failing at pack time would crash the flush and lose every healthy
    # request coalesced with it
    with pytest.raises(ValueError, match="batch spec"):
        rt.submit(Request(cats=[np.zeros(2, np.int32),
                                np.zeros(2, np.int32)],
                          batch=np.zeros((2, 5), np.float32)), now=0.0)
    with pytest.raises(ValueError, match="batch spec"):
        rt.submit(Request(cats=[np.zeros(2, np.int32),
                                np.zeros(2, np.int32)]), now=0.0)
    assert rt.queued_samples == 0


# ------------------------------------------------- the deadline scheduler


def test_flush_on_max_wait():
    de, state, rt, clock = _build(max_wait_ms=5)
    rt.warmup(_tmpl())
    rng = np.random.default_rng(1)
    rt.submit(_req(rng, n=2), now=0.0)
    assert rt.poll(now=0.004) == []
    clock.t = 0.005
    res = rt.poll(now=0.005)
    assert [type(r) for r in res] == [Served]
    assert res[0].latency_ms == pytest.approx(5.0)


def test_flush_on_full_rung():
    de, state, rt, clock = _build(max_batch=16)
    rt.warmup(_tmpl())
    rng = np.random.default_rng(2)
    for _ in range(4):
        rt.submit(_req(rng, n=4), now=0.0)
    res = rt.poll(now=0.0)   # 16 queued = the largest rung: no waiting
    assert sum(isinstance(r, Served) for r in res) == 4
    assert rt.stats()["rung_flushes"] == {"16": 1}


def test_poll_yields_while_batching(monkeypatch):
    """With requests queued and none due, poll() on its own clock sleeps
    until one is, POLL_IDLE_S at most, so that a caller looping on it
    does not spin through the batching delay; an explicit ``now``, an
    empty queue or a due flush never sleeps."""
    de, state, rt, clock = _build(max_wait_ms=5)
    rt.warmup(_tmpl())
    slept = []
    monkeypatch.setattr(sv.time, "sleep", slept.append)
    rng = np.random.default_rng(5)
    assert rt.poll() == [] and slept == []          # nothing queued
    rt.submit(_req(rng, n=2))
    assert rt.poll(now=0.001) == [] and slept == []  # the caller's time
    assert rt.poll() == []
    assert slept == [sv.POLL_IDLE_S]
    clock.t = 0.0049                                 # due in 0.1 ms
    assert rt.poll() == []
    assert slept[1] == pytest.approx(1e-4)
    clock.t = 0.005
    assert [type(r) for r in rt.poll()] == [Served]  # due: no sleep
    assert len(slept) == 2


def test_deadline_propagation_flushes_early():
    # huge max_wait: only the deadline can force this flush
    de, state, rt, clock = _build(max_wait_ms=10_000)
    rt.warmup(_tmpl())
    rng = np.random.default_rng(3)
    req = _req(rng, n=2)
    req.deadline_ms = 20.0
    rt.submit(req, now=0.0)
    assert rt.poll(now=0.010) == []
    res = rt.poll(now=0.020)   # t + est >= deadline -> flush now
    assert [type(r) for r in res] == [Served]
    assert not res[0].deadline_missed


def test_expired_requests_drop_typed():
    de, state, rt, clock = _build(max_wait_ms=10_000)
    rt.warmup(_tmpl())
    rng = np.random.default_rng(4)
    req = _req(rng, n=2)
    req.deadline_ms = 5.0
    rt.submit(req, now=0.0)
    clock.t = 0.05
    res = rt.poll(now=0.05)
    assert [type(r) for r in res] == [Expired]
    assert res[0].deadline_ms == 5.0
    s = rt.stats()
    assert s["expired"] == 1 and s["deadline_missed"] == 1
    assert s["served"] == 0 and rt.queued_samples == 0


def test_late_completion_marks_deadline_missed():
    de, state, rt, clock = _build(max_wait_ms=5)

    class SlowClock(ManualClock):
        def __call__(self):
            self.t += 0.02   # every clock read advances 20ms
            return self.t

    rt._clock = SlowClock()
    rt.warmup(_tmpl())
    rng = np.random.default_rng(5)
    req = _req(rng, n=2)
    # deadline chosen so the request does NOT expire before the flush
    # (submit reads t=0.02s -> deadline 0.05s; poll reads 0.04s < 0.05)
    # but the flush's completion read (0.08s) lands past it
    req.deadline_ms = 30.0
    rt.submit(req)
    res = rt.poll()
    served = [r for r in res if isinstance(r, Served)]
    assert len(served) == 1   # flushed, not expired
    assert served[0].deadline_missed
    assert rt.stats()["deadline_missed"] == 1


# ---------------------------------------------- overload admission control


def test_overload_sheds_typed_and_recovers():
    obs.drain_events()
    de, state, rt, clock = _build(max_batch=8, max_queue=16,
                                  shed_frac=0.5, max_wait_ms=10_000)
    rt.warmup(_tmpl())
    rng = np.random.default_rng(6)
    rejections = []
    for _ in range(12):
        r = rt.submit(_req(rng, n=2), now=0.0)
        if r is not None:
            rejections.append(r)
    # 16-sample queue: 4 fit below the 8-sample shed line... queue fills
    # to the cap, everything past it is typed, queue NEVER exceeds cap
    assert rt.queued_samples <= 16
    assert rejections and all(isinstance(r, Overloaded)
                              for r in rejections)
    assert {r.reason for r in rejections} <= {"load_shed", "queue_full"}
    assert rt.level == 2
    deg = obs.drain_events("serve_degraded")
    assert deg and deg[-1]["level"] == 2
    # drain: the ladder must walk back down and say so
    res = rt.flush(now=0.0)
    assert sum(isinstance(r, Served) for r in res) > 0
    assert rt.level == 0
    rec = obs.drain_events("serve_recovered")
    assert rec and rec[-1]["level"] == 0
    s = rt.stats()
    assert s["shed"] == len(rejections) and s["degraded"] >= 1
    assert s["recovered"] >= 1


def test_priority_survives_shed_level():
    de, state, rt, clock = _build(max_batch=8, max_queue=32,
                                  shed_frac=0.25, max_wait_ms=10_000)
    rt.warmup(_tmpl())
    rng = np.random.default_rng(7)
    while rt.queued_samples < 8:   # climb past the shed line
        assert rt.submit(_req(rng, n=2), now=0.0) is None
    assert rt.level == 2
    lo = rt.submit(_req(rng, n=2), now=0.0)
    assert isinstance(lo, Overloaded) and lo.reason == "load_shed"
    hi = _req(rng, n=2)
    hi.priority = 1
    assert rt.submit(hi, now=0.0) is None   # high priority still admitted
    full = _req(rng, n=2)
    full.priority = 99
    while rt.submit(full, now=0.0) is None:  # ...until the hard cap
        full = _req(rng, n=2)
        full.priority = 99
    rej = rt.submit(full, now=0.0)
    assert isinstance(rej, Overloaded) and rej.reason == "queue_full"


def test_pressure_level_shrinks_batching_delay():
    de, state, rt, clock = _build(max_batch=8, max_queue=64,
                                  max_wait_ms=10_000)
    rt.warmup(_tmpl())
    rng = np.random.default_rng(8)
    for _ in range(4):
        rt.submit(_req(rng, n=2), now=0.0)
    # 8 queued >= largest rung -> level 1: flush NOW despite max_wait
    assert rt.level == 1
    res = rt.poll(now=0.0)
    assert sum(isinstance(r, Served) for r in res) == 4


def test_flush_failure_answers_typed(monkeypatch):
    """A flush that raises (injected fault, transient backend error)
    answers its coalesced requests with typed Failed instead of the
    exception escaping poll() and losing them — and the loop keeps
    serving afterwards."""
    from distributed_embeddings_tpu.utils import runtime as rmod

    de, state, rt, clock = _build()
    rt.warmup(_tmpl())
    rmod.reset_fault_counts()
    monkeypatch.setenv(rmod.FAULT_ENV, "raise:serve_step:1")
    rng = np.random.default_rng(11)
    rt.submit(_req(rng, n=2), now=0.0)
    clock.t = 0.01
    res = rt.poll(now=0.01)
    assert [type(r) for r in res] == [sv.Failed]
    assert "FaultInjected" in res[0].reason
    assert rt.stats()["failed"] == 1 and rt.queued_samples == 0
    deg = obs.counters().get("event_serve_flush_error", 0)
    assert deg >= 1
    # the fault budget is spent: service continues normally
    rt.submit(_req(rng, n=2), now=0.02)
    clock.t = 0.03
    res = rt.poll(now=0.03)
    assert [type(r) for r in res] == [Served]


# ------------------------------------------------------ recompile hygiene


def test_mixed_sizes_never_recompile_after_warmup():
    de, state, rt, clock = _build(max_batch=32)
    rt.warmup(_tmpl())
    assert rt.warmup_compiles >= len(rt.rungs)
    rng = np.random.default_rng(9)
    for i in range(10):
        rt.submit(_req(rng, n=1 + (i * 3) % 7), now=clock.t)
        clock.t += 0.01
        rt.poll(now=clock.t)
    clock.t += 1.0
    rt.poll(now=clock.t)
    s = rt.stats()
    assert s["served"] == 10
    assert s["steady_state_recompiles"] == 0
    assert len(s["rung_flushes"]) >= 1


# ----------------------------------------------------------- the auditor


def test_audit_serve_program_world1_has_no_collectives():
    de, state, rt, clock = _build()
    rt.warmup(_tmpl())
    rep = sv.audit_serve_program(rt)
    assert rep.violations == []
    assert rep.collective_counts.get("all_to_all", 0) == 0
    assert rep.collective_counts.get("psum", 0) == 0


@pytest.fixture
def mesh8():
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()[:8]), ("data",))


def _world8_configs():
    return ([{"input_dim": 64, "output_dim": 8},
             {"input_dim": 32 + 8, "output_dim": 8,
              "streaming": {"capacity": 32, "buckets": 8}}]
            + [{"input_dim": 24 + i, "output_dim": 4} for i in range(6)])


def test_audit_serve_program_world8_forward_contract(mesh8):
    de, state, rt, clock = _build(
        [{"input_dim": 50 + i, "output_dim": 4} for i in range(8)],
        world=8, mesh=mesh8, max_batch=16)
    rt.warmup(_tmpl(n_inputs=8))
    rep = sv.audit_serve_program(rt)
    assert rep.violations == []
    # forward-only: id + out exchange, NO grad exchange, NO psum
    assert rep.a2a_census() == {"id_exchange_fwd": 1,
                                "out_exchange_fwd": 1}
    assert rep.collective_counts.get("psum", 0) == 0
    assert rep.host_interop == []


# ------------------------------------- read-only streaming serve (world 8)


def test_streaming_serve_world8_read_only_and_remap_agreement(mesh8):
    """The satellite-4 battery: at world 8, (a) serving leaves the slot
    map/sketch bitwise-unchanged, (b) cold ids resolve to shared bucket
    rows (two ids sharing a bucket serve identical embeddings), (c) the
    serve remap agrees with the train path — an id the TRAIN step
    admitted serves from its slot (diverging from its bucket-mate), and
    a later train update to that slot is visible to eval."""
    configs = _world8_configs()
    de = DistributedEmbedding(configs, world_size=8)
    scfg = StreamingConfig(admit_min_count=2, evict_margin=1, depth=2,
                           buckets=128)
    tx = optax.sgd(0.05)
    state = init_hybrid_state(de, SparseSGD(), {"w": jnp.ones((4, 1))},
                              tx, jax.random.key(0), mesh=mesh8)
    sstate = init_streaming(de, scfg, mesh=mesh8)

    def loss_fn(dp, outs, b):
        return (sum(jnp.mean(o) for o in outs) * jnp.mean(dp["w"])
                + jnp.mean(b))

    step = make_hybrid_train_step(de, loss_fn, tx, SparseSGD(),
                                  mesh=mesh8, dynamic=scfg,
                                  with_metrics=True, nan_guard=False)
    B = 16
    zeros = [jnp.zeros((B,), jnp.int32) if i != 1 else None
             for i in range(8)]

    def cats_with(ext_id):
        return [jnp.full((B,), ext_id, jnp.int32) if i == 1 else z
                for i, z in enumerate(zeros)]

    hot = 987_654_321
    b_t = jnp.zeros((B,), jnp.float32)
    m = None
    for _ in range(3):
        _, state, m, sstate = step(state, cats_with(hot), b_t, sstate)
    assert float(np.asarray(m["stream_hit_ids"]).sum()) > 0  # admitted

    # two COLD external ids engineered to share a hash bucket, one in a
    # different bucket — computed BEFORE warmup (the eager hash mixes
    # compile tiny programs that must not count as steady-state serves)
    tid = jnp.asarray(1, jnp.int32)
    nb = 8
    base = 111_111
    cands = jnp.arange(base, base + 4096, dtype=jnp.int32)
    buckets = np.asarray(smod._mix(cands, tid, smod._H_BUCKET)
                         % np.uint32(nb))
    cold_a = base
    cold_b = base + int(np.nonzero(buckets[1:] == buckets[0])[0][0]) + 1
    cold_c = base + int(np.nonzero(buckets[1:] != buckets[0])[0][0]) + 1

    clock = ManualClock()
    rt = ServingRuntime(
        de, _pred_fn, state, mesh=mesh8,
        config=ServeConfig(max_batch=16, max_wait_ms=2,
                           deadline_ms=1000, max_queue=64),
        streaming=(scfg, sstate), clock=clock)
    rt.warmup(_tmpl(n_inputs=8))
    before = jax.tree.map(np.asarray, rt.streaming_state)

    def serve_one(ext_id):
        req = Request(cats=[np.full((8,), ext_id, np.int32) if i == 1
                            else np.zeros((8,), np.int32)
                            for i in range(8)],
                      batch=np.zeros((8, 3), np.float32))
        rt.submit(req, now=clock.t)
        clock.t += 0.01
        res = rt.poll(now=clock.t)
        (r,) = [x for x in res if isinstance(x, Served)]
        return np.asarray(r.predictions)

    pa, pb, pc, ph = (serve_one(cold_a), serve_one(cold_b),
                      serve_one(cold_c), serve_one(hot))
    # (b) cold ids SHARE their bucket row: same bucket -> same serving
    np.testing.assert_array_equal(pa, pb)
    # the admitted id reads its own (zero-init, trained) slot row, not
    # the bucket row its cold self would have used
    assert not np.array_equal(ph, pa) or not np.array_equal(ph, pc)
    # (a) serving mutated NOTHING
    after = jax.tree.map(np.asarray, rt.streaming_state)
    for x, y in zip(jax.tree.leaves(before), jax.tree.leaves(after)):
        np.testing.assert_array_equal(x, y)
    assert rt.stats()["steady_state_recompiles"] == 0

    # (c) eval-vs-train remap agreement: the serving runtime's answer is
    # bitwise the plain eval step's for the same inputs...
    ev = make_hybrid_eval_step(de, _pred_fn, mesh=mesh8, dynamic=scfg)
    direct = np.asarray(ev(
        state, [jnp.full((8,), hot, jnp.int32) if i == 1
                else jnp.zeros((8,), jnp.int32) for i in range(8)],
        jnp.zeros((8, 3), jnp.float32), sstate))
    np.testing.assert_array_equal(ph, direct)
    # ...and a train update to the admitted slot is what eval sees next
    _, state2, _, sstate2 = step(state, cats_with(hot),
                                 jnp.ones((B,), jnp.float32), sstate)
    rt.state, rt.streaming_state = state2, sstate2
    ph2 = serve_one(hot)
    assert not np.array_equal(ph2, ph)


# ------------------------------------------------------------- the driver


def test_drive_applies_burst_positions():
    de, state, rt, clock = _build(max_batch=32, max_queue=2048,
                                  deadline_ms=60_000)
    import time as _time

    rt._clock = _time.monotonic   # drive runs in real time
    rt.warmup(_tmpl())
    rng = np.random.default_rng(10)

    def make_request(i):
        return _req(rng, n=1)

    res_plain = sv.drive(rt, make_request, qps=100, duration_s=0.5,
                         burst_positions=())
    n_plain = len(res_plain)
    res_burst = sv.drive(rt, make_request, qps=100, duration_s=0.5,
                         burst_positions=(0,), burst_x=4.0)
    # second 0 spans the whole 0.5s stream: ~4x the arrivals
    assert len(res_burst) > 2 * n_plain
    assert rt.stats()["steady_state_recompiles"] == 0


def test_realtime_driver_concurrent_with_publisher():
    """ISSUE 18: the wall-clock driver runs on its OWN thread while the
    'trainer' (this thread) keeps publishing snapshots — freshness_p95_s
    must come out of true concurrency, every request must come back
    typed (none lost, none hung), and the publish/flush race must never
    produce a torn read (the RCU contract under an actual second
    thread)."""
    import time as _time

    de, state, rt, clock = _build(max_batch=32, max_queue=4096,
                                  deadline_ms=60_000, max_wait_ms=2)
    rt._clock = _time.monotonic   # the driver runs in real time
    rt.warmup(_tmpl())
    rt.install_snapshot(state, version=1, train_step=0)
    rng = np.random.default_rng(11)
    drv = sv.RealtimeDriver(rt, lambda i: _req(rng, n=1), qps=300,
                            duration_s=None, burst_positions=(),
                            drain_s=30.0)
    drv.start()
    t0, v = _time.monotonic(), 1
    while _time.monotonic() - t0 < 0.6:
        v += 1
        rt.install_snapshot(state, version=v, train_step=v)
        rt.note_train_step(v)
        _time.sleep(0.02)
    drv.stop()
    drv.join(timeout=60)
    results = drv.results()
    assert drv.submitted > 0
    # conservation across threads: every submitted rid answered once
    assert sorted(r.rid for r in results) == list(range(drv.submitted))
    served = [r for r in results if isinstance(r, sv.Served)]
    assert served and {r.version for r in served} != {1}  # saw republishes
    st = rt.stats()
    assert st["freshness_p95_s"] is not None
    assert st["freshness_p95_s"] >= 0.0
    assert st["steady_state_recompiles"] == 0


def test_unavailable_is_typed_and_ranked_below_stale():
    """The outage response: carries its provenance, renders a status
    like every other typed result, and is NOT a Served."""
    u = sv.Unavailable(rid=7, latency_ms=0.0, reason="worker_down",
                       outage_s=1.5, restarts=2)
    assert u.status == "unavailable"
    assert not isinstance(u, sv.Served)
    assert (u.reason, u.outage_s, u.restarts) == ("worker_down", 1.5, 2)


def test_stats_surface():
    de, state, rt, clock = _build()
    rt.warmup(_tmpl())
    s = rt.stats()
    for k in ("served", "shed", "deadline_missed", "pad_fraction",
              "queue_depth_p95", "latency_p99_ms", "level_name",
              "steady_state_recompiles", "warmup_compiles",
              "latency_stages_ms", "p99_dominant_stage"):
        assert k in s
    assert s["level_name"] == "healthy"


# ---------------------------------------------- observability plane views


class TickClock(ManualClock):
    """Monotone clock that advances a hair on every read, so the flush
    timestamps (t0/t_pack/t_disp/t_dev/t1) are strictly increasing and
    every decomposition span is nonzero."""

    def __call__(self) -> float:
        self.t += 1e-4
        return self.t


def _build_ticking(**cfg_kw):
    de, state, rt, clock = _build(**cfg_kw)
    tick = TickClock()
    tick.t = clock.t
    rt._clock = tick
    return de, state, rt, tick


def _drive_obs(rt, clock, rng, rounds=40):
    lats = []
    for i in range(rounds):
        assert rt.submit(_req(rng, n=2)) is None
        # varied queue waits, all past max_wait_ms so every round
        # flushes exactly its own request (counts stay exact)
        clock.t += 0.006 + 0.0015 * (i % 9)
        for r in rt.poll():
            assert isinstance(r, Served)
            lats.append(r.latency_ms)
    return lats


def test_served_spans_sum_exactly_to_latency():
    de, state, rt, clock = _build_ticking()
    rt.warmup(_tmpl())
    rng = np.random.default_rng(0)
    seen = 0
    for i in range(6):
        assert rt.submit(_req(rng, n=2)) is None
        clock.t += 0.007
        for r in rt.poll():
            assert isinstance(r, Served)
            assert set(r.spans) == {"queue_wait_ms", "coalesce_ms",
                                    "dispatch_ms", "device_compute_ms",
                                    "reply_slice_ms"}
            # the five stages are a PARTITION of the request's life:
            # they sum to the end-to-end latency by construction
            assert sum(r.spans.values()) == pytest.approx(
                r.latency_ms, rel=1e-9)
            assert all(v >= 0.0 for v in r.spans.values())
            assert r.spans["queue_wait_ms"] > 0
            seen += 1
    assert seen == 6


def _host_spans(capture_dir):
    """The ``detpu/...`` host events of a profiler capture, by thread
    and in order of start: ``(name, ts, ts + dur, args)``."""
    from distributed_embeddings_tpu.utils import traceparse

    (path,) = traceparse.trace_files(capture_dir)
    by_thread = {}
    for e in traceparse.load_trace(path)["traceEvents"]:
        if e.get("ph") == "X" and obs.is_span_event(e.get("name")):
            by_thread.setdefault((e["pid"], e["tid"]), []).append(
                (e["name"][len(obs.SCOPE_PREFIX) + 1:], e["ts"],
                 e["ts"] + e["dur"],
                 e.get("args") or {}))
    return {t: sorted(v, key=lambda x: (x[1], -x[2]))
            for t, v in by_thread.items()}


def test_flush_spans_land_in_a_profiler_capture(tmp_path):
    """Under a jax.profiler capture one flush of two requests leaves one
    serve/flush span on the serving thread's line whose children are in
    the flush's own order, whose ``flush`` arg is the ordinal both
    requests' traces carry, and which holds ONE serve/h2d span, after
    the pack and before the dispatch, carrying the whole packed buffer.
    With no capture running nothing of the runtime changes: the tests
    around this one run the same code."""
    from distributed_embeddings_tpu.utils import reqtrace

    de, state, rt, clock = _build_ticking()
    rt.traces = reqtrace.TraceBuffer(
        capacity=8, sample=1.0, seed=0, enabled=True, process="serve",
        top_fn=rt._trace_top_decile)
    rt.warmup(_tmpl())
    rng = np.random.default_rng(4)
    reqs = [_req(rng, n=2), _req(rng, n=3)]
    with jax.profiler.trace(str(tmp_path)):
        for r in reqs:
            assert rt.submit(r) is None
        clock.t += 0.007
        served = rt.poll()
    assert [type(r) for r in served] == [Served, Served]
    for r in served:
        assert set(r.spans) == {f"{st}_ms" for st in sv.STAGES}
        assert sum(r.spans.values()) == pytest.approx(r.latency_ms,
                                                      rel=1e-9)
    threads = _host_spans(str(tmp_path))
    (spans,) = threads.values()        # one thread submitted and polled
    submits = [s for s in spans if s[0] == "serve/submit"]
    assert [(int(s[3]["rid"]), int(s[3]["n"])) for s in submits] \
        == [(r.rid, r.n) for r in reqs]
    (flush,) = [s for s in spans if s[0] == "serve/flush"]
    assert {k: int(v) for k, v in flush[3].items()} == {
        "flush": 1, "rung": 8, "requests": 2, "samples": 5}
    assert {t["attrs"]["flush"] for t in rt.traces.snapshot()} == {1}
    assert len(rt.traces.snapshot()) == 2
    inside = [s for s in spans if s is not flush
              and s[1] >= flush[1] and s[2] <= flush[2]]
    assert len(inside) == len(spans) - len(submits) - 1
    names = [s[0] for s in inside]
    # everything is packed, then sent once; the call, the wait, the reply
    assert names[-4:] == ["serve/h2d", "serve/dispatch", "serve/fetch",
                          "serve/reply"]
    assert names[:-4] and set(names[:-4]) == {"serve/pack"}
    for a, b in zip(inside, inside[1:]):
        assert a[2] <= b[1]            # siblings: none overlaps the next
    # rung 8 of two one-hot inputs and three float32 features: 8 x 5 words
    layout = rt._program(8)[0]
    assert int(inside[-4][3]["bytes"]) == layout.nbytes == 8 * 5 * 4


@pytest.mark.parametrize("n_inputs", [2, 7])
def test_one_transfer_a_flush_whatever_the_leaves(tmp_path, n_inputs):
    """A served flush is one host-to-device transfer: 3 input leaves or
    8 (the categorical inputs and the numerical block), the capture
    holds one serve/h2d span inside the one serve/flush span."""
    de, state, rt, clock = _build_ticking(
        configs=[{"input_dim": 40 + i, "output_dim": 4}
                 for i in range(n_inputs)])
    rt.warmup(_tmpl(n_inputs=n_inputs))
    assert len(jax.tree.leaves(_tmpl(n_inputs=n_inputs))) == n_inputs + 1
    rng = np.random.default_rng(n_inputs)
    sizes = [40 + i for i in range(n_inputs)]
    with jax.profiler.trace(str(tmp_path)):
        for n in (3, 1, 2):
            assert rt.submit(_req(rng, sizes, n=n)) is None
        clock.t += 0.007
        served = rt.poll()
    assert [type(r) for r in served] == [Served] * 3
    (spans,) = _host_spans(str(tmp_path)).values()
    names = [s[0] for s in spans]
    assert names.count("serve/flush") == 1
    assert names.count("serve/h2d") == 1
    (h2d,) = [s for s in spans if s[0] == "serve/h2d"]
    assert int(h2d[3]["bytes"]) == 8 * (n_inputs + 3) * 4


def test_stats_sketch_percentiles_match_numpy_reference():
    # the serving battery's pin: sketch-backed stats() percentiles sit
    # within the sketch's guaranteed relative error of the numpy
    # reference over the SAME samples (method="lower" = the exact order
    # statistic at the sketch's rank definition, q * (count - 1))
    de, state, rt, clock = _build_ticking()
    rt.warmup(_tmpl())
    rng = np.random.default_rng(2)
    lats = _drive_obs(rt, clock, rng, rounds=60)
    assert len(lats) == 60
    s = rt.stats()
    arr = np.asarray(lats, np.float64)
    for key, q in (("latency_p50_ms", 50), ("latency_p95_ms", 95),
                   ("latency_p99_ms", 99)):
        ref = float(np.percentile(arr, q, method="lower"))
        assert s[key] == pytest.approx(ref, rel=0.011), key


def test_stage_decomposition_accounts_for_total_latency():
    de, state, rt, clock = _build_ticking()
    rt.warmup(_tmpl())
    rng = np.random.default_rng(3)
    lats = _drive_obs(rt, clock, rng, rounds=30)
    s = rt.stats()
    stages = s["latency_stages_ms"]
    assert set(stages) == set(sv.STAGES)
    for st in stages.values():
        assert st["count"] == len(lats)
        assert {"p50", "p95", "p99", "mean", "sum"} <= set(st)
    # per-request spans partition the latency, so the per-stage sketch
    # SUMS add up to the total served latency (exactly — sums are not
    # bucketed)
    total = sum(st["sum"] for st in stages.values())
    assert total == pytest.approx(sum(lats), rel=1e-9)
    assert s["p99_dominant_stage"] in stages
    # with these injected waits the queue dominates the tail
    assert s["p99_dominant_stage"] == "queue_wait"


def test_serving_registry_prometheus_surface():
    de, state, rt, clock = _build_ticking()
    rt.warmup(_tmpl())
    rng = np.random.default_rng(4)
    _drive_obs(rt, clock, rng, rounds=10)
    text = rt.metrics.render()
    assert "# TYPE detpu_serve_latency_ms summary" in text
    assert "detpu_serve_latency_ms_count 10" in text
    assert 'detpu_serve_stage_ms{stage="queue_wait",quantile="0.99"}' \
        in text
    assert 'detpu_serve_total{outcome="served"} 10' in text
    assert "detpu_serve_level 0" in text
    assert "detpu_serve_steady_state_recompiles 0" in text
    # the registry snapshot round-trips through JSON in mergeable form
    doc = json.loads(json.dumps(rt.metrics.to_dict()))
    lat = doc["detpu_serve_latency_ms"]["series"][0]["value"]
    assert mplane.QuantileSketch.from_dict(lat).count == 10
