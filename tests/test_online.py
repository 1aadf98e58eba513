"""Online learning runtime: RCU snapshot publication, the freshness
SLO, and bounded staleness under chaos (``parallel/online.py`` +
the serving runtime's snapshot side).

The semantics under test:

* staleness arithmetic — per-response ``staleness_steps`` /
  ``staleness_s`` and the ``freshness_p95_*`` stats measure the
  installed snapshot against the latest completed train step and the
  flush clock, deterministically under an injected clock;
* publication consistency — versions are strictly monotone (a
  regression raises), a streaming runtime refuses a snapshot without
  its matching streaming-state copy, and on the 8-virtual-device mesh
  a flush interleaved with a publisher observes exactly ONE whole
  version (bitwise the plain eval step's answer for that version's
  state — never a mid-publish mix);
* the freshness rung — when publication falls behind the step SLO the
  server sheds low-priority load with typed
  ``Overloaded(reason="stale_snapshot")``, rides the existing
  degradation ladder (level 2, ``snapshot_lagging`` event), and
  recovers the moment a fresh snapshot installs;
* rollback composition — when training rewinds under the published
  view, ``maybe_publish`` republishes the ring-candidate state at once
  with the version still advancing;
* chaos composition — the combined ``DETPU_FAULT=oovflood@P,burst@P``
  drill (a traffic spike of never-seen ids while serving) admits
  streaming ids, sheds only typed, recovers post-burst, and keeps 0
  steady-state recompiles; preemption mid-serve checkpoints a
  consistent (training state, published version) pair that auto-resume
  continues monotonically.
"""

import json
import os
import time

import numpy as np
import jax
import jax.numpy as jnp
import optax
import pytest

from distributed_embeddings_tpu.parallel import (
    DistributedEmbedding, OnlineConfig, OnlineRuntime, Overloaded,
    ServeConfig, Served, ServingRuntime, SnapshotPublisher, SparseAdagrad,
    SparseSGD, StreamingConfig, init_hybrid_state, init_streaming,
    make_hybrid_eval_step, make_hybrid_train_step, online_sidecar_path)
from distributed_embeddings_tpu.parallel import online as om
from distributed_embeddings_tpu.parallel import serving as sv
from distributed_embeddings_tpu.parallel import streaming as smod
from distributed_embeddings_tpu.utils import obs, runtime


class ManualClock:
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


# ------------------------------------------------- staleness arithmetic


def test_staleness_arithmetic_and_served_stamps():
    de, state, rt, clock = _build()
    rt.warmup(_tmpl())
    rt.install_snapshot(state, version=1, train_step=10,
                        published_t=0.0, now=0.0)
    s = rt.stats()
    assert s["snapshot_version"] == 1
    assert s["snapshot_train_step"] == 10
    assert not rt.freshness_stale
    # training advances 3 steps past the snapshot
    rt.note_train_step(13, now=1.0)
    rng = np.random.default_rng(0)
    assert rt.submit(_req(rng, n=2), now=1.0) is None
    clock.t = 1.5
    (r,) = rt.poll(now=1.5)
    assert isinstance(r, Served)
    assert r.version == 1
    assert r.staleness_steps == 3.0
    # seconds-staleness is measured at flush completion vs published_t
    assert r.staleness_s == pytest.approx(1.5)
    s = rt.stats()
    # stats() percentiles now come from mergeable log-bucketed sketches:
    # exact to within the sketch's guaranteed relative error (1%)
    assert s["freshness_p95_steps"] == pytest.approx(3.0, rel=0.011)
    assert s["freshness_p95_s"] == pytest.approx(1.5, rel=0.011)
    assert s["snapshots_installed"] == 1


def test_stats_freshness_none_before_any_snapshot_serve():
    de, state, rt, clock = _build()
    rt.warmup(_tmpl())
    s = rt.stats()
    assert s["freshness_p95_steps"] is None
    assert s["freshness_p95_s"] is None
    assert s["snapshot_version"] is None


def test_version_monotonicity_enforced():
    de, state, rt, clock = _build()
    rt.install_snapshot(state, version=3, train_step=1, now=0.0)
    with pytest.raises(ValueError, match="monotonic"):
        rt.install_snapshot(state, version=3, train_step=2, now=0.0)
    with pytest.raises(ValueError, match="monotonic"):
        rt.install_snapshot(state, version=2, train_step=2, now=0.0)
    rt.install_snapshot(state, version=4, train_step=2, now=0.0)
    assert rt.stats()["snapshot_version"] == 4


def test_streaming_runtime_requires_streaming_state_copy():
    configs = [{"input_dim": 20, "output_dim": 4},
               {"input_dim": 32 + 8, "output_dim": 4,
                "streaming": {"capacity": 32, "buckets": 8}}]
    de = DistributedEmbedding(configs, world_size=1)
    scfg = StreamingConfig(admit_min_count=2, evict_margin=1, depth=2,
                           buckets=64)
    tx = optax.sgd(0.1)
    state = init_hybrid_state(de, SparseSGD(), {"w": jnp.ones((4, 1))},
                              tx, jax.random.key(0))
    sstate = init_streaming(de, scfg)
    rt = ServingRuntime(de, _pred_fn, state, streaming=(scfg, sstate),
                        clock=ManualClock())
    with pytest.raises(ValueError, match="streaming_state"):
        rt.install_snapshot(state, version=1, train_step=0, now=0.0)
    rt.install_snapshot(state, sstate, version=1, train_step=0, now=0.0)


# ------------------------------------------------- the freshness rung


def test_freshness_rung_sheds_typed_and_recovers():
    obs.drain_events()
    de, state, rt, clock = _build()
    rt.warmup(_tmpl())
    rt.set_freshness_slo(max_steps=2)
    rt.install_snapshot(state, version=1, train_step=0, now=0.0)
    assert not rt.freshness_stale
    # within SLO: 2 steps behind is the boundary, still fresh
    rt.note_train_step(2, now=0.0)
    assert not rt.freshness_stale
    # past it: the rung engages
    rt.note_train_step(3, now=0.0)
    assert rt.freshness_stale
    lag = obs.drain_events("snapshot_lagging")
    assert lag and lag[-1]["lag_steps"] == 3
    assert rt.level == 2
    rng = np.random.default_rng(1)
    rej = rt.submit(_req(rng, n=2), now=0.0)
    assert isinstance(rej, Overloaded) and rej.reason == "stale_snapshot"
    hi = _req(rng, n=2)
    hi.priority = 1
    assert rt.submit(hi, now=0.0) is None  # high priority still admitted
    # a fresh publication recovers the rung immediately
    rt.install_snapshot(state, version=2, train_step=3, now=0.0)
    assert not rt.freshness_stale and rt.level == 0
    assert rt.submit(_req(rng, n=2), now=0.0) is None
    s = rt.stats()
    assert s["stale_shed"] == 1
    assert s["freshness_stale"] is False
    assert obs.drain_events("snapshot_published")


def test_freshness_wall_clock_slo():
    de, state, rt, clock = _build()
    rt.warmup(_tmpl())
    rt.set_freshness_slo(max_steps=0, max_s=10.0)  # 0 = steps unchecked
    rt.install_snapshot(state, version=1, train_step=0, now=0.0)
    rt.note_train_step(100, now=5.0)   # steps don't matter here
    assert not rt.freshness_stale
    clock.t = 11.0
    rt.poll(now=11.0)                  # poll refreshes wall-clock age
    assert rt.freshness_stale


# ------------------------------------------------ publisher semantics


def test_publisher_cadence_sidecar_and_rollback_rewind(tmp_path):
    obs.drain_events()
    de, state, rt, clock = _build()
    rt.warmup(_tmpl())
    side = online_sidecar_path(str(tmp_path / "ck"))
    pub = SnapshotPublisher(
        rt, config=OnlineConfig(publish_every_steps=3,
                                freshness_max_steps=8),
        sidecar_path=side, clock=clock)
    st = lambda k: state._replace(step=jnp.asarray(k, jnp.int32))
    assert pub.maybe_publish(st(0)) is not None        # first: always
    assert pub.maybe_publish(st(2)) is None            # off-cadence
    assert rt.stats()["snapshot_train_step"] == 0      # ...not installed
    snap = pub.maybe_publish(st(3))                    # cadence hit
    assert snap is not None and snap.version == 2
    assert json.load(open(side))["train_step"] == 3
    # rollback: training rewound under the published view -> immediate
    # republish, version still advancing while train_step goes BACK
    back = pub.maybe_publish(st(1))
    assert back is not None and back.version == 3 and back.train_step == 1
    assert obs.drain_events("snapshot_rewound")
    assert rt.stats()["snapshot_version"] == 3
    assert rt.stats()["snapshot_train_step"] == 1
    assert json.load(open(side)) ["version"] == 3


def test_publisher_resume_continues_version_counter(tmp_path):
    de, state, rt, clock = _build()
    rt.warmup(_tmpl())
    side = online_sidecar_path(str(tmp_path / "ck"))
    pub = SnapshotPublisher(rt, sidecar_path=side, clock=clock)
    pub.publish(state, train_step=4)
    pub.publish(state, train_step=5)
    assert json.load(open(side))["version"] == 2
    # "resume": a new publisher (fresh process) over the same sidecar
    de2, state2, rt2, clock2 = _build()
    pub2 = SnapshotPublisher(rt2, sidecar_path=side, resume=True,
                             clock=clock2)
    snap = pub2.publish(state2, train_step=6)
    assert snap.version == 3                  # monotone across the resume
    # resume=False starts a fresh lineage and deletes the stale record
    pub3 = SnapshotPublisher(rt2, sidecar_path=side, resume=False,
                             clock=clock2)
    assert not os.path.exists(side)
    assert pub3.version == 0


def test_published_buffers_are_real_copies():
    """Donation safety: the published view must survive the training
    step donating the source buffers — distinct device buffers, equal
    values."""
    de, state, rt, clock = _build()
    pub = SnapshotPublisher(rt, clock=clock)
    snap = pub.publish(state, train_step=0)
    src = jax.tree.leaves(state.emb_params)
    dst = jax.tree.leaves(snap.state.emb_params)
    for a, b in zip(src, dst):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert a.unsafe_buffer_pointer() != b.unsafe_buffer_pointer()


# --------------------------------------- no torn reads (8-device mesh)


@pytest.fixture
def mesh8():
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()[:8]), ("data",))


def test_no_torn_reads_under_interleaved_publication_mesh8(mesh8):
    """RCU on the real mesh: requests queued BEFORE a publish flush
    against the version installed at flush time — the whole new
    version, bitwise the plain eval step's answer for that state, never
    a mid-publish mix of old and new tables."""
    configs = [{"input_dim": 50 + i, "output_dim": 4} for i in range(8)]
    de, state, rt, clock = _build(configs, world=8, mesh=mesh8,
                                  max_batch=16)
    pub = SnapshotPublisher(rt, clock=clock)
    # two visibly different table generations, same shapes/shardings
    state_a = state
    state_b = state._replace(
        emb_params=jax.tree.map(lambda a: a + jnp.asarray(1, a.dtype),
                                state.emb_params),
        # derived from state.step so it keeps the mesh-replicated
        # placement a train step returns (a fresh jnp.asarray lands on
        # one device: a different jit cache key, i.e. a retrace)
        step=state.step + 7)
    ev = make_hybrid_eval_step(de, _pred_fn, mesh=mesh8)
    # one-time compiles (publisher cloners, the reference eval step)
    # land BEFORE the warmup baseline — the steady-state window then
    # covers the interleaved publish/serve sequence itself
    pub.warm(state_a)
    ev(state_a, [jnp.zeros(8, jnp.int32) for _ in range(8)],
       jnp.zeros((8, 3), jnp.float32))
    rt.warmup(_tmpl(n_inputs=8))
    rng = np.random.default_rng(3)

    def serve_one(req):
        rt.submit(req, now=clock.t)
        clock.t += 0.01
        res = rt.poll(now=clock.t)
        (r,) = [x for x in res if isinstance(x, Served)]
        return r

    def direct(st, req):
        return np.asarray(ev(st, [jnp.asarray(c) for c in req.cats],
                             jnp.asarray(req.batch)))

    pub.publish(state_a, train_step=0)
    r1q = _req(rng, de_sizes=[50 + i for i in range(8)], n=8)
    r1 = serve_one(r1q)
    assert r1.version == 1
    np.testing.assert_array_equal(np.asarray(r1.predictions),
                                  direct(state_a, r1q))

    # interleave: queue a request, THEN publish, THEN flush — the flush
    # must observe v2 whole (read-once discipline)
    r2q = _req(rng, de_sizes=[50 + i for i in range(8)], n=8)
    assert rt.submit(r2q, now=clock.t) is None
    pub.publish(state_b)
    clock.t += 0.01
    res = rt.poll(now=clock.t)
    (r2,) = [x for x in res if isinstance(x, Served)]
    assert r2.version == 2
    np.testing.assert_array_equal(np.asarray(r2.predictions),
                                  direct(state_b, r2q))
    # bitwise distinguishable generations: a torn read could not match
    assert not np.array_equal(np.asarray(r2.predictions),
                              direct(state_a, r2q))
    assert rt.stats()["steady_state_recompiles"] == 0


# ------------------------------------------------ chaos composition


def _online_setup(mesh=None, world=1):
    configs = [{"input_dim": 20, "output_dim": 4},
               {"input_dim": 32 + 8, "output_dim": 4,
                "streaming": {"capacity": 32, "buckets": 8}}]
    de = DistributedEmbedding(configs, world_size=world)
    scfg = StreamingConfig(admit_min_count=2, evict_margin=1, depth=2,
                           buckets=256)
    emb_opt = SparseAdagrad()
    tx = optax.sgd(0.05)
    state = init_hybrid_state(de, emb_opt,
                              {"w": jnp.ones((4, 1), jnp.float32)},
                              tx, jax.random.key(0), mesh=mesh)
    sstate = init_streaming(de, scfg, mesh=mesh)

    def loss_fn(dp, outs, batch):
        return sum(batch[:, i].mean() * jnp.mean(o)
                   for i, o in enumerate(outs)) * jnp.mean(dp["w"])

    step = make_hybrid_train_step(de, loss_fn, tx, emb_opt, mesh=mesh,
                                  with_metrics=True, nan_guard=True,
                                  dynamic=scfg)

    def make_batch(i):
        rng = np.random.default_rng(900 + i)
        cats = [jnp.asarray(rng.integers(0, 20, 8), jnp.int32),
                jnp.asarray(rng.integers(i, i + 6, 8) * 7 + 10_000_000,
                            jnp.int32)]
        return cats, jnp.asarray(rng.normal(size=(8, 2)), jnp.float32)

    return de, scfg, emb_opt, tx, state, sstate, step, make_batch


def test_combined_chaos_oovflood_and_burst_while_serving(monkeypatch):
    """The joint drill: at step 2 the training stream floods with
    never-seen ids (oovflood@) while at step 3 serve traffic spikes 8x
    (burst@). Wanted: streaming admissions happen, every refusal is
    typed, the ladder recovers after the burst, staleness stays within
    the SLO, and nothing retraces."""
    monkeypatch.setenv(runtime.FAULT_ENV, "oovflood@2,burst@3")
    de, scfg, emb_opt, tx, state, sstate, step, make_batch = \
        _online_setup()
    rt = ServingRuntime(de, _pred_fn, state,
                        config=ServeConfig(max_batch=16, max_wait_ms=0,
                                           deadline_ms=10_000,
                                           max_queue=16),
                        streaming=(scfg, sstate))
    rng = np.random.default_rng(7)

    STEPS = 8
    def data(start):
        for i in range(start, STEPS):
            yield make_batch(i)

    online = OnlineRuntime(
        rt, config=OnlineConfig(publish_every_steps=2,
                                freshness_max_steps=4))
    res = online.run(step, state, data, de=de,
                     warmup_template=_tmpl(numerical=2),
                     make_request=lambda i: _req(rng, (20, 40), n=2,
                                                 numerical=2),
                     requests_per_step=2, burst_x=8.0,
                     streaming_state=sstate, emb_optimizer=emb_opt,
                     dense_tx=tx, metrics_interval=0)
    assert res.train.step == STEPS and not res.train.preempted
    # oovflood absorbed into the admission machinery: admissions happened
    occ = smod.occupancy(de, res.train.streaming)
    assert int(occ["admitted"]) > 0
    served = [r for r in res.serve_results if isinstance(r, Served)]
    others = [r for r in res.serve_results if not isinstance(r, Served)]
    assert served, "no request was ever served"
    # typed sheds only: the burst overflow came back as Overloaded, not
    # exceptions or losses
    assert others and all(isinstance(r, Overloaded) for r in others)
    assert {r.reason for r in others} <= {"queue_full", "load_shed"}
    # post-burst recovery: the ladder walked back down
    assert rt.level == 0
    s = res.serve_stats
    assert s["steady_state_recompiles"] == 0
    assert s["freshness_p95_steps"] is not None
    assert s["freshness_p95_steps"] <= 4 * 1.011  # sketch rel-error slack
    # every served answer observed a whole published version
    assert all(r.version >= 1 for r in served)
    vs = [r.version for r in served]
    assert vs == sorted(vs)  # versions only ever move forward


def test_realtime_mode_wall_clock_freshness():
    """ISSUE 18 tentpole: ``realtime_qps`` hands the serve plane its own
    thread of control. Arrivals land on wall-clock time against the
    live publisher WHILE training runs, the pump only publishes, and
    ``freshness_p95_s`` measures true concurrent staleness. Request
    conservation: every driver submission comes back typed exactly
    once, and nothing retraces."""
    de, scfg, emb_opt, tx, state, sstate, step, make_batch = \
        _online_setup()
    rt = ServingRuntime(de, _pred_fn, state,
                        config=ServeConfig(max_batch=16, max_wait_ms=2,
                                           deadline_ms=10_000,
                                           max_queue=256),
                        streaming=(scfg, sstate))
    rng = np.random.default_rng(11)

    STEPS = 8
    def data(start):
        for i in range(start, STEPS):
            time.sleep(0.03)  # hold the stream open: wall-clock arrivals
            yield make_batch(i)

    online = OnlineRuntime(rt, config=OnlineConfig(publish_every_steps=2))
    res = online.run(step, state, data, de=de,
                     warmup_template=_tmpl(numerical=2),
                     make_request=lambda i: _req(rng, (20, 40), n=2,
                                                 numerical=2),
                     realtime_qps=150.0, realtime_drain_s=60.0,
                     streaming_state=sstate, emb_optimizer=emb_opt,
                     dense_tx=tx, metrics_interval=0)
    assert res.train.step == STEPS and not res.train.preempted
    served = [r for r in res.serve_results if isinstance(r, Served)]
    assert served, "driver produced no served responses"
    # conservation: runtime rids are contiguous and every submission
    # came back exactly once (no losses, no duplicates through the
    # concurrent submit/poll/install interleaving)
    rids = sorted(r.rid for r in res.serve_results)
    assert rids == list(range(len(rids)))
    assert all(r.version >= 1 for r in served)
    s = res.serve_stats
    assert s["steady_state_recompiles"] == 0
    # wall-clock freshness, measured by the open-loop driver's flushes
    assert s["freshness_p95_s"] is not None and s["freshness_p95_s"] > 0
    assert res.published_version >= 1


def test_realtime_mode_argument_validation():
    online = OnlineRuntime(object())  # serving untouched before validation
    with pytest.raises(ValueError, match="ONE load mode"):
        online.run(None, None, None, de=None,
                   make_request=lambda i: None, requests_per_step=2,
                   realtime_qps=10.0)
    with pytest.raises(ValueError, match="make_request"):
        online.run(None, None, None, de=None, realtime_qps=10.0)
    with pytest.raises(ValueError, match="positive"):
        online.run(None, None, None, de=None,
                   make_request=lambda i: None, realtime_qps=0.0)


def test_preempt_mid_serve_then_resume_consistent_pair(tmp_path,
                                                       monkeypatch):
    """Preemption mid-serve: the SIGTERM checkpointed training state and
    the sidecar's published version form a consistent pair (published
    step never ahead of the saved step), and auto-resume continues the
    version lineage monotonically from the restored state."""
    ckpt = str(tmp_path / "ck")
    STEPS = 10
    rng = np.random.default_rng(11)

    def run_once(faults):
        # a fresh process each time: new de/state/step templates, a new
        # serving runtime — only the checkpoint + sidecar carry over
        de, scfg, emb_opt, tx, state, sstate, step, make_batch = \
            _online_setup()

        def data(start):
            for i in range(start, STEPS):
                yield make_batch(i)

        rt = ServingRuntime(de, _pred_fn, state,
                            config=ServeConfig(max_batch=16,
                                               max_wait_ms=0,
                                               deadline_ms=10_000,
                                               max_queue=64),
                            streaming=(scfg, sstate))
        if faults:
            monkeypatch.setenv(runtime.FAULT_ENV, faults)
        else:
            monkeypatch.delenv(runtime.FAULT_ENV, raising=False)
        online = OnlineRuntime(
            rt, config=OnlineConfig(publish_every_steps=2,
                                    freshness_max_steps=4),
            checkpoint_dir=ckpt)
        return online.run(
            step, state, data, de=de,
            warmup_template=_tmpl(numerical=2),
            make_request=lambda i: _req(rng, (20, 40), n=2, numerical=2),
            requests_per_step=2, streaming_state=sstate,
            emb_optimizer=emb_opt, dense_tx=tx,
            checkpoint_every_steps=2, metrics_interval=0)

    r1 = run_once("preempt@4")
    assert r1.train.preempted
    side = json.load(open(online_sidecar_path(ckpt)))
    saved_step = json.load(
        open(os.path.join(ckpt, "meta.json")))["step"]
    # the consistent pair: the published view never leads the checkpoint
    assert side["version"] == r1.published_version >= 1
    assert side["train_step"] <= saved_step

    r2 = run_once(None)
    assert r2.train.step == STEPS and not r2.train.preempted
    # versions continue, never restart, across the preemption boundary
    assert r2.published_version > r1.published_version
    served2 = [r for r in r2.serve_results if isinstance(r, Served)]
    assert served2
    assert min(r.version for r in served2) > r1.published_version
    # the first resumed publication is the RESTORED state, not the init
    # template the process started from
    assert all(r.staleness_steps is not None for r in served2)
    assert json.load(open(online_sidecar_path(ckpt)))["train_step"] \
        == r2.train.step


# ------------------------------------------- freshness-breach post-mortem


def test_freshness_breach_dumps_blackbox(tmp_path):
    """The stale TRANSITION parks a CRC-intact black box naming the
    lagging version — the serving runtime's leg of the flight-recorder
    contract."""
    from distributed_embeddings_tpu.utils import mplane

    mplane.uninstall_flight_recorder()
    try:
        de, state, rt, clock = _build()
        rt.warmup(_tmpl())
        path = str(tmp_path / "serve.blackbox.json")
        rec = mplane.install_flight_recorder(path, capacity=8)
        assert rec is not None
        rt.set_freshness_slo(max_steps=2)
        rt.install_snapshot(state, version=1, train_step=0, now=0.0)
        rt.note_train_step(3, now=4.5)
        assert rt.freshness_stale
        payload = mplane.verify_blackbox(path)
        assert payload["trigger"] == "freshness_breach"
        assert payload["context"]["version"] == 1
        assert payload["context"]["lag_steps"] == 3
        # a stats() snapshot rode along (captured AT the breach, i.e.
        # the last pre-breach view), and the snapshot_lagging event
        # reached the ring through the obs tap
        assert payload["stats"][-1]["source"] == "serving"
        assert payload["stats"][-1]["stats"]["snapshot_version"] == 1
        assert any(e["event"] == "snapshot_lagging"
                   for e in payload["events"])
    finally:
        mplane.uninstall_flight_recorder()
