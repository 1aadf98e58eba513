"""Process-isolated serving (ISSUE 18): snapshot payload fidelity, the
supervision config, outage semantics, and one real spawned worker.

The crash/hang/restart chaos drill runs out-of-band in
``tools/check_isolation.py`` (= ``make check-isolation``); here we pin
the pieces that make it deterministic: the shm payload reconstructs the
in-process ``install_snapshot`` state BITWISE, an unstarted/downed
supervisor answers typed ``Unavailable`` instead of hanging callers,
and a real spawn-context worker serves a stream end to end with
request conservation."""

import os

import numpy as np
import jax
import pytest

from distributed_embeddings_tpu.parallel import serving as sv
from distributed_embeddings_tpu.parallel import supervisor as sup
from distributed_embeddings_tpu.utils import mplane

from tools import isolation_common as ic


# ------------------------------------------------ payload <-> state pin


def test_snapshot_payload_reconstructs_state_bitwise():
    built = ic.build(world=1)
    state, stream = built["state"], built["streaming"][1]
    payload = sup.snapshot_payload(state, stream)
    state2, stream2, step = sup.install_payload(payload, state, stream)
    assert step == int(np.asarray(state.step))
    ref = jax.tree.leaves((state.emb_params, state.dense_params))
    got = jax.tree.leaves((state2.emb_params, state2.dense_params))
    assert len(ref) == len(got)
    for a, b in zip(ref, got):
        a, b = np.asarray(a), np.asarray(b)
        assert a.dtype == b.dtype and np.array_equal(a, b)
    for a, b in zip(jax.tree.leaves(stream), jax.tree.leaves(stream2)):
        a, b = np.asarray(a), np.asarray(b)
        assert a.dtype == b.dtype and np.array_equal(a, b)


def test_payload_install_matches_in_process_install_snapshot():
    """The cross-boundary pin: a runtime fed the RECONSTRUCTED state
    must answer bitwise-identically to one fed the original via plain
    ``install_snapshot`` — and installing the reconstruction must not
    retrace the compiled ladder (device_put onto template shardings)."""
    built = ic.build(world=1)
    state, (scfg, sstate) = built["state"], built["streaming"]

    def mk_rt():
        rt = sv.ServingRuntime(built["de"], built["pred_fn"], state,
                               config=built["config"],
                               streaming=(scfg, sstate))
        rt.warmup(built["template"])
        return rt

    rt_direct, rt_shm = mk_rt(), mk_rt()
    stream_copy = jax.tree.map(lambda x: np.asarray(x), sstate)
    rt_direct.install_snapshot(state, stream_copy, version=1, train_step=0)
    payload = sup.snapshot_payload(state, sstate)
    state2, stream2, _ = sup.install_payload(payload, state, sstate)
    rt_shm.install_snapshot(state2, stream2, version=1, train_step=0)

    make_request = ic.make_request_fn(seed=5)
    for i in range(4):
        for rt in (rt_direct, rt_shm):
            assert rt.submit(make_request(i)) is None
    a = {r.rid: r for r in rt_direct.flush()}
    b = {r.rid: r for r in rt_shm.flush()}
    assert set(a) == set(b) and a
    for rid in a:
        assert isinstance(a[rid], sv.Served)
        assert np.array_equal(np.asarray(a[rid].predictions),
                              np.asarray(b[rid].predictions))
    assert rt_shm.steady_recompiles() == 0


def test_install_payload_rejects_mismatched_template():
    built = ic.build(world=1)
    state, stream = built["state"], built["streaming"][1]
    payload = sup.snapshot_payload(state, stream)
    with pytest.raises(ValueError, match="streaming"):
        sup.install_payload(payload, state, None)


# --------------------------------------------------------------- config


def test_supervise_config_env_defaults(monkeypatch):
    cfg = sup.SuperviseConfig()
    assert cfg.heartbeat_s == 0.25 and cfg.deadline_s == 5.0
    assert cfg.max_restarts == 3
    monkeypatch.setenv(sup.MAX_RESTARTS_ENV, "7")
    monkeypatch.setenv(sup.HEARTBEAT_ENV, "0.5")
    cfg = sup.SuperviseConfig()
    assert cfg.max_restarts == 7 and cfg.heartbeat_s == 0.5


def test_supervise_config_rejects_unbeatable_deadline():
    with pytest.raises(ValueError, match="deadline"):
        sup.SuperviseConfig(heartbeat_s=2.0, deadline_s=1.0)


# ------------------------------------------------------ outage semantics


def test_unstarted_supervisor_answers_typed_unavailable():
    s = sup.Supervisor("tools.isolation_common:worker_factory",
                       {"world": 1})
    try:
        make_request = ic.make_request_fn()
        rej = s.submit(make_request(0))
        assert isinstance(rej, sv.Unavailable)
        assert rej.status == "unavailable"
        assert rej.reason == "never_started" and rej.rid == 0
        rej2 = s.submit(make_request(1))
        assert rej2.rid == 1            # rids stay monotone while down
        assert s.queued_samples == 0    # nothing hung, nothing lost
        st = s.stats(sync=False)
        assert st["supervisor"]["worker_alive"] is False
        assert st["supervisor"]["unavailable"] == 2
    finally:
        s.close()


# ----------------------------------------------------- one real worker


def test_supervised_worker_end_to_end(tmp_path):
    """Spawn a real world-1 worker, publish a snapshot through shared
    memory, drive a request stream via the wall-clock driver, and pin
    request conservation + the supervisor stats block. (Crash/restart
    chaos is ``make check-isolation``'s job.)"""
    s = sup.Supervisor(
        "tools.isolation_common:worker_factory", {"world": 1},
        config=sup.SuperviseConfig(
            blackbox_path=str(tmp_path / "sup.blackbox.json"),
            env={"JAX_PLATFORMS": "cpu", "DETPU_FAULT": "",
                 "DETPU_METRICS_PORT": ""}))
    try:
        s.start()
        assert s._warm and s.stats(sync=False)["supervisor"]["worker_alive"]
        built = ic.build(world=1)
        s.install_snapshot(built["state"], built["streaming"][1],
                           version=1, train_step=0)
        s.note_train_step(1)
        drv = sv.RealtimeDriver(s, ic.make_request_fn(seed=2), qps=60,
                                duration_s=0.5, burst_positions=(),
                                drain_s=60.0)
        drv.start()
        drv.join(timeout=120)
        results = drv.results()
        assert drv.submitted > 0
        assert sorted(r.rid for r in results) == list(range(drv.submitted))
        served = [r for r in results if isinstance(r, sv.Served)]
        assert served, [type(r).__name__ for r in results]
        assert all(r.version == 1 for r in served)
        st = s.stats()
        assert st["served"] >= len(served) - 1
        assert st["steady_state_recompiles"] == 0
        block = st["supervisor"]
        assert block["restarts"] == 0 and block["worker_alive"]
        assert block["shm_region_bytes"] > 0
        assert block["shm_publish_p95_ms"] is not None
        # monotone versioning enforced supervisor-side too
        with pytest.raises(ValueError, match="monotonic"):
            s.install_snapshot(built["state"], built["streaming"][1],
                               version=1, train_step=2)
    finally:
        s.close()
    assert not os.path.exists(str(tmp_path / "sup.blackbox.json"))


def test_worker_without_a_free_device_says_so(tmp_path):
    """A worker whose backend will not come up (here: a TPU asked for on a
    machine without one; on the chip: the supervisor's process holds it)
    fails the start at once with the reason, not after the start timeout."""
    s = sup.Supervisor(
        "tools.isolation_common:worker_factory", {"world": 1},
        config=sup.SuperviseConfig(
            blackbox_path=str(tmp_path / "sup.blackbox.json"),
            env={"JAX_PLATFORMS": "tpu", "DETPU_FAULT": "",
                 "DETPU_METRICS_PORT": ""}))
    try:
        with pytest.raises(RuntimeError, match="found no free device"):
            s.start()
    finally:
        s.close()
