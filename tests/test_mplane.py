"""Production observability plane (ISSUE 17): the mergeable quantile
sketch, the metrics registry + Prometheus rendering, the scrape
endpoint, and the crash flight recorder.

The sketch tests are the acceptance teeth for the serving migration:
every reported quantile must sit within the sketch's GUARANTEED
relative-error bound of the exact numpy reference, and merges must be
associative and commutative (per-rank sketches fold into one fleet view
in any order). The flight-recorder tests pin the black-box contract —
bounded rings, atomic CRC-stamped dump, tamper detection."""

import json
import math
import os
import threading
import urllib.request

import numpy as np
import pytest

from distributed_embeddings_tpu.utils import mplane, obs
from distributed_embeddings_tpu.utils.mplane import (
    FlightRecorder, MetricsRegistry, QuantileSketch)


@pytest.fixture(autouse=True)
def _isolate_recorder():
    mplane.uninstall_flight_recorder()
    yield
    mplane.uninstall_flight_recorder()


# ------------------------------------------------------------ the sketch


def _ref_quantile(vals, q):
    # the sketch ranks with rank = q * (count - 1): numpy's "linear"
    # interpolation on the same definition, then compare midpoints
    return float(np.quantile(np.asarray(vals, np.float64), q))


@pytest.mark.parametrize("dist", ["lognormal", "uniform", "exponential"])
def test_sketch_quantiles_within_relative_error(dist):
    rng = np.random.default_rng(7)
    vals = {
        "lognormal": rng.lognormal(1.0, 1.2, 8000),
        "uniform": rng.uniform(0.5, 500.0, 8000),
        "exponential": rng.exponential(20.0, 8000),
    }[dist]
    sk = QuantileSketch()
    for v in vals:
        sk.observe(float(v))
    for q in (0.01, 0.1, 0.5, 0.9, 0.95, 0.99, 0.999):
        ref = _ref_quantile(vals, q)
        got = sk.quantile(q)
        # the DDSketch guarantee is vs the sample at the rank the sketch
        # reads; numpy interpolates between ranks, so allow one extra
        # accuracy step of slack on top of the guaranteed bound
        assert got == pytest.approx(ref, rel=3 * sk.relative_accuracy), q


def test_sketch_exact_rank_guarantee():
    # against the EXACT order statistic (no interpolation) the bound is
    # the advertised relative_accuracy itself
    rng = np.random.default_rng(11)
    vals = np.sort(rng.lognormal(0.0, 2.0, 5001))
    sk = QuantileSketch()
    for v in vals:
        sk.observe(float(v))
    for q in (0.5, 0.9, 0.99):
        exact = float(vals[int(q * (len(vals) - 1))])
        assert abs(sk.quantile(q) - exact) <= \
            sk.relative_accuracy * exact * (1 + 1e-9)


def test_sketch_empty_and_edge_quantiles():
    sk = QuantileSketch()
    assert sk.quantile(0.5) is None
    assert sk.mean is None
    sk.observe(42.0)
    assert sk.quantile(0.0) == pytest.approx(42.0, rel=0.011)
    assert sk.quantile(1.0) == pytest.approx(42.0, rel=0.011)
    assert sk.mean == 42.0
    with pytest.raises(ValueError):
        sk.quantile(1.5)


def test_sketch_zero_and_negative_values():
    sk = QuantileSketch()
    for v in (0.0, -1.0, 0.0, 5.0):
        sk.observe(v)
    assert sk.count == 4
    assert sk.zero_count == 3
    assert sk.quantile(0.25) == 0.0
    assert sk.quantile(1.0) == pytest.approx(5.0, rel=0.011)


def test_sketch_merge_commutative_and_associative():
    rng = np.random.default_rng(3)
    parts = [rng.lognormal(0.0, 1.0, 700) for _ in range(3)]

    def build(vals):
        s = QuantileSketch()
        for v in vals:
            s.observe(float(v))
        return s

    a_bc = build(parts[0]).merge(build(parts[1]).merge(build(parts[2])))
    ab_c = build(parts[0]).merge(build(parts[1])).merge(build(parts[2]))
    c_ba = build(parts[2]).merge(build(parts[1])).merge(build(parts[0]))
    direct = build(np.concatenate(parts))
    # bucket-count addition: any association/order gives IDENTICAL state
    for other in (ab_c, c_ba, direct):
        assert a_bc.buckets == other.buckets
        assert a_bc.count == other.count
        assert a_bc.sum == pytest.approx(other.sum)
        for q in (0.5, 0.95, 0.99):
            assert a_bc.quantile(q) == other.quantile(q)


def test_sketch_merge_rejects_accuracy_mismatch():
    with pytest.raises(ValueError, match="accuracy"):
        QuantileSketch(0.01).merge(QuantileSketch(0.02))


def test_sketch_dict_roundtrip_preserves_merge():
    rng = np.random.default_rng(5)
    sk = QuantileSketch()
    for v in rng.exponential(3.0, 1000):
        sk.observe(float(v))
    back = QuantileSketch.from_dict(
        json.loads(json.dumps(sk.to_dict())))
    assert back.buckets == sk.buckets
    assert back.quantile(0.99) == sk.quantile(0.99)
    # and the deserialized sketch still merges
    back.merge(sk)
    assert back.count == 2 * sk.count


def test_sketch_collapse_bounds_memory_keeps_high_quantiles():
    rng = np.random.default_rng(9)
    vals = rng.lognormal(0.0, 3.0, 20000)  # many decades -> many buckets
    full = QuantileSketch()
    for v in vals:
        full.observe(float(v))
    assert len(full.buckets) > 512  # the data really needs a collapse
    sk = QuantileSketch(max_buckets=512)
    for v in vals:
        sk.observe(float(v))
    assert len(sk.buckets) <= 512
    # the collapse folds LOW buckets together: every quantile above the
    # collapsed floor — here p95/p99, the ones SLOs read — keeps the
    # guarantee; quantiles below the floor are the sacrificed ones
    for q in (0.95, 0.99, 0.999):
        ref = _ref_quantile(vals, q)
        assert sk.quantile(q) == pytest.approx(ref, rel=0.03), q


# ---------------------------------------------------------- the registry


def test_registry_golden_prometheus_rendering():
    reg = MetricsRegistry()
    reg.counter("detpu_requests_total", "served requests").inc(
        3, outcome="ok")
    reg.counter("detpu_requests_total").inc(1, outcome="shed")
    reg.gauge("detpu_level", "degradation rung").set(2)
    sk = reg.sketch("detpu_latency_ms", "end-to-end latency")
    for v in [10.0] * 99 + [100.0]:
        sk.observe(v)
    text = reg.render()
    lines = text.strip().splitlines()
    assert "# HELP detpu_latency_ms end-to-end latency" in lines
    assert "# TYPE detpu_latency_ms summary" in lines
    assert "# TYPE detpu_level gauge" in lines
    assert "# TYPE detpu_requests_total counter" in lines
    assert 'detpu_requests_total{outcome="ok"} 3' in lines
    assert 'detpu_requests_total{outcome="shed"} 1' in lines
    assert "detpu_level 2" in lines
    assert "detpu_latency_ms_count 100" in lines
    assert "detpu_latency_ms_sum 1090" in lines
    q50 = [ln for ln in lines if ln.startswith(
        'detpu_latency_ms{quantile="0.5"}')]
    assert len(q50) == 1
    assert float(q50[0].split()[-1]) == pytest.approx(10.0, rel=0.011)
    assert text.endswith("\n")


def test_registry_kind_collision_raises():
    reg = MetricsRegistry()
    reg.counter("detpu_x")
    with pytest.raises(TypeError, match="counter"):
        reg.gauge("detpu_x")
    with pytest.raises(TypeError):
        reg.sketch("detpu_x")


def test_registry_collector_pull_model_and_broken_collector():
    reg = MetricsRegistry()
    state = {"n": 0}

    def sync():
        state["n"] += 1
        reg.gauge("detpu_pull").set(state["n"])

    def broken():
        raise RuntimeError("adapter bug")

    reg.register_collector(sync)
    reg.register_collector(broken)
    assert "detpu_pull 1" in reg.render()
    assert "detpu_pull 2" in reg.render()  # re-pulled per scrape


def test_registry_export_file_atomic(tmp_path):
    reg = MetricsRegistry()
    reg.counter("detpu_total").inc(7)
    path = str(tmp_path / "metrics.prom")
    assert reg.export_file(path) == path
    with open(path) as f:
        assert f.read() == reg.render()
    assert not os.path.exists(path + ".tmp")


def test_registry_to_dict_mergeable_across_processes():
    # simulate two ranks exporting + a chief merging their sketches
    ranks = []
    for seed in (0, 1):
        reg = MetricsRegistry()
        sk = reg.sketch("detpu_lat_ms")
        for v in np.random.default_rng(seed).exponential(5.0, 500):
            sk.child().observe(float(v))
        ranks.append(json.loads(json.dumps(reg.to_dict())))
    merged = QuantileSketch.from_dict(
        ranks[0]["detpu_lat_ms"]["series"][0]["value"])
    merged.merge(QuantileSketch.from_dict(
        ranks[1]["detpu_lat_ms"]["series"][0]["value"]))
    assert merged.count == 1000


def test_sync_counters_and_step_metrics_adapters():
    reg = MetricsRegistry()
    mplane.sync_counters(reg, {"served": 10, "shed": 2, "bogus": "x"})
    mplane.sync_step_metrics(reg, {"loss": 0.5, "grad_norm": 1.25,
                                   "skip": None})
    text = reg.render()
    assert 'detpu_events_total{event="served"} 10' in text
    assert 'detpu_events_total{event="shed"} 2' in text
    assert "bogus" not in text  # unconvertible values skipped
    assert "detpu_step_loss 0.5" in text
    assert "detpu_step_grad_norm 1.25" in text
    # the mirror is idempotent (set_total, not inc): re-sync != double
    mplane.sync_counters(reg, {"served": 11})
    assert 'detpu_events_total{event="served"} 11' in reg.render()


def test_concurrent_observe_while_scrape():
    """The race the process-isolated serving driver hits: runtime
    threads observe (mutating sketch buckets AND creating labelled
    children) while the exporter's daemon thread renders. Pre-lock this
    died with ``dictionary changed size during iteration``; post-lock
    every observation must also still be accounted for (none torn)."""
    reg = MetricsRegistry()
    fam = reg.sketch("detpu_race_ms", "observe-while-scrape drill")
    writers, per_writer = 4, 1500
    errors = []
    stop = threading.Event()

    def writer(tid):
        try:
            rng = np.random.default_rng(tid)
            for i in range(per_writer):
                # rotating label sets force child creation mid-scrape
                fam.observe(float(rng.exponential(5.0)),
                            stage=f"s{tid}", shard=str(i % 7))
        except Exception as e:  # noqa: BLE001 - the assertion surface
            errors.append(e)

    def scraper():
        try:
            while not stop.is_set():
                reg.render()
                reg.to_dict()
        except Exception as e:  # noqa: BLE001 - the assertion surface
            errors.append(e)

    scrape = threading.Thread(target=scraper)
    threads = [threading.Thread(target=writer, args=(t,))
               for t in range(writers)]
    scrape.start()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    stop.set()
    scrape.join()
    assert errors == []
    assert sum(sk.count for _, sk in fam.items()) == writers * per_writer


def test_concurrent_observe_while_quantile_under_collapse():
    """Sketch-level: a tiny ``max_buckets`` forces :meth:`_collapse`
    (bucket-dict pops) to interleave with ``quantile`` iteration — the
    tightest version of the torn-read window."""
    sk = QuantileSketch(max_buckets=8)
    errors = []
    done = threading.Event()

    def writer(seed):
        try:
            rng = np.random.default_rng(seed)
            for _ in range(4000):
                sk.observe(float(rng.lognormal(mean=2.0, sigma=3.0)))
        except Exception as e:  # noqa: BLE001 - the assertion surface
            errors.append(e)

    def reader():
        try:
            while not done.is_set():
                sk.quantile(0.99)
                sk.to_dict()
        except Exception as e:  # noqa: BLE001 - the assertion surface
            errors.append(e)

    r = threading.Thread(target=reader)
    ws = [threading.Thread(target=writer, args=(s,)) for s in range(2)]
    r.start()
    for w in ws:
        w.start()
    for w in ws:
        w.join()
    done.set()
    r.join()
    assert errors == []
    assert sk.count == 2 * 4000
    assert len(sk.buckets) <= 8
    assert sk.quantile(0.5) is not None


# ---------------------------------------------------- the scrape endpoint


def test_http_exporter_scrape_roundtrip():
    reg = MetricsRegistry()
    reg.counter("detpu_scrapeme_total").inc(5)
    exp = mplane.start_http_exporter(reg, port=0)
    assert exp is not None and exp.port > 0
    try:
        with urllib.request.urlopen(exp.url(), timeout=10) as resp:
            assert resp.status == 200
            assert resp.headers["Content-Type"].startswith("text/plain")
            body = resp.read().decode("utf-8")
        assert "detpu_scrapeme_total 5" in body
        # non-metrics paths 404 rather than leaking anything
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(
                f"http://127.0.0.1:{exp.port}/secrets", timeout=10)
    finally:
        exp.stop()


def test_http_exporter_off_by_default(monkeypatch):
    monkeypatch.delenv(mplane.METRICS_PORT_ENV, raising=False)
    assert mplane.start_http_exporter(MetricsRegistry()) is None
    monkeypatch.setenv(mplane.METRICS_PORT_ENV, "not-a-port")
    assert mplane.start_http_exporter(MetricsRegistry()) is None


def test_http_exporter_env_port(monkeypatch):
    monkeypatch.setenv(mplane.METRICS_PORT_ENV, "0")
    reg = MetricsRegistry()
    reg.gauge("detpu_env_g").set(1)
    exp = mplane.start_http_exporter(reg)
    assert exp is not None
    try:
        with urllib.request.urlopen(exp.url(), timeout=10) as resp:
            assert b"detpu_env_g 1" in resp.read()
    finally:
        exp.stop()


# --------------------------------------------------- the flight recorder


def test_flight_recorder_ring_is_bounded(tmp_path):
    rec = FlightRecorder(str(tmp_path / "bb.json"), capacity=8)
    for i in range(50):
        rec.note_step(i, {"loss": float(i)})
        rec.note_event("tick", i=i)
    snap = rec.snapshot()
    assert len(snap["steps"]) == 8
    assert len(snap["events"]) == 8
    assert snap["steps"][-1]["step"] == 49
    assert snap["steps"][0]["step"] == 42  # oldest evicted


def test_flight_recorder_dump_and_verify(tmp_path):
    path = str(tmp_path / "run.blackbox.json")
    rec = FlightRecorder(path, capacity=4)
    rec.note_step(10, {"loss": 0.1})
    rec.note_event("training_rollback", restored_step=8)
    rec.note_stats({"latency_p99_ms": 12.5})
    out = rec.dump("nan_escalation", last_good_step=10,
                   unhealthy_tables=["table3"])
    assert out == path
    payload = mplane.verify_blackbox(path)
    assert payload["trigger"] == "nan_escalation"
    assert payload["context"]["unhealthy_tables"] == ["table3"]
    assert payload["steps"][0]["metrics"]["loss"] == 0.1
    assert payload["events"][0]["event"] == "training_rollback"
    assert payload["stats"][0]["stats"]["latency_p99_ms"] == 12.5
    assert not os.path.exists(path + ".tmp")  # atomic: no tmp debris


def test_flight_recorder_tamper_detected(tmp_path):
    path = str(tmp_path / "bb.json")
    rec = FlightRecorder(path, capacity=4)
    rec.note_step(1, {"loss": 1.0})
    rec.dump("preemption")
    doc = json.load(open(path))
    doc["payload"]["trigger"] = "nothing_happened"
    with open(path, "w") as f:
        json.dump(doc, f)
    with pytest.raises(ValueError, match="CRC"):
        mplane.verify_blackbox(path)


def test_flight_recorder_dump_never_raises(tmp_path):
    rec = FlightRecorder(str(tmp_path / "no" / "such" / "dir" / "bb.json"))
    rec.note_step(1, {})
    assert rec.dump("unhandled_crash") is None  # OSError swallowed


def test_flight_recorder_jsonable_coerces_device_payloads(tmp_path):
    path = str(tmp_path / "bb.json")
    rec = FlightRecorder(path)
    rec.note_step(0, {"arr": np.arange(3), "scalar": np.float32(1.5),
                      "weird": object()})
    rec.dump("unhandled_crash", err=ValueError("boom"))
    payload = mplane.verify_blackbox(path)
    m = payload["steps"][0]["metrics"]
    assert m["arr"] == [0, 1, 2]
    assert m["scalar"] == 1.5
    assert isinstance(m["weird"], str)
    assert "boom" in payload["context"]["err"]


def test_install_flight_recorder_idempotent_and_event_tap(tmp_path):
    path = str(tmp_path / "bb.json")
    rec = mplane.install_flight_recorder(path, capacity=16)
    assert rec is not None
    assert mplane.install_flight_recorder(path) is rec  # same path: kept
    # record_event flows into the ring through the tap
    obs.record_event("snapshot_published", version=3)
    events = rec.snapshot()["events"]
    assert any(e["event"] == "snapshot_published" and e["version"] == 3
               for e in events)
    # a new path REPLACES the recorder
    other = mplane.install_flight_recorder(str(tmp_path / "bb2.json"))
    assert other is not rec
    assert mplane.flight_recorder() is other


def test_install_flight_recorder_disabled_by_env(tmp_path, monkeypatch):
    monkeypatch.setenv(mplane.BLACKBOX_ENV, "0")
    assert mplane.install_flight_recorder(str(tmp_path / "bb.json")) is None
    assert mplane.flight_recorder() is None


def test_blackbox_ring_env_controls_capacity(tmp_path, monkeypatch):
    monkeypatch.setenv(mplane.BLACKBOX_RING_ENV, "3")
    rec = FlightRecorder(str(tmp_path / "bb.json"))
    assert rec.capacity == 3
    for i in range(9):
        rec.note_event("e", i=i)
    assert len(rec.snapshot()["events"]) == 3
