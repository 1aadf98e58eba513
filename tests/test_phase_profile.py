"""Measured phase-time observatory tests.

Three layers, mirroring the module split:

* handwritten-trace parser tests (``utils/traceparse.py``): gzipped
  Chrome-trace fixtures with fused events, events missing ``op_name``
  metadata, and multi-device streams — so the parser is pinned
  independently of the live profiler format;
* the live capture path: the until-now-untested ``DETPU_PROFILE_DIR``
  round trip (``obs.profile_trace`` -> trace directory -> parser
  recovers the ``detpu/`` phase names), :func:`profile_steps` on a tiny
  jitted step, and the opt-in guarantee (a profiled step's outputs are
  bitwise the unprofiled step's);
* calibration/agreement units — no jax.
"""

import gzip
import json
import os
import types

import pytest

from distributed_embeddings_tpu.utils import obs, traceparse
from distributed_embeddings_tpu.analysis import phase_profile as pp

DATA = os.path.join(os.path.dirname(__file__), "data")
MINI = os.path.join(DATA, "mini.trace.json.gz")


def _ev(name, ts, dur, pid=1, tid=1, **args):
    e = {"ph": "X", "pid": pid, "tid": tid, "name": name,
         "ts": float(ts), "dur": float(dur)}
    if args:
        e["args"] = args
    return e


def _doc(*events):
    return {"traceEvents": list(events)}


# ------------------------------------------------------------ parser units


def test_mini_fixture_roundtrip():
    """The checked-in miniature trace parses to hand-computable numbers
    (this is the fixture the no-jax obs_report selftest also pins)."""
    events = traceparse.parse_events(traceparse.load_trace(MINI))
    assert len(events) == 8          # the $python host frame is dropped
    m = traceparse.measure_events(events)
    assert {"embedding_forward/id_all_to_all",
            "embedding_forward/lookup_w8_d/packed_gather",
            "sparse_apply/sparse_apply_w8"} <= set(m["phase_ms"])
    assert m["a2a_union_ms"] == pytest.approx(0.11)
    assert m["measured_serialized_fraction"] == pytest.approx(
        85.0 / 110.0, abs=1e-4)
    # dot.4 carries op metadata (no detpu scope) -> resolved-unscoped;
    # copy.3 carries nothing -> unresolved
    assert m["events_resolved"] == 7
    unresolved = [e for e in events if not e.resolved]
    assert [e.name for e in unresolved] == ["copy.3"]


def test_fused_event_and_missing_opname():
    doc = _doc(
        _ev("fusion.7", 0, 50,
            long_name="jit(f)/detpu/sparse_apply/detpu/dedup/sort"),
        _ev("custom-call.2", 50, 10),                  # no metadata
        _ev("reduce.1", 60, 10, op_name="jit(f)/reduce_sum"),
        _ev("$file.py:1 frame", 0, 100),               # host: dropped
        _ev("ThreadpoolListener::Record", 0, 1),       # host: dropped
    )
    events = traceparse.parse_events(doc)
    assert [e.name for e in events] == ["fusion.7", "custom-call.2",
                                        "reduce.1"]
    assert events[0].phase == "sparse_apply/dedup"
    assert events[0].resolved
    assert not events[1].resolved and events[1].phase == ""
    assert events[2].resolved and events[2].phase == ""


def test_host_spans_are_not_device_ops():
    """An obs.span lands in the same capture NAMED detpu/...: the scope
    regex would read it as an op under that phase, so the parser drops
    it (like req/ events), and with it phase_profile's measurement."""
    doc = _doc(
        _ev("detpu/serve/flush", 0, 900, pid=701, tid=9, flush="3"),
        _ev("detpu/serve/h2d", 10, 300, pid=701, tid=9, bytes="1024"),
        _ev("fusion.1", 400, 50, pid=3, tid=3,
            tf_op="jit(local_eval)/detpu/dense_forward/dot_general:"),
    )
    events = traceparse.parse_events(doc)
    assert [e.name for e in events] == ["fusion.1"]
    m = traceparse.measure_events(events)
    assert set(m["phase_ms"]) == {"dense_forward"}
    assert m["wall_ms"] == pytest.approx(0.05)


def test_bare_name_resolver_join():
    """CPU-style events (bare instruction names) join through a
    resolver, including the ``.clone`` fallback and the ``hlo_op``
    arg."""
    table = {"dot.4": "embedding_forward/lookup_w8_d",
             "my_fusion": "sparse_apply/sparse_apply_w8"}
    doc = _doc(
        _ev("dot.4", 0, 10),
        _ev("my_fusion.clone", 10, 10),
        _ev("call", 20, 10, hlo_op="dot.4"),
        _ev("nonesuch.9", 30, 10),
    )

    def resolver(name):
        if name.endswith(".clone"):
            name = name[:-6]
        return table.get(name)

    events = traceparse.parse_events(doc, resolver=resolver)
    assert [e.phase for e in events] == [
        "embedding_forward/lookup_w8_d", "sparse_apply/sparse_apply_w8",
        "embedding_forward/lookup_w8_d", ""]
    assert [e.resolved for e in events] == [True, True, True, False]


def test_multi_device_streams_union_and_concurrency():
    """Two device lanes running the same phases concurrently: summed
    durations double, the wall union does not."""
    op = "jit(s)/detpu/sparse_apply/scatter"
    doc = _doc(_ev("scatter.1", 0, 100, pid=1, op_name=op),
               _ev("scatter.1", 20, 100, pid=2, op_name=op))
    m = traceparse.measure_events(traceparse.parse_events(doc))
    assert m["phase_ms"]["sparse_apply"] == pytest.approx(0.2)
    assert m["wall_ms"] == pytest.approx(0.12)
    assert m["concurrency"] == pytest.approx(0.2 / 0.12, abs=1e-3)


def test_trace_files_layouts(tmp_path):
    """Both capture layouts parse: the plugins/profile nesting with a
    gz file, and a bare .trace.json handed directly."""
    doc = _doc(_ev("add.1", 0, 10, op_name="jit(f)/detpu/nanguard/add"))
    nested = tmp_path / "cap" / "plugins" / "profile" / "r1"
    nested.mkdir(parents=True)
    with gzip.open(nested / "host.trace.json.gz", "wb") as f:
        f.write(json.dumps(doc).encode())
    plain = tmp_path / "solo.trace.json"
    plain.write_text(json.dumps(doc))

    ev_dir = traceparse.parse_capture(str(tmp_path / "cap"))
    ev_file = traceparse.parse_capture(str(plain))
    assert len(ev_dir) == len(ev_file) == 1
    assert ev_dir[0].phase == "nanguard"


def test_interval_math():
    merge = traceparse.merge_intervals
    assert merge([(0, 10), (10, 20), (30, 40)]) == [(0, 20), (30, 40)]
    assert merge([(5, 15), (0, 30)]) == [(0, 30)]
    assert traceparse.intersect_total([(0, 10), (20, 30)],
                                      [(5, 25)]) == pytest.approx(10)
    assert traceparse.intersect_total([], [(0, 1)]) == 0.0


def test_group_of():
    g = traceparse.group_of
    assert g("embedding_forward/id_all_to_all") == "exchange"
    assert g("sparse_apply/grad_all_to_all") == "exchange"
    assert g("embedding_forward/lookup_w4_d/packed_gather") == "lookup"
    assert g("sparse_apply/sparse_apply_w4") == "apply"
    assert g("sparse_apply/sparse_apply_w4/dedup") == "apply"
    assert g("dense_forward_backward") == "dense"
    assert g("dense_update") == "dense"
    assert g("streaming_commit") == "streaming"
    assert g("") == "other"
    assert g("nanguard") == "other"


def test_independent_spans_decide_classification():
    """The DAG-aware hook: with no independent spans a fully-shadowed
    exchange still classifies serialized; with generous independent
    spans it classifies overlapped."""
    a2a = "embedding_forward/id_all_to_all"
    doc = _doc(
        _ev("all-to-all.1", 0, 100,
            op_name=f"jit(s)/detpu/embedding_forward/detpu/"
                    f"id_all_to_all/a2a"),
        # concurrent compute that is DAG-DEPENDENT (another device's
        # gather feeding its own exchange): must not count as hiding
        _ev("gather.1", 0, 100,
            op_name="jit(s)/detpu/embedding_forward/detpu/"
                    "lookup_w4_d/gather"),
    )
    events = traceparse.parse_events(doc)
    m_dep = traceparse.measure_events(events,
                                      independent_spans={a2a: []})
    assert m_dep["collectives"][0]["classification"] == "serialized"
    assert m_dep["measured_serialized_fraction"] == pytest.approx(1.0)
    m_ind = traceparse.measure_events(
        events, independent_spans={a2a: [(0.0, 100.0)]})
    assert m_ind["collectives"][0]["classification"] == "overlapped"
    assert m_ind["measured_serialized_fraction"] == pytest.approx(0.0)
    # the naive fallback (no spans dict) over-credits: documented upper
    # bound — the gather's concurrency counts
    m_naive = traceparse.measure_events(events)
    assert m_naive["collectives"][0]["classification"] == "overlapped"


# -------------------------------------------------- live capture round trip


@pytest.fixture
def cpu_jit_fn():
    import jax
    import jax.numpy as jnp

    def f(x, y):
        with obs.scope("embedding_forward"):
            with obs.scope("id_all_to_all"):
                a = x @ y
        with obs.scope("sparse_apply"):
            b = jnp.sin(a) + jnp.cos(a)
        return b.sum()

    jf = jax.jit(f)
    x = jnp.ones((128, 128))
    jf(x, x).block_until_ready()
    return jf, x


def test_detpu_profile_dir_roundtrip(cpu_jit_fn, tmp_path, monkeypatch):
    """Satellite 1: the until-now-untested ``DETPU_PROFILE_DIR`` capture
    path in utils/obs.py — capture a tiny jitted step on CPU through
    ``obs.profile_trace``, assert the trace directory exists, and the
    parser recovers the known ``detpu/`` phase names."""
    jf, x = cpu_jit_fn
    cap = tmp_path / "cap"
    monkeypatch.setenv("DETPU_PROFILE_DIR", str(cap))
    with obs.profile_trace("roundtrip"):
        jf(x, x).block_until_ready()
    root = cap / "roundtrip"
    assert root.is_dir()
    files = traceparse.trace_files(str(root))
    assert files, "profile_trace produced no .trace.json[.gz] capture"
    events = traceparse.parse_capture(str(root))
    phases = {e.phase for e in events if e.phase}
    # CPU events carry bare instruction names; join them against the
    # compiled module's own op_name text
    if not phases:
        txt = jf.lower(x, x).compile().as_text()
        index = pp.HloPhaseIndex(txt)
        events = traceparse.parse_capture(str(root),
                                          resolver=index.resolve)
        phases = {e.phase for e in events if e.phase}
    assert any(p.startswith("embedding_forward") for p in phases), phases
    assert any(p.startswith("sparse_apply") for p in phases), phases


def test_profile_steps_and_bitwise_opt_in(cpu_jit_fn, tmp_path):
    """:func:`profile_steps` reduces live captures to a PhaseProfile
    with per-step spread — and profiling is strictly opt-in: the
    profiled step's outputs are bitwise the unprofiled step's."""
    import jax
    import numpy as np

    jf, x = cpu_jit_fn
    txt = jf.lower(x, x).compile().as_text()
    index = pp.HloPhaseIndex(txt)
    out = {}

    def run_one():
        out["y"] = jf(x, x)
        float(out["y"])

    prof = pp.profile_steps(run_one, steps=2, index=index,
                            profile_dir=str(tmp_path / "keep"),
                            label="tiny")
    assert prof.steps == 2
    assert prof.step_wall_ms["p50"] > 0
    assert prof.capture_s is not None and prof.parse_s is not None
    assert any(p.startswith("embedding_forward")
               for p in prof.phase_ms), prof.phase_ms
    # explicit profile_dir keeps the captures (the
    # DETPU_PHASE_PROFILE_DIR contract)
    assert traceparse.trace_files(str(tmp_path / "keep"))
    # opt-in: same inputs with the profiler off -> bitwise-equal output
    y_prof = np.asarray(out["y"])
    y_plain = np.asarray(jf(x, x))
    assert y_plain.tobytes() == y_prof.tobytes()
    json.dumps(prof.to_json())     # must round-trip
    assert "tiny" in prof.markdown()


# --------------------------------------------- calibration and agreement


def _fake_sched(phase_cost_ns, collectives):
    return types.SimpleNamespace(
        phase_cost_ns=phase_cost_ns,
        collectives=[types.SimpleNamespace(phase=p, classification=c)
                     for p, c in collectives])


def _profile_with(phase_ms, collectives=()):
    measures = [{
        "events": 10, "events_resolved": 10,
        "wall_ms": sum(phase_ms.values()), "busy_ms": 0.0,
        "concurrency": 1.0, "phase_ms": dict(phase_ms),
        "group_ms": {g: 0.0 for g in traceparse.GROUPS},
        "a2a_union_ms": 0.0, "a2a_frac": 0.0,
        "collectives": [
            {"phase": p, "union_ms": 1.0, "hidden_ms": 0.0,
             "hidden_frac": h,
             "classification": ("overlapped" if h >= 0.5
                                else "serialized")}
            for p, h in collectives],
        "measured_serialized_fraction": None,
        "overlap_min_frac": 0.5,
    }]
    return pp.PhaseProfile.from_steps(measures, label="t", world=1,
                                      backend="cpu")


def test_calibrate_flags_relative_drift():
    """A uniform backend-speed factor cancels; only RELATIVE mispricing
    flags."""
    prof = _profile_with({"a": 100.0, "b": 10.0, "c": 1.0})
    # modeled costs exactly 1000x cheaper across the board -> no drift
    sched = _fake_sched({"a": 100.0 * 1e3, "b": 10.0 * 1e3,
                         "c": 1.0 * 1e3}, [])
    rep = pp.calibrate(prof, sched, drift_max=2.0)
    assert rep.ok and rep.scale == pytest.approx(1000.0)
    # phase b now modeled 10x too cheap relative to the others
    sched = _fake_sched({"a": 100.0 * 1e3, "b": 1.0 * 1e3,
                         "c": 1.0 * 1e3}, [])
    rep = pp.calibrate(prof, sched, drift_max=2.0)
    assert not rep.ok
    assert any("'b'" in f for f in rep.flagged)
    assert not any("'a'" in f for f in rep.flagged)
    json.dumps(rep.to_json())
    assert "DRIFT" in rep.markdown()


def test_calibrate_ignores_trace_noise_phases():
    """Phases below the share floor never flag (ratio noise on a 0.1%
    phase is not mispricing)."""
    prof = _profile_with({"big": 1000.0, "tiny": 0.1})
    sched = _fake_sched({"big": 1000.0 * 1e3, "tiny": 0.0001 * 1e3}, [])
    rep = pp.calibrate(prof, sched, drift_max=2.0)
    assert rep.ok
    tiny = next(r for r in rep.rows if r.phase == "tiny")
    assert tiny.normalized is not None and not tiny.flagged


def test_check_agreement_semantics():
    ida = "embedding_forward/id_all_to_all"
    outa = "embedding_forward/out_all_to_all"
    # modeled serialized + measured serialized -> agreement
    prof = _profile_with({}, collectives=[(ida, 0.1)])
    sched = _fake_sched({}, [(ida, "serialized")])
    assert pp.check_agreement(prof, sched) == []
    # modeled serialized + measured overlapped -> violation
    prof = _profile_with({}, collectives=[(ida, 0.9)])
    assert any("modeled SERIALIZED" in v
               for v in pp.check_agreement(prof, sched))
    # modeled overlappable may measure either way
    sched = _fake_sched({}, [(ida, "overlappable")])
    prof = _profile_with({}, collectives=[(ida, 0.1)])
    assert pp.check_agreement(prof, sched) == []
    # modeled exchange never measured -> violation; psum collectives
    # (non-exchange phases) are ignored entirely
    sched = _fake_sched({}, [(ida, "serialized"), (outa, "serialized"),
                             ("nanguard", "serialized"),
                             ("", "serialized")])
    prof = _profile_with({}, collectives=[(ida, 0.1)])
    vs = pp.check_agreement(prof, sched)
    assert any(outa in v for v in vs)
    assert not any("nanguard" in v for v in vs)
    # measured exchange the model never saw -> violation
    sched = _fake_sched({}, [(ida, "serialized")])
    prof = _profile_with({}, collectives=[(ida, 0.1), (outa, 0.1)])
    assert any("not a collective of the modeled" in v
               for v in pp.check_agreement(prof, sched))
