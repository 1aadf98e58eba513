"""Each reader on a small trace recorded on the chip (three steps of
kaggle_train_onehot, one v5e chip, kept under benchmarks/testdata)."""

import os

import pytest

from benchmarks.lib import manifest, peaks, tracered

TRACE = os.path.join(manifest.BENCH, "testdata",
                     "train_onehot_3steps.trace.json.gz")
CONFIG = manifest.load_json(os.path.join(manifest.BENCH, "configs",
                                         "dlrm-kaggle.json"))
FAMILY = manifest.load_family(CONFIG["family"])


@pytest.fixture(scope="module")
def ctx():
    trace = tracered.load(TRACE)
    window = max(o.start + o.dur for o in trace.ops) - min(
        o.start for o in trace.ops)
    return {"trace": trace, "steps": 3, "samples": 3 * 65536,
            "window_s": window, "chips": 1, "config": CONFIG, "family": FAMILY,
            "peaks": peaks.of("TPU v5 lite"),
            "counters": {"compiles_in_window": 0},
            "work": {"ids_per_step": 26 * 65536.0,
                     "distinct_rows_per_step": 400_000.0,
                     "outputs_per_step": 26 * 65536.0}}


def test_the_trace_loads(ctx):
    t = ctx["trace"]
    assert t.devices == 1 and len(t.modules) == 3 and len(t.ops) == 1398
    assert {n for n, _, _ in t.spans} == {"dispatch", "block"}
    assert tracered.scope_of("jit(step)/detpu/sparse_apply/detpu/"
                             "sparse_apply_w128/scatter") \
        == "sparse_apply/sparse_apply_w128"


# what the trace holds, read by hand from the same file (ms a step)
READINGS = {
    "lookup_ms": 19.843, "dense_ms": 22.481, "apply_ms": 64.540,
    "guard_update_ms": 0.5852, "unscoped_ms": 3.6704,
    "device_step_ms": 111.143, "compiles_in_window": 0.0,
}


@pytest.mark.parametrize("metric", sorted(READINGS))
def test_reader_reads_the_recorded_trace(ctx, metric):
    got = manifest.read_metric(metric, ctx)
    assert got == pytest.approx(READINGS[metric], rel=2e-4, abs=1e-9)


def test_shares_of_a_peak_stay_under_it(ctx):
    for metric in ("lookup_roofline", "dense_roofline", "apply_roofline",
                   "train_step_mfu"):
        got = manifest.read_metric(metric, ctx)
        assert 0.0 < got < 100.0, (metric, got)
    # 3 x 4.9 MFLOP a sample at 65536 samples in 22.48 ms, over 197 TFLOP/s
    flops = FAMILY.work.dense_train_flops_per_sample(CONFIG) * 65536
    assert manifest.read_metric("dense_roofline", ctx) == pytest.approx(
        100 * flops / 197e12 / 22.481e-3, rel=1e-3)
    idle = manifest.read_metric("device_idle_share.train", ctx)
    assert 0.0 <= idle < 1.0
    assert manifest.read_metric("host_gap_ms", ctx) == pytest.approx(
        ctx["window_s"] * 1e3 / 3 * idle / 100, rel=1e-6)


def test_a_reader_with_nothing_to_read_reports_nothing(ctx):
    # one chip has no exchange: the metric is left out, never 0
    assert manifest.read_metric("exchange_ms", ctx) is None
    assert manifest.read_metric("exchange_exposed_ms", ctx) is None
    empty = dict(ctx, trace=tracered.Trace([], [], [], 0))
    for metric in ("lookup_ms", "lookup_roofline", "train_step_mfu",
                   "device_idle_share.train", "device_step_ms",
                   "serve_lookup_ms"):
        assert manifest.read_metric(metric, empty) is None
    assert manifest.read_metric("serve_queue_wait_ms_p99", ctx) is None


def test_serve_readers_read_spans_and_counters():
    ctx = {"spans": {"queue_wait_ms": list(range(101)),
                     "coalesce_ms": [1.0, 3.0, 5.0],
                     "gen_late_ms": [0.0] * 99 + [7.0]},
           "counters": {"serve_recompiles": 0},
           "ratios": {"serve_pad_fraction": 0.25}}
    assert manifest.read_metric("serve_queue_wait_ms_p99", ctx) == 99.0
    assert manifest.read_metric("serve_coalesce_ms_p50", ctx) == 3.0
    assert manifest.read_metric("serve_pad_fraction", ctx) == 0.25
    assert manifest.read_metric("serve_recompiles", ctx) == 0.0
    assert 0.0 < manifest.read_metric("gen_late_ms_p99", ctx) <= 7.0


def test_exposed_time_is_what_nothing_else_overlaps():
    Op = tracered.Op
    t = tracered.Trace(ops=[Op(0, "id_all_to_all", "a2a", 0.0, 4.0),
                            Op(0, "dense_forward_backward", "mm", 1.0, 2.0),
                            Op(0, "grad_all_to_all", "a2a", 10.0, 1.0)],
                       modules=[], spans=[], devices=1)
    assert tracered.exposed_seconds(t, ["id_all_to_all", "grad_all_to_all"]) \
        == pytest.approx(3.0)
    assert tracered.busy_seconds(t) == pytest.approx(5.0)
    gaps = dict(tracered.breakdown(t)["idle_gaps"])
    assert gaps["_no_harness_span_"] == pytest.approx(6.0)


def _idle_gaps_by_every_span(trace):
    """``tracered.breakdown``'s idle gaps as PR 25 wrote them: every harness
    span tried on every gap. Quadratic (897 s on a serving trace with one
    span an empty poll, PERF.md); kept here as what the one moving index has
    to reproduce."""
    dev0 = tracered.union((o.start, o.start + o.dur) for o in trace.ops
                          if o.device == 0)
    gaps = [(a[1], b[0]) for a, b in zip(dev0, dev0[1:]) if b[0] > a[1]]
    spans = sorted((s, s + d, n) for n, s, d in trace.spans)
    by_span = {}
    for gs, ge in gaps:
        covered = 0.0
        for ss, se, name in spans:
            if se <= gs or ss >= ge:
                continue
            part = min(ge, se) - max(gs, ss)
            by_span[name] = by_span.get(name, 0.0) + part
            covered += part
        rest = (ge - gs) - covered
        if rest > 0:
            by_span["_no_harness_span_"] = \
                by_span.get("_no_harness_span_", 0.0) + rest
    return sorted(by_span.items(), key=lambda kv: -kv[1])[:10]


@pytest.mark.parametrize("name", ["train_onehot_3steps",
                                  "serve_ranking_flushes", "nested"])
def test_breakdown_walks_the_spans_once_and_reads_the_same(name):
    if name == "nested":
        # spans that overlap and nest, which a single thread's never do: a
        # short span behind a long one must not stop the walk
        Op = tracered.Op
        trace = tracered.Trace(
            ops=[Op(0, "", "op", float(t), 0.25) for t in range(12)],
            modules=[], devices=1,
            spans=[("long", 0.1, 9.0), ("short", 0.3, 0.2), ("mid", 2.5, 3.1),
                   ("late", 8.9, 2.0), ("short", 4.0, 0.1)])
    else:
        trace = tracered.load(os.path.join(manifest.BENCH, "testdata",
                                           name + ".trace.json.gz"))
    want = _idle_gaps_by_every_span(trace)
    got = tracered.breakdown(trace)["idle_gaps"]
    assert want and got == [[k, v] for k, v in want]    # to the last digit
