"""The program's own spans as the benchmark reads them: each of PR 26's ten
metrics on a small trace recorded on the chip (four flushes of
kaggle_serve_ranking, one v5e chip, kept under benchmarks/testdata), and the
two span readers on spans made by hand."""

import os

import pytest

from benchmarks.lib import manifest, progspans, tracered
from benchmarks.readers import idle_in_span, prog_span

TRACE = os.path.join(manifest.BENCH, "testdata",
                     "serve_ranking_flushes.trace.json.gz")
HOST, OTHER = (701, 1), (701, 2)


def _span(name, start, dur, thread=HOST, **args):
    return progspans.Span(name, thread, float(start), float(dur), args)


def _read(reader, spec, ctx):
    return {"prog_span": prog_span, "idle_in_span": idle_in_span}[
        reader].read(ctx, spec)


# ------------------------------------------------------ the recorded trace


# what the trace holds, read by hand from the same file: flushes 101 to 104 of
# a traced window (13, 4, 13 and 3 requests), ms a flush and shares of the
# 31.713 ms that device 0 idles between its first and its last operation
FLUSHES, FIRST_FLUSH, RUNGS = 4, 101, [4096, 2048, 4096, 512]
READINGS = {
    "serve_pack_ms_p50": 0.705665,     # 0.912439 0.5179 0.89343 0.50385
    "serve_h2d_ms_p50": 6.9941545,     # 6.903099 7.15946 6.75707 7.08521
    "serve_h2d_ms_max": 7.15946,
    "serve_h2d_per_flush": 27.0,
    "serve_fetch_ms_p50": 3.022475,    # 3.7573 2.28765 3.80547 1.279329
    "serve_flush_ms_max": 12.50001,
    "serve_idle_in_h2d_share": 62.87833,
    "serve_idle_outside_flush_share": 3.03193,
    "serve_dense_ms": 0.2161152,
    "serve_unscoped_ms": 0.02981957,
}


@pytest.fixture(scope="module")
def ctx():
    trace = tracered.load(TRACE)
    return {"trace": trace, "prog_spans": progspans.load(TRACE),
            "flushes": len(trace.modules)}


def test_the_recorded_trace_loads(ctx):
    t, spans = ctx["trace"], ctx["prog_spans"]
    assert t.devices == 1 and len(t.modules) == FLUSHES
    assert len(t.ops) == 555 and len(spans) == 274
    groups = progspans.flushes(spans)
    assert [int(f.args["flush"]) for f, _ in groups] \
        == list(range(FIRST_FLUSH, FIRST_FLUSH + FLUSHES))
    for f, inside in groups:
        names = [s.name for s in inside]
        assert names[-3:] == ["serve/dispatch", "serve/fetch", "serve/reply"]
        assert names.count("serve/h2d") == 27   # 26 tables and the numericals
        assert int(f.args["samples"]) <= int(f.args["rung"])
    assert [int(f.args["rung"]) for f, _ in groups] == RUNGS
    # the spans outside every flush are the submits between them
    in_flush = sum(len(inside) for _, inside in groups)
    assert {s.name for s in spans} - {"serve/submit"} == {
        "serve/flush", "serve/pack", "serve/h2d", "serve/dispatch",
        "serve/fetch", "serve/reply"}
    assert in_flush + FLUSHES + sum(s.name == "serve/submit"
                                    for s in spans) == len(spans)
    # every scoped operation of the serve program is under one of its two
    # scopes; the harness's own spans are tracered's, not these
    assert {o.scope.split("/")[0] for o in t.ops if o.scope} \
        == {"embedding_forward", "dense_forward"}
    assert {n for n, _, _ in t.spans} == {"poll", "submit"}


@pytest.mark.parametrize("metric", sorted(READINGS))
def test_reader_reads_the_recorded_trace(ctx, metric):
    got = manifest.read_metric(metric, ctx)
    assert got == pytest.approx(READINGS[metric], rel=2e-4, abs=1e-9)


def test_the_flush_adds_up_on_the_recorded_trace(ctx):
    # pack and transfers are what coalesce_ms times; the shares are of one idle
    # time; the lookup is nearly all of embedding_forward (ms a flush)
    read = lambda m: manifest.read_metric(m, ctx)  # noqa: E731
    assert read("serve_idle_in_h2d_share") \
        + read("serve_idle_outside_flush_share") <= 100.0
    assert read("serve_unscoped_ms") < 0.1 * read("serve_device_step_ms")
    flushes = ctx["flushes"]
    emb = 1e3 * tracered.scope_seconds(ctx["trace"],
                                       ["embedding_forward"]) / flushes
    assert emb == pytest.approx(0.7061708, rel=2e-4)
    assert emb - read("serve_lookup_ms") == pytest.approx(0.006529, rel=2e-3)
    assert read("serve_device_step_ms") == pytest.approx(0.9525728, rel=2e-4)


def test_a_trace_without_the_spans_reports_nothing():
    # a train cell, a parent commit: no serve/ span anywhere in the trace
    train = os.path.join(manifest.BENCH, "testdata",
                         "train_onehot_3steps.trace.json.gz")
    ctx = {"trace": tracered.load(train), "prog_spans": progspans.load(train),
           "flushes": None}
    assert ctx["prog_spans"] == []
    for metric in ("serve_pack_ms_p50", "serve_h2d_per_flush",
                   "serve_flush_ms_max", "serve_idle_in_h2d_share",
                   "serve_idle_outside_flush_share", "serve_dense_ms",
                   "serve_unscoped_ms"):
        assert manifest.read_metric(metric, ctx) is None
    # no trace at all: nothing is looked for on the disk either
    assert progspans.of_ctx({}) is None
    assert progspans.of_run(os.path.join(manifest.BENCH, "testdata")) is None


# ------------------------------------------------------------ spans by hand


def test_children_belong_to_the_flush_of_their_thread():
    spans = [
        _span("serve/h2d", 0.0, 0.5),                 # the warm-up's: no flush
        _span("serve/flush", 1.0, 1.0, flush="7"),
        _span("serve/pack", 1.0, 0.1),
        _span("serve/h2d", 1.1, 0.2),
        _span("serve/h2d", 1.3, 0.3),
        _span("serve/h2d", 1.4, 0.1, thread=OTHER),   # not this thread's
        _span("serve/fetch", 1.7, 0.2),
        _span("serve/flush", 3.0, 2.0, flush="8"),
        _span("serve/h2d", 3.5, 1.0),
        _span("serve/fetch", 4.5, 0.4),
    ]
    groups = progspans.flushes(spans)
    assert [f.args["flush"] for f, _ in groups] == ["7", "8"]
    assert [[s.name for s in inside] for _, inside in groups] == [
        ["serve/pack", "serve/h2d", "serve/h2d", "serve/fetch"],
        ["serve/h2d", "serve/fetch"]]
    ctx = {"prog_spans": spans}
    h2d = {"span": "serve/h2d", "take": "ms"}
    assert _read("prog_span", dict(h2d, over="max"), ctx) \
        == pytest.approx(1000.0)
    assert _read("prog_span", dict(h2d, over=50), ctx) == pytest.approx(750.0)
    assert _read("prog_span", dict(h2d, over="mean"), ctx) \
        == pytest.approx(750.0)
    assert _read("prog_span", {"span": "serve/h2d", "take": "count",
                               "over": "mean"}, ctx) == pytest.approx(1.5)
    assert _read("prog_span", {"span": "serve/flush", "take": "ms",
                               "over": "max"}, ctx) == pytest.approx(2000.0)
    # a flush without the span counts as nought; a name no flush has is nothing
    assert _read("prog_span", {"span": "serve/pack", "take": "ms",
                               "over": "mean"}, ctx) == pytest.approx(50.0)
    assert _read("prog_span", dict(h2d, span="serve/reply", over=50),
                 ctx) is None
    assert _read("prog_span", dict(h2d, over=50), {"prog_spans": []}) is None
    assert _read("prog_span", dict(h2d, over=50),
                 {"prog_spans": [spans[0]]}) is None


def test_idle_time_by_the_span_it_falls_in():
    Op = tracered.Op
    # device 0 runs 0-1 and 3-4: one gap of two seconds
    trace = tracered.Trace(ops=[Op(0, "", "a", 0.0, 1.0),
                                Op(0, "", "b", 3.0, 1.0),
                                Op(1, "", "c", 1.0, 2.0)],
                           modules=[], spans=[], devices=2)
    spans = [
        _span("serve/flush", 1.5, 2.1),
        _span("serve/h2d", 2.0, 1.5),                   # half the gap
        _span("serve/h2d", 2.5, 0.3, thread=OTHER),     # in no flush of its own
        _span("serve/flush", 1.0, 0.2, thread=OTHER),
    ]
    ctx = {"trace": trace, "prog_spans": spans}
    h2d = {"span": "serve/h2d"}
    assert _read("idle_in_span", dict(h2d, within_flush=True), ctx) \
        == pytest.approx(50.0)
    # both threads' spans, and the overlap counted once
    assert _read("idle_in_span", h2d, ctx) == pytest.approx(50.0)
    # the two flushes cover 1.0-1.2 and 1.5-3.0 of the gap: 0.3 s are outside
    assert _read("idle_in_span", {"span": "serve/flush", "outside": True},
                 ctx) == pytest.approx(15.0)
    assert _read("idle_in_span", {"span": "serve/reply"}, ctx) is None
    assert _read("idle_in_span", h2d, dict(ctx, prog_spans=[])) is None
    no_device = tracered.Trace(ops=[], modules=[], spans=[], devices=0)
    assert _read("idle_in_span", h2d, dict(ctx, trace=no_device)) is None
    assert _read("idle_in_span", h2d, dict(ctx, trace=None)) is None
