"""The session runtime (``parallel/lm_serving.py``) on the CPU at toy widths,
on an injected clock: two sessions interleaved answer as each does alone and
as the plain reference's full forward pass says, turns of a session
serialize and continue its context, admission counts the cache's tokens and
the queue, a turn past its deadline expires, no shape compiles once warm, and
``stats()`` has what the harness reads."""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.families import mla_lm as fam  # noqa: E402
from benchmarks.families.mla_lm import serve, traffic  # noqa: E402
from benchmarks.lib.traffic import power_law_ids, rng_of  # noqa: E402
from distributed_embeddings_tpu.parallel.serving import (  # noqa: E402
    Expired, Overloaded, Request, Served)

CONFIG = {
    "hidden_size": 64, "num_attention_heads": 4, "q_lora_rank": 32,
    "kv_lora_rank": 16, "qk_nope_head_dim": 8, "qk_rope_head_dim": 8,
    "v_head_dim": 8, "intermediate_size": 96, "moe_intermediate_size": 16,
    "n_shared_experts": 1, "num_hidden_layers": 3,
    "first_k_dense_replace": 1, "router_outputs": 16, "n_group": 4,
    "topk_group": 2, "num_experts_per_tok": 4, "experts_held": [0, 4],
    "n_routed_experts": 4, "routed_scaling_factor": 2.5,
    "norm_topk_prob": True, "vocab_size": 96, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 32,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "rms_norm_eps": 1e-6, "chips": 1,
    "program": {"moe_chunk": 64, "ffn_chunk": 32, "attn_block": 32}}
TRAFFIC = {"documents": 2, "document_tokens": 64, "sessions_per_document": 2,
           "id_alpha": 1.05, "prompt_tokens": 12, "logits_at": [0, 3, 7]}
SERVE = {"rungs": [4], "capacity": 128, "prefill_chunk": 8,
         "deadline_ms": 60000, "max_queue": 40}
SEED = 5
G = 8


class Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


@pytest.fixture(scope="module")
def built():
    return fam.build(CONFIG, TRAFFIC, SEED)


def _runtime(built, **serve_over):
    rt = fam.serving_runtime(built, dict(SERVE, **serve_over))
    rt._clock = Clock()
    rt.warmup()
    return rt


def _prompt(k):
    return power_law_ids(rng_of(k, 7), CONFIG["vocab_size"], (12,), 1.05)


def _turn(session, k, **kw):
    return Request(cats=[_prompt(k)], session=session, max_new_tokens=G,
                   logits_at=tuple(TRAFFIC["logits_at"]), **kw)


def _serve(rt, reqs, step_ms=1.0, polls=500):
    """Submit ``reqs`` at the clock's now, then poll (the clock moving
    ``step_ms`` a poll) until each is answered; ``{rid: result}``."""
    out = {}
    for r in reqs:
        rej = rt.submit(r)
        if rej is not None:
            out[rej.rid] = rej
    for _ in range(polls):
        for res in rt.poll():
            out[res.rid] = res
        rt._clock.t += step_ms / 1e3
        if len(out) == len(reqs):
            break
    return out


def _schedule(turns):
    """The turns ``[(session, prompt key)]``, in submission order, as the
    family's schedule, for the reference."""
    n = len(turns)
    return traffic.SessionSchedule(
        due_s=np.zeros(n), offsets=np.arange(n + 1) * G,
        prompts=np.stack([_prompt(k) for _, k in turns]),
        session=np.asarray([s for s, _ in turns]),
        documents=traffic.documents(TRAFFIC, CONFIG["vocab_size"], SEED),
        per_document=TRAFFIC["sessions_per_document"],
        logits_at=tuple(TRAFFIC["logits_at"]))


def test_interleaved_sessions_answer_as_each_alone_and_as_the_reference(
        built):
    """Sessions 0 and 1 start from document 0, 2 and 3 from document 1.
    Turn X alone on session 0, then X on session 1 beside turn Y on session
    2, then Y alone on session 3: each pair agrees, and all four agree with
    the reference's full forward pass of the document and the turn."""
    rt = _runtime(built)
    (x0,) = _serve(rt, [_turn(0, 1)]).values()
    both = _serve(rt, [_turn(1, 1), _turn(2, 2)])
    x1, y2 = (both[k] for k in sorted(both))
    (y3,) = _serve(rt, [_turn(3, 2)]).values()
    for a, b in ((x0, x1), (y2, y3)):
        assert isinstance(a, Served) and isinstance(b, Served)
        assert a.tokens.shape == (G,) and a.predictions.shape == (3, 96)
        np.testing.assert_array_equal(a.tokens, b.tokens)
        np.testing.assert_allclose(a.predictions, b.predictions, atol=1e-5)
    assert rt.steady_recompiles() == 0      # before the reference compiles
    sch = _schedule([(0, 1), (1, 1), (2, 2), (3, 2)])
    results = dict(enumerate([x0, x1, y2, y3]))
    nums = serve.compare(sch, results, [0, 1, 2, 3],
                         fam.reference_answers(CONFIG, sch, [0, 1, 2, 3],
                                               SEED))
    assert nums["misshapen"] == 0 and nums["logit_gap"] < 0.02


def test_turns_of_a_session_serialize_and_continue_its_context(built):
    rt = _runtime(built)
    got = _serve(rt, [_turn(0, 3), _turn(0, 4)])
    first, second = (got[k] for k in sorted(got))
    # the second waits in the queue until the first's last step is out
    assert second.spans["queue_wait_ms"] >= first.latency_ms - 2.0
    for r in (first, second):
        assert abs(sum(r.spans.values()) - r.latency_ms) < 1e-6
    assert rt.steady_recompiles() == 0
    sch = _schedule([(0, 3), (0, 4)])
    nums = serve.compare(sch, dict(enumerate([first, second])), [0, 1],
                         fam.reference_answers(CONFIG, sch, [0, 1], SEED))
    assert nums["misshapen"] == 0 and nums["logit_gap"] < 0.02
    st = rt.stats()
    for key in ("flushes", "pad_fraction", "steps", "chunk_steps",
                "decode_batch_mean", "ttft_p50_ms", "served", "shed",
                "latency_p50_ms", "queued_samples"):
        assert key in st, key
    assert st["served"] == 2 and st["queued_samples"] == 0
    # one chunk step a prompt of 12 in chunks of 8 is two; a turn decodes 7
    assert st["chunk_steps"] == 4 and st["decode_steps"] == 14


def test_admission_counts_the_cache_and_the_queue(built):
    """A session of 128 tokens holds a document of 64 and three turns of
    12 + 8 - 1 (each needs 16 free for its two prompt chunks); the fourth
    overflows. The queue holds 40 tokens still to generate: five turns."""
    rt = _runtime(built)
    got = [rt.submit(_turn(0, k)) for k in range(4)]
    assert got[:3] == [None] * 3
    assert isinstance(got[3], Overloaded) and got[3].reason == "cache_full"
    more = [rt.submit(_turn(1, 10 + k)) for k in range(3)]
    assert more[:2] == [None, None]
    assert isinstance(more[2], Overloaded) and more[2].reason == "queue_full"
    assert rt.queued_samples == 5 * G
    assert rt.stats()["shed"] == 2


def test_a_turn_waiting_past_its_deadline_expires(built):
    rt = _runtime(built)
    got = _serve(rt, [_turn(2, 5), _turn(2, 6, deadline_ms=5.0)],
                 step_ms=1.0)
    first, second = (got[k] for k in sorted(got))
    assert isinstance(first, Served)
    assert isinstance(second, Expired)
    assert rt.stats()["expired"] == 1 and rt.queued_samples == 0


def test_every_seed_gets_the_same_arrivals_and_its_own_ids():
    """The family's arrivals are due at the same times whatever the seed
    (the gaps' order is drawn from ``traffic.ARRIVAL_SEED``); the prompts
    and the documents are the seed's."""
    tr = dict(TRAFFIC, rate_per_s=4.0, size_quantiles={"p": [0, 1],
                                                       "samples": [G, G]})
    a, b = (traffic.serve_schedule(tr, CONFIG["vocab_size"], s, 5.0)
            for s in (2**31 + 7, 12345))
    np.testing.assert_array_equal(a.due_s, b.due_s)
    np.testing.assert_array_equal(a.offsets, b.offsets)
    np.testing.assert_array_equal(a.session, b.session)
    assert not np.array_equal(a.prompts, b.prompts)
    assert not np.array_equal(a.documents, b.documents)


def test_a_flush_span_holds_each_steps_transfer_and_read_back(built,
                                                              monkeypatch):
    """``serve/flush`` wraps a poll that has work: the step's ``serve/h2d``
    and the previous step's ``serve/fetch`` lie inside one, as the harness's
    ``prog_span`` readers group them; a poll with nothing to do opens
    none."""
    import contextlib

    from distributed_embeddings_tpu.parallel import lm_serving
    rt = _runtime(built)
    log, depth = [], []

    @contextlib.contextmanager
    def span(name, **_):
        log.append((name, tuple(depth)))
        depth.append(name)
        try:
            yield
        finally:
            depth.pop()

    monkeypatch.setattr(lm_serving.obs, "span", span)
    assert rt.poll() == [] and log == []
    _serve(rt, [_turn(1, 8)])
    inner = [outer for name, outer in log
             if name in ("serve/h2d", "serve/fetch", "serve/step")]
    assert inner and all("serve/flush" in outer for outer in inner)
    flushes = sum(name == "serve/flush" for name, _ in log)
    # one a step, and one that reads the last step back
    assert flushes == rt.stats()["flushes"] + 1


class Device:
    """Stands in for ``SessionRuntime._await``: a device that runs the
    runtime's steps in order from when each was sent, ``chunk_ms`` a step
    that holds a prompt chunk and ``decode_ms`` another, on the runtime's
    clock. Asked about a step, a tenth of a millisecond after the poll sent
    its own, it says whether the step is done and moves the clock to the
    step's end where it is not."""

    def __init__(self, clock, decode_ms, chunk_ms):
        self.clock, self.decode_ms, self.chunk_ms = clock, decode_ms, chunk_ms
        self.free = 0.0
        self.log = []               # (chunk, ready) a read-back

    def __call__(self, st):
        self.clock.t += 1e-4
        start = max(st.t_sent, self.free)
        self.free = start + (self.chunk_ms if st.chunk else self.decode_ms) / 1e3
        ready = self.clock.t >= self.free
        self.clock.t = max(self.clock.t, self.free)
        self.log.append((st.chunk, ready))
        return ready


def _timed_runtime(built, monkeypatch, decode_ms=3.0, chunk_ms=8.0):
    rt = _runtime(built)
    dev = Device(rt._clock, decode_ms, chunk_ms)
    monkeypatch.setattr(rt, "_await", dev)
    return rt, dev


STEP_KEYS = ("readbacks", "readback_ready", "readback_ready_share",
             "decode_step_ms", "chunk_step_ms", "decode_step_pairs",
             "chunk_step_pairs", "tpot_p50_ms")


def test_read_backs_that_wait_in_a_row_time_each_step_on_the_device(
        built, monkeypatch):
    """A host that polls every millisecond beside a device that takes 3 ms a
    decode step and 8 a chunk step waits at every read-back: each step after
    the first of a turn pairs with the one before, so the counters read the
    device's own step times, each kind in its own sum. A turn's prompt of 12
    is two chunk steps, the second of which yields the first token, then 7
    decode steps; the last is read back by a poll that sends nothing."""
    rt, dev = _timed_runtime(built, monkeypatch)
    (r,) = _serve(rt, [_turn(0, 1)]).values()
    assert isinstance(r, Served)
    assert dev.log == [(True, False)] * 2 + [(False, False)] * 7
    st = rt.stats()
    assert st["readbacks"] == 9 and st["readback_ready"] == 0
    assert st["readback_ready_share"] == 0.0
    # the first chunk step has no predecessor that waited: no pair
    assert st["chunk_step_pairs"] == 1 and st["decode_step_pairs"] == 7
    assert st["chunk_step_ms"] == pytest.approx(8.0, abs=1e-9)
    assert st["decode_step_ms"] == pytest.approx(3.0, abs=1e-9)
    # a second turn after an idle gap starts a new chain
    (r2,) = _serve(rt, [_turn(1, 2)]).values()
    st = rt.stats()
    assert st["readbacks"] == 18
    assert st["chunk_step_pairs"] == 2 and st["decode_step_pairs"] == 14
    assert st["chunk_step_ms"] == pytest.approx(8.0, abs=1e-9)
    assert st["decode_step_ms"] == pytest.approx(3.0, abs=1e-9)


def test_a_ready_read_back_counts_and_breaks_the_chain_on_both_sides(
        built, monkeypatch):
    """The host stalls 50 ms once, in the middle of the decode steps: the
    read-back after the stall finds its step done (``readback_ready``), and
    neither it nor the step after it pairs, since the device may have idled
    between them; the steps further on pair again."""
    rt, dev = _timed_runtime(built, monkeypatch)
    assert rt.submit(_turn(2, 3)) is None
    got = []
    for k in range(40):
        if k == 5:
            rt._clock.t += 0.05
        got += rt.poll()
        rt._clock.t += 1e-3
        if got:
            break
    assert len(got) == 1 and isinstance(got[0], Served)
    assert [ready for _, ready in dev.log].count(True) == 1
    st = rt.stats()
    assert st["readbacks"] == 9 and st["readback_ready"] == 1
    assert st["readback_ready_share"] == pytest.approx(100.0 / 9)
    assert st["chunk_step_pairs"] == 1 and st["decode_step_pairs"] == 5
    assert st["decode_step_ms"] == pytest.approx(3.0, abs=1e-9)


def test_a_host_slower_than_the_device_finds_every_step_done(built,
                                                             monkeypatch):
    """A device of half a millisecond a step beside a poll a millisecond:
    every read-back is ready, no step pairs, and the step times read
    ``None``, not 0, as they do on a runtime that has served nothing."""
    rt, dev = _timed_runtime(built, monkeypatch, decode_ms=0.5, chunk_ms=0.5)
    fresh = rt.stats()
    for key in STEP_KEYS:
        assert key in fresh, key
    assert fresh["readbacks"] == 0 and fresh["readback_ready_share"] is None
    assert fresh["decode_step_ms"] is None and fresh["chunk_step_ms"] is None
    assert fresh["tpot_p50_ms"] is None
    _serve(rt, [_turn(3, 4)])
    st = rt.stats()
    assert st["readbacks"] == 9 and st["readback_ready_share"] == 100.0
    assert st["decode_step_pairs"] == st["chunk_step_pairs"] == 0
    assert st["decode_step_ms"] is None and st["chunk_step_ms"] is None


def test_tpot_is_the_median_wait_for_each_token_after_the_first(
        built, monkeypatch):
    """``tpot_p50_ms`` is the median over served turns of ``decode_ms /
    (max_new_tokens - 1)``; a turn of one token has no such wait and is left
    out."""
    rt, _ = _timed_runtime(built, monkeypatch)
    one = Request(cats=[_prompt(9)], session=3, max_new_tokens=1,
                  logits_at=(0,))
    got = _serve(rt, [_turn(0, 5), _turn(1, 6), _turn(2, 7), one])
    served = [r for r in got.values() if isinstance(r, Served)]
    assert len(served) == 4
    want = [r.spans["decode_ms"] / (G - 1) for r in served
            if len(r.tokens) == G]
    assert len(want) == 3
    assert rt.stats()["tpot_p50_ms"] == pytest.approx(float(np.median(want)))


def test_a_scope_read_per_step_that_held_a_chunk():
    """``stat_scope_ms`` divides a scope's device time by a count of the
    runtime's ``stats()``: on the recorded three-step trace, the apply's
    64.54 ms a step read over three "chunk steps", and nothing where the
    count or the scope is absent."""
    from benchmarks.lib import manifest, tracered
    from benchmarks.readers import stat_scope_ms
    trace = tracered.load(os.path.join(manifest.BENCH, "testdata",
                                       "train_onehot_3steps.trace.json.gz"))
    spec = {"scopes": ["sparse_apply"], "exclude": [],
            "per_stat": "chunk_steps"}
    got = stat_scope_ms.read({"trace": trace,
                              "stats": {"chunk_steps": 3}}, spec)
    assert got == pytest.approx(64.540, rel=2e-4)
    assert stat_scope_ms.read({"trace": trace, "stats": {}}, spec) is None
    assert stat_scope_ms.read({"trace": trace, "stats": {"chunk_steps": 3}},
                              dict(spec, scopes=["mla_prefill"])) is None
