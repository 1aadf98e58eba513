"""What counts as failed, and latency from the due time: a hand-made schedule
against a fake runtime with one stall."""

import numpy as np

from benchmarks.families import system
from benchmarks.lib import manifest, serve
from distributed_embeddings_tpu.parallel import serving as sv

traffic = manifest.load_family("dlrm").traffic


def test_a_late_answer_is_not_failed_and_a_refusal_is():
    late = sv.Served(rid=0, latency_ms=5000.0, deadline_missed=True)
    assert not serve.failed(late)
    for refused in (sv.Overloaded(rid=1, latency_ms=0.0),
                    sv.Expired(rid=2, latency_ms=2000.0),
                    sv.Unavailable(rid=3, latency_ms=0.0),
                    sv.Failed(rid=4, latency_ms=1.0)):
        assert serve.failed(refused)


class _FakeRuntime:
    """Answers every queued request at its next poll, 1 ms after the poll;
    one poll stalls for ``stall_s``. Time is the test's own."""

    def __init__(self, clock, stall_at, stall_s):
        self.clock, self.stall_at, self.stall_s = clock, stall_at, stall_s
        self.queue, self.rid, self.polls = [], 0, 0
        self.queued_samples = 0

    def submit(self, req):
        req.rid, req.t_submit = self.rid, self.clock.now
        self.rid += 1
        self.queue.append(req)
        self.queued_samples += 1
        return None

    def poll(self):
        if not self.queue:
            return []
        self.polls += 1
        if self.polls == self.stall_at:
            self.clock.now += self.stall_s
        self.clock.now += 0.001
        out = [sv.Served(rid=r.rid,
                         latency_ms=(self.clock.now - r.t_submit) * 1e3)
               for r in self.queue]
        self.queue, self.queued_samples = [], 0
        return out


class _Clock:
    def __init__(self):
        self.now = 100.0

    def __call__(self):
        self.now += 1e-5    # reading the clock takes time: a busy wait ends
        return self.now

    def sleep(self, s):
        self.now += s


def test_p95_counts_the_wait_a_stall_imposes_on_later_requests():
    n = 100
    sched = traffic.ServeSchedule(
        due_s=np.arange(n) * 0.010, offsets=np.arange(n + 1),
        ids=[np.zeros(n, np.int32)], numerical=np.zeros((n, 1), np.float32))
    reqs = [system.Request(cats=c, batch=b)
            for c, b in (sched.request(i) for i in range(n))]
    clock = _Clock()
    rt = _FakeRuntime(clock, stall_at=3, stall_s=0.200)
    results, t_sub, t_last = serve.open_loop(rt, sched, reqs, clock=clock,
                                             sleep=clock.sleep)
    assert len(results) == n
    lat = serve.latencies_ms(sched.due_s, t_sub, results)
    # the stall hits one request directly, and the twenty that fell due during
    # it were submitted late: from their due times they waited too, though the
    # runtime's own latency (from submit) forgives them
    own = np.array([results[i].latency_ms for i in range(n)])
    assert (own > 50).sum() == 1
    assert (lat > 50).sum() >= 15
    assert np.percentile(lat, 95) > 100 > np.percentile(own, 95)
    assert abs(t_last - (sched.due_s[-1] + 0.001)) < 0.05


def test_a_failed_request_counts_as_the_longest():
    due = np.array([0.0, 0.1, 0.2])
    res = {0: sv.Served(rid=0, latency_ms=10.0),
           1: sv.Expired(rid=1, latency_ms=2000.0),
           2: sv.Served(rid=2, latency_ms=30.0)}
    lat = serve.latencies_ms(due, due + 0.001, res)
    assert np.allclose(lat, [11.0, 31.0, 31.0])
    # a request that never came back counts the same
    del res[1]
    assert np.allclose(serve.latencies_ms(due, due + 0.001, res),
                       [11.0, 31.0, 31.0])


def test_the_sample_to_compare_holds_the_largest_request():
    res = {i: sv.Served(rid=i, latency_ms=1.0) for i in range(50)}
    res[7] = sv.Overloaded(rid=7, latency_ms=0.0)
    sizes = np.arange(50) + 100
    sizes[7] = 10_000
    pick = serve.sample_to_compare(11, res, sizes, 10)
    assert 49 in pick and 7 not in pick and len(pick) in (10, 11)
    assert pick == serve.sample_to_compare(11, res, sizes, 10)
