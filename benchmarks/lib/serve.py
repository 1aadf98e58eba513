"""A serving cell: an open loop of a family's requests at a fixed rate through
``ServingRuntime.submit``/``poll`` in this process, timed from when each
request was due, and which of the served requests the family's reference is
asked about.
"""

from __future__ import annotations

import contextlib
import time
from typing import List

import numpy as np

from benchmarks.families import system

DRAIN_S = 60.0      # how long past the close an answer is waited for
IDLE_SLEEP_S = 2e-4


def failed(result) -> bool:
    """Refused, lost or expired: ``Overloaded``, ``Expired``, ``Failed``,
    ``Unavailable``. A ``Served`` is not, however late (``deadline_missed``);
    whether it says the right thing is the comparison's to decide."""
    return system.is_refused(result)


def latencies_ms(due_s, t_submit_s, results: dict) -> np.ndarray:
    """Latency of every request from its due time to its reply. ``results[i]``
    is request ``i``'s typed result or missing; ``t_submit_s[i]`` the clock at
    its submit, on the window's time base. A failed or unanswered request
    counts as the longest served one."""
    lat = np.full(len(due_s), np.nan)
    for i, r in results.items():
        if not failed(r):
            lat[i] = (t_submit_s[i] - due_s[i]) * 1e3 + r.latency_ms
    worst = np.nanmax(lat) if np.isfinite(lat).any() else float("inf")
    return np.where(np.isnan(lat), worst, lat)


def open_loop(rt, schedule, requests: List,
              span=contextlib.nullcontext, clock=time.monotonic,
              sleep=time.sleep):
    """Submit each request when it is due and poll until every one has its
    result or ``DRAIN_S`` has passed since the last was due. Returns
    ``(results by request index, submit times, last reply time)``, times in
    seconds since the window opened."""
    n = len(schedule)
    due = schedule.due_s
    t_sub = np.zeros(n)
    results, by_rid = {}, {}
    t_last = 0.0
    i = 0
    t_open = clock()
    while len(results) < n:
        now = clock() - t_open
        if i < n and due[i] <= now:
            with span("submit"):
                while i < n and due[i] <= now:
                    rej = rt.submit(requests[i])
                    t_sub[i] = requests[i].t_submit - t_open
                    by_rid[requests[i].rid] = i
                    if rej is not None:
                        results[i] = rej
                    i += 1
                    now = clock() - t_open
        with span("poll"):
            done = rt.poll()
        if done:
            t_last = clock() - t_open
            for r in done:
                results[by_rid[r.rid]] = r
        elif i == n and now > due[-1] + DRAIN_S:
            break
        elif not rt.queued_samples and (i == n or due[i] - now > 2 * IDLE_SLEEP_S):
            with span("wait_arrival"):
                sleep(IDLE_SLEEP_S)
    return results, t_sub, t_last


def slowest_flushes(results: dict, n: int) -> list:
    """``(request index, result)`` of one request from each of the ``n``
    flushes that took longest from pack to reply, for standard error."""
    in_flush = lambda r: r.latency_ms - r.spans["queue_wait_ms"]  # noqa: E731
    seen, out = set(), []
    for i, r in sorted(((i, r) for i, r in results.items() if not failed(r)),
                       key=lambda ir: -in_flush(ir[1])):
        key = round(in_flush(r), 6)     # requests of one flush share it
        if key not in seen:
            seen.add(key)
            out.append((i, r))
        if len(out) == n:
            break
    return out


def sample_to_compare(seed: int, results: dict, sizes: np.ndarray,
                      want: int) -> List[int]:
    """``want`` served requests drawn from the seed, the largest among them."""
    served = sorted(i for i, r in results.items() if not failed(r))
    if not served:
        return []
    rng = np.random.default_rng([int(seed), 3])
    pick = set(rng.choice(served, size=min(want, len(served)),
                          replace=False).tolist())
    pick.add(max(served, key=lambda i: sizes[i]))
    return sorted(pick)
