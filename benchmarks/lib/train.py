"""A training cell's measured window, over the compiled step and the state
that the family drove through the first ``CHECK_STEPS`` steps in set-up.
"""

from __future__ import annotations

import contextlib
import time

import jax
import numpy as np

CHECK_STEPS = 3   # the first steps, which the reference follows
# Steps the host may run ahead of the device: what one dispatch of a 16-step
# loop would queue. With 2, one host stall of half a second starved the device
# (one run in twelve read 8 % low, my chip runs, PR 25); the device's own time
# a step does not change with it.
IN_FLIGHT = 16


def window(step, state, staged, batch_size: int, seconds: float, first: int,
           span=None):
    """Dispatch steps for ``seconds`` seconds, the host at most ``IN_FLIGHT``
    steps ahead; the clock stops when the last step's state exists. Returns
    ``(samples_per_s, steps, seconds_taken, state, losses, stalls)``;
    ``stalls`` says where the host's longest waits were, for standard error."""
    span = span or contextlib.nullcontext
    pending, losses = [], []
    longest = {"dispatch": 0.0, "block": 0.0, "between": 0.0}
    n = 0
    jax.block_until_ready(state)
    t0 = t = time.perf_counter()
    while t - t0 < seconds:
        with span("dispatch"):
            loss, state = step(state, *staged[(first + n) % len(staged)])
        pending.append(loss)
        losses.append(loss)
        n += 1
        t1 = time.perf_counter()
        if len(pending) > IN_FLIGHT:
            with span("block"):
                pending.pop(0).block_until_ready()
        t2 = time.perf_counter()
        longest["dispatch"] = max(longest["dispatch"], t1 - t)
        longest["block"] = max(longest["block"], t2 - t1)
        t = time.perf_counter()
        longest["between"] = max(longest["between"], t - t2)
    with span("block"):
        jax.block_until_ready(state)
    dt = time.perf_counter() - t0
    return (n * batch_size / dt, n, dt, state,
            np.asarray(jax.device_get(losses)), longest)
