"""The one general traffic generator, as far as it knows no model: the draws
(ids by a power law, multi-hot row lengths) and the arrival process that a
family's batch and request makers are built from.

Every seed gets the same multiset of request sizes, arrival gaps and
multi-hot row lengths in another order, so that the seed changes which ids
are drawn and not how much work a run holds.
"""

from __future__ import annotations

import numpy as np


def power_law_ids(rng: np.random.Generator, vocab: int, shape,
                  alpha: float) -> np.ndarray:
    """Ids in ``[0, vocab)`` with ``p(x) ~ x**-alpha`` by the inverse CDF (the
    draw of the program's ``utils.data.power_law_ids``, copied so that the
    program cannot move the yardstick)."""
    u = rng.random(size=shape)
    exp = 1.0 - alpha
    ids = ((vocab + 1) ** exp * u + (1 - u)) ** (1.0 / exp) - 1.0
    return np.clip(ids.astype(np.int64), 0, vocab - 1).astype(np.int32)


def rng_of(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), stream])


def row_lengths(rng, batch: int, lo: int, hi: int, capacity: int) -> np.ndarray:
    """``batch`` row lengths, the fixed multiset ``lo..hi`` repeated evenly and
    shuffled; trimmed from the end if the sum would pass ``capacity``."""
    base = np.resize(np.arange(lo, hi + 1), batch)
    hot = rng.permutation(base)
    over = int(hot.sum()) - capacity
    i = batch - 1
    while over > 0:
        cut = min(over, int(hot[i]) - 1)
        hot[i] -= cut
        over -= cut
        i -= 1
    return hot


def _quantile_grid(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def request_sizes(traffic: dict, n: int) -> np.ndarray:
    """``n`` request sizes: the traffic file's piecewise-linear quantile curve
    read on an even grid, so the multiset is the same whatever the seed."""
    q = traffic["size_quantiles"]
    return np.round(np.interp(_quantile_grid(n), q["p"], q["samples"])
                    ).astype(np.int64)


def arrival_gaps(rate_per_s: float, n: int) -> np.ndarray:
    """``n`` exponential gaps (seconds) as the distribution's own quantiles on
    an even grid, scaled so that they sum to ``n / rate`` exactly."""
    g = -np.log1p(-_quantile_grid(n))
    return g * (n / g.sum()) / rate_per_s


def _burst_warp(due: np.ndarray, every: float, factor: float) -> np.ndarray:
    """Arrivals at a steady rate -> the same arrivals with the first second of
    each ``every`` seconds at ``factor`` times the rate and the rest slowed so
    that the mean rate is unchanged."""
    slow = (every - factor) / (every - 1.0)
    if slow <= 0:
        raise ValueError("burst factor leaves no load for the other seconds")
    period = np.floor(due / every)
    work = due - period * every
    return period * every + np.where(work < factor, work / factor,
                                     1.0 + (work - factor) / slow)


def arrivals(traffic: dict, rng: np.random.Generator, seconds: float):
    """Open-loop Poisson arrivals at the traffic file's fixed rate for
    ``seconds`` seconds: ``(due_s [n], offsets [n+1])``, request ``i`` due
    ``due_s[i]`` seconds after the window opens and holding samples
    ``offsets[i]:offsets[i+1]``. A family goes on drawing each request's
    content from the same ``rng``."""
    rate = float(traffic["rate_per_s"])
    n = max(1, int(round(rate * seconds)))
    sizes = rng.permutation(request_sizes(traffic, n))
    gaps = rng.permutation(arrival_gaps(rate, n))
    due = np.cumsum(gaps) - gaps       # the last gap closes the window
    burst = traffic.get("burst")
    if burst:
        due = _burst_warp(due, float(burst["every_s"]),
                          float(burst["factor"]))
    offsets = np.zeros(n + 1, np.int64)
    np.cumsum(sizes, out=offsets[1:])
    return due, offsets
