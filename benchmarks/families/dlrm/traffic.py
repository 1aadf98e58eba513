"""What family ``dlrm`` makes of a traffic file and a seed: the staged
training batches (ids a feature, one-hot or ragged, numerical features,
labels) and the ranking queries of a serving window. The draws that know no
model (the power law, the row lengths, the arrivals) are the general
generator's, ``benchmarks/lib/traffic.py``.
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional

import numpy as np

from benchmarks.lib.traffic import (arrivals, power_law_ids, rng_of,
                                    row_lengths)


@dataclasses.dataclass
class TrainBatch:
    """One global batch on the host. ``splits`` is None for one-hot ids;
    otherwise ``ids[t]`` is ``[capacity]`` and ``splits[t]`` ``[batch+1]``."""
    ids: List[np.ndarray]
    splits: Optional[List[np.ndarray]]
    numerical: np.ndarray
    labels: np.ndarray


def live_ids(batch: TrainBatch) -> List[np.ndarray]:
    if batch.splits is None:
        return batch.ids
    return [i[:int(s[-1])] for i, s in zip(batch.ids, batch.splits)]


def train_batches(traffic: dict, table_sizes, num_numerical: int,
                  seed: int) -> List[TrainBatch]:
    """``distinct_batches`` global batches drawn from the seed."""
    rng = rng_of(seed, 1)
    b = int(traffic["global_batch"])
    alpha = float(traffic["id_alpha"])
    hot = traffic["hotness"]
    out = []
    for _ in range(int(traffic["distinct_batches"])):
        if hot["kind"] == "one":
            ids = [power_law_ids(rng, s, (b,), alpha) for s in table_sizes]
            splits = None
        elif hot["kind"] == "uniform":
            cap = int(hot["capacity"])
            ids, splits = [], []
            for s in table_sizes:
                lens = row_lengths(rng, b, int(hot["min"]), int(hot["max"]),
                                   cap)
                sp = np.zeros(b + 1, np.int32)
                np.cumsum(lens, out=sp[1:])
                v = np.zeros(cap, np.int32)
                v[:sp[-1]] = power_law_ids(rng, s, (int(sp[-1]),), alpha)
                ids.append(v)
                splits.append(sp)
        else:
            raise ValueError(f"unknown hotness kind {hot['kind']!r}")
        out.append(TrainBatch(
            ids=ids, splits=splits,
            numerical=rng.normal(size=(b, num_numerical)).astype(np.float32),
            labels=rng.integers(0, 2, size=(b, 1)).astype(np.float32)))
    return out


@dataclasses.dataclass
class ServeSchedule:
    """Every request of a window: request ``i`` is due ``due_s[i]`` seconds
    after the window opens and holds samples ``offsets[i]:offsets[i+1]``."""
    due_s: np.ndarray
    offsets: np.ndarray
    ids: List[np.ndarray]
    numerical: np.ndarray

    def __len__(self):
        return len(self.due_s)

    def request(self, i: int):
        a, b = int(self.offsets[i]), int(self.offsets[i + 1])
        return [c[a:b] for c in self.ids], self.numerical[a:b]


def serve_schedule(traffic: dict, table_sizes, num_numerical: int, seed: int,
                   seconds: float) -> ServeSchedule:
    """Open-loop Poisson arrivals at the traffic file's fixed rate for
    ``seconds`` seconds, each request one ranking query."""
    rng = rng_of(seed, 2)
    due, offsets = arrivals(traffic, rng, seconds)
    total = int(offsets[-1])
    alpha = float(traffic["id_alpha"])
    return ServeSchedule(
        due_s=due, offsets=offsets,
        ids=[power_law_ids(rng, s, (total,), alpha) for s in table_sizes],
        numerical=rng.standard_normal(size=(total, num_numerical),
                                      dtype=np.float32))
