"""What family ``moe_lm`` makes of a training traffic file and a seed: the
staged batches, ``sequences`` documents of the configuration's
``train_sequence_length`` tokens each (no packing: every step the same work
on every seed), the ids by the general generator's power law over the
vocabulary held."""

from __future__ import annotations

from typing import List

import numpy as np

from benchmarks.lib.traffic import power_law_ids, rng_of


def train_batches(traffic: dict, vocab: int, seq_len: int,
                  seed: int) -> List[np.ndarray]:
    """``distinct_batches`` batches of ``[sequences, seq_len]`` int32 ids."""
    rng = rng_of(seed, 1)
    shape = (int(traffic["sequences"]), seq_len)
    return [power_law_ids(rng, vocab, shape, float(traffic["id_alpha"]))
            for _ in range(int(traffic["distinct_batches"]))]
