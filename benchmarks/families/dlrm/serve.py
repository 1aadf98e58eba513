"""A serving cell of family ``dlrm``: the ranking queries as the runtime takes
them, the reference's logits for the served requests that the runner picked,
and the comparison of what was served with them.
"""

from __future__ import annotations

from typing import List

import jax.numpy as jnp
import numpy as np

from benchmarks.families import system

from . import program, reference, traffic, weights


def requests_of(schedule: traffic.ServeSchedule) -> List:
    """Every request of the schedule as the runtime takes it."""
    return [system.Request(cats=cats, batch=num) for cats, num in
            (schedule.request(i) for i in range(len(schedule)))]


def reference_logits(config: dict, schedule: traffic.ServeSchedule,
                     picked: List[int], seed: int,
                     precision: str = "float32") -> np.ndarray:
    """The plain reference's logits for every sample of the picked requests,
    in their order, from weights it makes itself from the seed."""
    sizes = [int(s) for s in config["table_sizes"]]
    dim = int(config["embedding_dim"])
    tdt = program._dtype(config["table_dtype"])
    words = jnp.asarray(weights.seed_words(seed))
    ids = [np.concatenate([schedule.request(i)[0][t] for i in picked])
           for t in range(len(sizes))]
    num = np.concatenate([schedule.request(i)[1] for i in picked])
    room = (len(picked) + 1) * int(np.diff(schedule.offsets).max())
    room += -room % 4096
    uniq, mapped, _ = reference.compact([ids],
                                        [min(s, room) for s in sizes])
    tabs = weights.rows_fn(sizes, dim, tdt)(uniq, words)
    dense = [(jnp.asarray(k), jnp.asarray(b)) for k, b in weights.dense_params(
        seed, int(config["num_numerical"]), config["bottom_mlp"],
        config["top_mlp"], len(sizes), dim)]
    return reference.forward_blocks(tabs, dense, mapped[0], num,
                                    len(config["bottom_mlp"]), precision)


def compare(schedule: traffic.ServeSchedule, results: dict, picked: List[int],
            want: np.ndarray) -> dict:
    """The widest gap between a served prediction and the reference's logit
    for the same sample, over the picked requests, and how many of them came
    back with another number of predictions than they had samples."""
    gap, misshapen, a = 0.0, 0, 0
    for i in picked:
        n = int(schedule.offsets[i + 1] - schedule.offsets[i])
        got = np.asarray(results[i].predictions, np.float32).reshape(-1)
        if got.shape[0] != n or not np.isfinite(got).all():
            misshapen += 1
        else:
            gap = max(gap, float(np.abs(got - want[a:a + n]).max()))
        a += n
    return {"logit_gap": gap, "misshapen": float(misshapen),
            "logit_scale": float(np.abs(want).max()) if len(want) else 0.0}
