"""A training cell: set-up, the first three steps that the reference follows,
the measured window over the same compiled step and state, and the comparison.
"""

from __future__ import annotations

import contextlib
import time
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from . import check, program, reference, traffic, weights

CHECK_STEPS = 3
OBSERVE_ROWS = 65536   # ids a table in one call of the row observer
# Steps the host may run ahead of the device: what one dispatch of a 16-step
# loop would queue. With 2, one host stall of half a second starved the device
# (one run in twelve read 8 % low, my chip runs, PR 25); the device's own time
# a step does not change with it.
IN_FLIGHT = 16


def _leaf_norms(a_layers, b_layers, scale=1.0) -> List[float]:
    out = []
    for (ka, ba), (kb, bb) in zip(a_layers, b_layers):
        out.append(float(np.linalg.norm(np.asarray(ka, np.float64)
                                        - np.asarray(kb, np.float64))) * scale)
        out.append(float(np.linalg.norm(np.asarray(ba, np.float64)
                                        - np.asarray(bb, np.float64))) * scale)
    return out


def live_ids(batch: traffic.TrainBatch) -> List[np.ndarray]:
    if batch.splits is None:
        return batch.ids
    return [i[:int(s[-1])] for i, s in zip(batch.ids, batch.splits)]


class RowWatch:
    """Squared change of the table rows that a set of batches touched, read
    from the program's state through its own lookup."""

    def __init__(self, built: program.Built, words):
        self.built = built
        self.observe = program.row_observer(built)
        cfg = built.config
        sizes = [int(s) for s in cfg["table_sizes"]]
        dim = int(cfg["embedding_dim"])
        tdt = program._dtype(cfg["table_dtype"])
        self.rows = min(OBSERVE_ROWS, max(sizes))
        self.rows -= self.rows % built.world

        rows = weights.rows_fn(sizes, dim, tdt)
        # the seed goes in as an argument: closed over, it would be a constant
        # of the program, and every seed would compile its own
        self.words = words
        self.want = jax.jit(lambda ids, words: jnp.stack(rows(ids, words),
                                                         axis=1))

    def change_norms(self, state, batches) -> List[float]:
        """Per table, the norm of ``state's rows - the seed's rows`` over the
        distinct ids of ``batches``."""
        return self.change_norms_of(state, [
            np.unique(np.concatenate([live_ids(b)[t] for b in batches]))
            for t in range(len(batches[0].ids))])

    def change_norms_of(self, state, uniq) -> List[float]:
        """The same over ``uniq[table]``, distinct ids of each table."""
        n = self.rows
        total = np.zeros(len(uniq), np.float64)
        put = self.built.put
        for c in range(max(-(-len(u) // n) for u in uniq)):
            ids = np.zeros((len(uniq), n), np.int32)
            mask = np.zeros((n, len(uniq)), np.float32)
            for t, u in enumerate(uniq):
                part = u[c * n:(c + 1) * n]
                ids[t, :len(part)] = part
                mask[:len(part), t] = 1.0
            cats = [put(i) for i in ids]
            want = self.want(cats, self.words)
            got = self.observe(state, cats, (want, put(mask)))
            total += np.asarray(got, np.float64).sum(axis=0)
        return list(np.sqrt(total))


def one_half_ids(batch: traffic.TrainBatch) -> List[np.ndarray]:
    """Per table the ids that only the first half of the batch's samples
    touches, then per table those that only the second half touches: the rows
    that stay put, or move double, where half of a batch is left out and the
    mean taken over the rest."""
    h = len(batch.numerical) // 2
    first, second = [], []
    for t, ids in enumerate(live_ids(batch)):
        cut = h if batch.splits is None else int(batch.splits[t][h])
        a, b = np.unique(ids[:cut]), np.unique(ids[cut:])
        first.append(np.setdiff1d(a, b, assume_unique=True))
        second.append(np.setdiff1d(b, a, assume_unique=True))
    return first + second


def _two_halves(per_table: List[float]) -> List[float]:
    """Norms over the rows of each table (first half's tables, then second
    half's) -> two leaves: the norm over all tables' rows that the first half
    touches alone, and the second half's. A table's own few such rows (one or
    none in a table of a thousand rows) move by the table dtype's rounding,
    which the two sides do not share; over all tables they are some hundreds
    of thousands of rows."""
    h = len(per_table) // 2
    return [float(np.sqrt(np.sum(np.square(per_table[:h])))),
            float(np.sqrt(np.sum(np.square(per_table[h:]))))]


def reference_numbers(config: dict, tr: dict, batches, seed: int,
                      precision="float32", fault=None) -> dict:
    """The plain reference over the first three batches: its losses and leaf
    norms, from weights it makes itself from the seed."""
    sizes = [int(s) for s in config["table_sizes"]]
    dim = int(config["embedding_dim"])
    tdt = program._dtype(config["table_dtype"])
    words = jnp.asarray(weights.seed_words(seed))
    n_bottom = len(config["bottom_mlp"])
    lr_e, lr_d = float(tr["emb_lr"]), float(tr["dense_lr"])
    steps = batches[:CHECK_STEPS]
    valid = None if steps[0].splits is None else \
        [[int(s[-1]) for s in b.splits] for b in steps]
    # room for every id of the three steps to be distinct, or the whole table
    room = CHECK_STEPS * max(len(i) for i in steps[0].ids)
    uniq, mapped, counts = reference.compact(
        [b.ids for b in steps], [min(s, room) for s in sizes], valid)
    t0 = weights.rows_fn(sizes, dim, tdt)(uniq, words)
    d0 = [(jnp.asarray(k), jnp.asarray(b)) for k, b in weights.dense_params(
        seed, int(config["num_numerical"]), config["bottom_mlp"],
        config["top_mlp"], len(sizes), dim)]
    tabs, dense = t0, d0
    losses = []
    out = {}
    for k, b in enumerate(steps):
        ids, splits, num, lab = mapped[k], b.splits, b.numerical, b.labels
        if fault == "half_batch":
            h = len(num) // 2
            num, lab = num[:h], lab[:h]
            if splits is None:
                ids = [i[:h] for i in ids]
            else:   # ids past the half's last split are dead
                splits = [s[:h + 1] for s in splits]
        loss, _, new_t, new_d = reference.sgd_step_jit(
            tabs, dense, (ids, splits, num, lab), lr_e, lr_d,
            n_bottom=n_bottom, precision=precision)
        if fault != "state_unchanged":
            tabs, dense = new_t, new_d
        losses.append(float(loss))
        if k == 0:
            out["grad1_dense"] = _leaf_norms(dense, d0, 1.0 / lr_d)
            moved = [np.asarray(a.astype(jnp.float32) - b_.astype(jnp.float32),
                                np.float64) for a, b_ in zip(tabs, t0)]
            out["grad1_tables"] = [float(np.linalg.norm(m)) / lr_e
                                   for m in moved]
            n_t = len(sizes)
            out["grad1_half_rows"] = _two_halves([
                float(np.linalg.norm(
                    moved[j % n_t][np.searchsorted(uniq[j % n_t][:counts[j % n_t]],
                                                   ids_)])) / lr_e
                for j, ids_ in enumerate(one_half_ids(b))])
    out["losses"] = losses
    out["delta3_dense"] = _leaf_norms(dense, d0)
    out["delta3_tables"] = [
        float(jnp.linalg.norm(a.astype(jnp.float32) - b_.astype(jnp.float32)))
        for a, b_ in zip(tabs, t0)]
    return out


def first_steps(built, tr: dict, step, staged, batches, seed: int):
    """Drive the compiled step through its first three batches and read what
    the reference is compared with. Returns ``(numbers, state)``; the state
    goes on into the window."""
    cfg = built.config
    lr_e, lr_d = float(tr["emb_lr"]), float(tr["dense_lr"])
    words = jnp.asarray(weights.seed_words(seed))
    watch = RowWatch(built, words)
    sizes = cfg["table_sizes"]
    d0 = weights.dense_params(seed, int(cfg["num_numerical"]),
                              cfg["bottom_mlp"], cfg["top_mlp"], len(sizes),
                              int(cfg["embedding_dim"]))
    state = built.state
    built.state = None  # the step donates it
    out = {"losses": []}
    for k in range(CHECK_STEPS):
        loss, state = step(state, *staged[k])
        out["losses"].append(float(loss))
        if k == 0:
            out["grad1_dense"] = _leaf_norms(
                program.dense_layers(state.dense_params), d0, 1.0 / lr_d)
            out["grad1_tables"] = [x / lr_e for x in
                                   watch.change_norms(state, batches[:1])]
            halves = one_half_ids(batches[0])
            n_t = len(halves) // 2
            out["grad1_half_rows"] = _two_halves([
                x / lr_e for x in
                watch.change_norms_of(state, halves[:n_t])
                + watch.change_norms_of(state, halves[n_t:])])
    out["delta3_dense"] = _leaf_norms(
        program.dense_layers(state.dense_params), d0)
    out["delta3_tables"] = watch.change_norms(state, batches[:CHECK_STEPS])
    return out, state


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
