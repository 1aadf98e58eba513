"""A training cell of family ``dlrm``: the first three steps that the
reference follows, read from the compiled step and state that the window then
drives, the reference's numbers for the same batches, and the comparison.

Training numbers (``How correct is decided``, training): the loss of each of
the first three steps, the first gradient as the optimizer got it (worked out
from the parameters after one step: ``(p0 - p1) / lr``) and the parameters'
change after the three, the last two by the worst leaf as a gap of norms.
A leaf is one MLP kernel or bias, or one embedding table. One more number
reads the first gradient over the rows that only one half of the first batch
touches.
"""

from __future__ import annotations

from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.lib.check import rel_gap, worst_leaf_gap
from benchmarks.lib.train import CHECK_STEPS

from . import program, reference, traffic, weights

OBSERVE_ROWS = 65536   # ids a table in one call of the row observer


def _leaf_norms(a_layers, b_layers, scale=1.0) -> List[float]:
    out = []
    for (ka, ba), (kb, bb) in zip(a_layers, b_layers):
        out.append(float(np.linalg.norm(np.asarray(ka, np.float64)
                                        - np.asarray(kb, np.float64))) * scale)
        out.append(float(np.linalg.norm(np.asarray(ba, np.float64)
                                        - np.asarray(bb, np.float64))) * scale)
    return out


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
        live = [traffic.live_ids(b) for b in batches]
        return self.change_norms_of(state, [
            np.unique(np.concatenate([ids[t] for ids in live]))
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
    for t, ids in enumerate(traffic.live_ids(batch)):
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


def train_numbers(prog: dict, ref: dict) -> Dict[str, float]:
    """``prog`` and ``ref`` hold ``losses`` [3], and per group (``dense``,
    ``tables``) the leaf norms ``grad1_<group>`` and ``delta3_<group>``."""
    out = {f"loss{k + 1}": rel_gap(prog["losses"][k], ref["losses"][k])
           for k in range(3)}
    # the median leaf is its group's (MLP leaves, tables): the two groups have
    # learning rates of their own, so their changes are not of one scale
    for group in ("dense", "tables"):
        g = f"grad1_{group}"
        d = f"delta3_{group}"
        # a leaf whose gradient is nought to rounding in the reference moves
        # by round-off alone: left out of the change by the reference's
        # gradient
        tiny = 1e-3 * float(np.median(ref[g]))
        out[g] = worst_leaf_gap(prog[g], ref[g], ref[g])
        out[d] = worst_leaf_gap(prog[d], ref[d], ref[d],
                                skip=[x < tiny for x in ref[g]])
    # rows that one half of the first batch touches alone (two leaves: the
    # first half's, the second half's, each over all tables): where half of a
    # batch is left out they stay put or move double, which a whole table's
    # norm hides behind its hot rows
    if "grad1_half_rows" in ref:
        out["grad1_half_rows"] = worst_leaf_gap(
            prog["grad1_half_rows"], ref["grad1_half_rows"],
            ref["grad1_half_rows"])
    return out
