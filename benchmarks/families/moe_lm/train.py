"""A training cell of family ``moe_lm``: the first three steps that the
reference follows, read from the compiled step and state that the window then
drives, the reference's numbers for the same batches, and the comparison.

The numbers: the loss of each of the three steps; the first gradient as the
optimizers got it, read from Adam's first moment after step 1
(``mu / (1 - b1)``: Adam's update itself is all but the gradient's sign), the
dense leaves by the worst leaf as a gap of norms, the token table over the
rows the batch touched, through the program's own lookup; the change of the
weights after the three steps, likewise; and the first gradient over the
table rows that only one half of the first batch's sequences touches, which
is what half a batch left out moves.
"""

from __future__ import annotations

from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.lib.check import rel_gap, worst_leaf_gap
from benchmarks.lib.train import CHECK_STEPS

from . import program, reference, weights


def one_half_ids(batch: np.ndarray) -> List[np.ndarray]:
    """The ids that only the first half of the batch's sequences touches,
    and those that only the second half touches."""
    h = batch.shape[0] // 2
    a, b = np.unique(batch[:h]), np.unique(batch[h:])
    return [np.setdiff1d(a, b, assume_unique=True),
            np.setdiff1d(b, a, assume_unique=True)]


def _norm(x) -> float:
    return float(jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))))


class RowWatch:
    """Norms over table rows, read from the program's state through its own
    lookup, a step's worth of ids a call."""

    def __init__(self, built: program.Built, n: int):
        self.observe = program.row_observer(built)
        self.n, self.width = n, built.model.hidden_size

    def norm(self, state, ids: np.ndarray, rows_of=None) -> float:
        """The norm of ``state's rows - rows_of(ids)`` (of the rows
        themselves without ``rows_of``) over the distinct ``ids``."""
        total = 0.0
        for a in range(0, len(ids), self.n):
            part = ids[a:a + self.n]
            padded = np.zeros(self.n, np.int32)
            padded[:len(part)] = part
            mask = (np.arange(self.n) < len(part)).astype(np.float32)
            dev = jnp.asarray(padded)
            want = jnp.zeros((self.n, self.width), jnp.float32) \
                if rows_of is None else rows_of(dev)
            total += float(self.observe(state, [dev],
                                        (want, jnp.asarray(mask))))
        return float(np.sqrt(total))


def first_steps(built, tr: dict, step, staged, batches, seed: int):
    """Drive the compiled step through its first three batches and read what
    the reference is compared with. Returns ``(numbers, state)``; the state
    goes on into the window."""
    cfg = built.config
    b1 = float(tr["adam"]["b1"])
    watch = RowWatch(built, batches[0].size)
    paths = weights.leaf_paths(cfg)
    state, built.state = built.state, None    # the step donates it
    out = {"losses": []}
    for k in range(CHECK_STEPS):
        loss, state = step(state, *staged[k])
        out["losses"].append(float(loss))
        if k == 0:
            mu, table_mu = program.first_moment(state)
            out["grad1_dense"] = [
                _norm(weights.leaf_of(mu, p)) / (1 - b1) for p, _ in paths]
            as_rows = state._replace(emb_params=table_mu)
            out["grad1_table"] = watch.norm(
                as_rows, np.unique(batches[0])) / (1 - b1)
            out["grad1_half_rows"] = [
                watch.norm(as_rows, ids) / (1 - b1)
                for ids in one_half_ids(batches[0])]
    out["delta3_dense"] = [
        _norm(weights.leaf_of(state.dense_params, p)
              - weights.leaf(cfg, seed, i)) for i, (p, _) in enumerate(paths)]
    # the table an argument: closed over, it would be a constant of the
    # compiled program
    table = weights.token_table(cfg, seed)
    rows = jax.jit(lambda table, ids: table[ids])
    out["delta3_table"] = watch.norm(
        state, np.unique(np.stack(batches[:CHECK_STEPS])),
        rows_of=lambda ids: rows(table, ids))
    if hasattr(step, "drain"):
        step.drain()
    return out, state


def reference_numbers(config: dict, tr: dict, batches, seed: int,
                      precision="float32", fault=None) -> dict:
    """The plain reference over the first three batches, from weights it
    makes itself from the seed."""
    a = tr["adam"]
    adam = dict(b1=float(a["b1"]), b2=float(a["b2"]), eps=float(a["eps"]))
    gradients = reference.make_gradients(config, precision)
    paths = weights.leaf_paths(config)
    params = weights.dense_params(config, seed)
    table = weights.token_table(config, seed)
    # the dense leaves' moments wait on the host between steps and come to
    # the device a leaf at a time: beside the weights and their gradient the
    # cell's size leaves no room for them
    moments = [None] * len(paths)
    tmu, tnu = jnp.zeros_like(table), jnp.zeros_like(table)
    out = {"losses": []}
    for k, batch in enumerate(batches[:CHECK_STEPS]):
        seen = batch[:batch.shape[0] // 2] if fault == "half_batch" else batch
        loss, gd, gt = gradients(params, table, jnp.asarray(seen))
        out["losses"].append(loss)
        if k == 0:
            out["grad1_dense"] = [_norm(weights.leaf_of(gd, p))
                                  for p, _ in paths]
            out["grad1_table"] = _norm(gt)
            out["grad1_half_rows"] = [_norm(gt[jnp.asarray(ids)])
                                      for ids in one_half_ids(batch)]
        if fault == "state_unchanged":
            continue
        touched = jnp.zeros(table.shape[0], bool).at[
            jnp.asarray(seen.reshape(-1))].set(True)
        t = float(k + 1)
        for i, (path, _) in enumerate(paths):
            *where, name = path
            holder = weights.leaf_of(params, where)
            m, v = (jnp.asarray(a) for a in moments[i]) if moments[i] else \
                (jnp.zeros_like(holder[name]), jnp.zeros_like(holder[name]))
            holder[name], m, v = reference.adamw(
                holder[name], m, v,
                weights.leaf_of(gd, path), t, lr=float(tr["dense_lr"]),
                wd=float(a["weight_decay"]), **adam)
            moments[i] = np.asarray(m), np.asarray(v)
        table, tmu, tnu = reference.lazy_adam(
            table, tmu, tnu, gt, touched, t, lr=float(tr["emb_lr"]), **adam)
        del gd, gt
    del moments, tmu, tnu
    out["delta3_dense"] = [
        _norm(weights.leaf_of(params, p) - weights.leaf(config, seed, i))
        for i, (p, _) in enumerate(paths)]
    out["delta3_table"] = _norm(table - weights.token_table(config, seed))
    return out


def train_numbers(prog: dict, ref: dict) -> Dict[str, float]:
    out = {f"loss{k + 1}": rel_gap(prog["losses"][k], ref["losses"][k])
           for k in range(CHECK_STEPS)}
    for name in ("grad1_dense", "delta3_dense", "grad1_half_rows"):
        out[name] = worst_leaf_gap(prog[name], ref[name], ref[name])
    for name in ("grad1_table", "delta3_table"):
        out[name] = rel_gap(prog[name], ref[name])
    return out
