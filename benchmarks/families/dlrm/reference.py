"""The plain reference: MLPerf DLRM forward, loss, gradient and SGD step in
straightforward float32 ``jax.numpy``. It imports nothing of the program.

Parameters are stored as the configuration states them (tables in the table
dtype, MLPs in float32); all arithmetic is float32 at ``highest`` matmul
precision. Tables are never held whole: a caller gives the rows that its ids
touch (made by ``weights.table_rows``) and ids already mapped into them.

The layout of the interaction features (strict lower triangle of the Gram
matrix in row-major order, then the bottom MLP's output) is the upstream
example's ``dot_interact`` and not MLPerf's PyTorch order (dense first); with
random weights only a shared order can agree, and the program's is followed.

``precision`` is the control's knob: ``float32`` is the reference, and
``float8`` rounds both operands of every matmul and the gathered rows to
float8_e4m3fn first (scaled per tensor), the nearest precision below the bfloat16 the
configurations state.
"""

from __future__ import annotations

from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def _q(x, precision: str):
    """Round ``x`` to the precision; ``reduce_precision`` is a rounding that
    no compiler option removes."""
    if precision == "float32":
        return x
    if precision == "float8":
        # e4m3, scaled per tensor into the format's range, as a float8 recipe
        # that meant to work would do
        scale = 224.0 / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
        return jax.lax.reduce_precision(x * scale, 4, 3) / scale
    if precision == "bfloat16":
        return jax.lax.reduce_precision(x, 8, 7)
    raise ValueError(f"unknown precision {precision!r}")


def _mm(a, b, precision: str):
    return jnp.matmul(_q(a, precision), _q(b, precision), precision=HIGHEST)


def combine(rows: jax.Array, ids, splits, batch: int, precision: str):
    """Embedding output ``[batch, dim]`` of one feature: ``rows[ids]`` for
    one-hot ids, or the sum over each sample's ids for ``(ids, splits)``."""
    r = _q(rows.astype(jnp.float32), precision)
    if splits is None:
        return r[ids]
    n = splits[-1]
    pos = jnp.arange(ids.shape[0])
    seg = jnp.searchsorted(splits, pos, side="right") - 1
    vals = jnp.where((pos < n)[:, None], r[ids], 0.0)
    return jax.ops.segment_sum(vals, jnp.clip(seg, 0, batch - 1),
                               num_segments=batch)


def logits(dense: Sequence, embs: List[jax.Array], numerical,
           n_bottom: int, precision: str = "float32") -> jax.Array:
    """``[batch]`` logits from the embedding outputs and numerical features."""
    x = numerical.astype(jnp.float32)
    for k, b in dense[:n_bottom]:
        x = jax.nn.relu(_mm(x, k, precision) + b)
    feats = jnp.stack([x] + list(embs), axis=1)
    fq = _q(feats, precision)
    gram = jnp.einsum("bfd,bgd->bfg", fq, fq, precision=HIGHEST)
    li, lj = np.tril_indices(feats.shape[1], k=-1)
    y = jnp.concatenate([gram[:, li, lj], x], axis=1)
    top = dense[n_bottom:]
    for k, b in top[:-1]:
        y = jax.nn.relu(_mm(y, k, precision) + b)
    k, b = top[-1]
    return (_mm(y, k, precision) + b)[:, 0]


def bce(z, labels):
    y = labels.reshape(-1)
    return jnp.mean(jnp.clip(z, 0) - z * y + jnp.log1p(jnp.exp(-jnp.abs(z))))


def loss_fn(tables, dense, ids, splits, numerical, labels, n_bottom, precision):
    batch = numerical.shape[0]
    embs = [combine(t, i, None if splits is None else s, batch, precision)
            for t, i, s in zip(tables, ids, splits or [None] * len(ids))]
    return bce(logits(dense, embs, numerical, n_bottom, precision), labels)


def sgd_step(tables, dense, batch, lr_emb: float, lr_dense: float,
             n_bottom: int, precision: str = "float32"):
    """One step of plain SGD on one global batch. Returns ``(loss, gradients,
    new_tables, new_dense)``; a table is rounded to its own dtype once a step,
    after the whole update is summed in float32."""
    ids, splits, numerical, labels = batch
    loss, (gt, gd) = jax.value_and_grad(loss_fn, argnums=(0, 1))(
        [t.astype(jnp.float32) for t in tables], dense, ids, splits,
        numerical, labels, n_bottom, precision)
    new_t = [(t.astype(jnp.float32) - lr_emb * g).astype(t.dtype)
             for t, g in zip(tables, gt)]
    new_d = jax.tree.map(lambda p, g: p - lr_dense * g, dense, gd)
    return loss, (gt, gd), new_t, new_d


sgd_step_jit = jax.jit(sgd_step, static_argnames=("n_bottom", "precision"))


def forward_blocks(tables, dense, ids, numerical, n_bottom: int,
                   precision: str = "float32", block: int = 16384):
    """Serving's forward over ``n`` one-hot samples in blocks of rows, so that
    it fits beside whatever else is on the device. Returns ``[n]`` float32."""
    n = numerical.shape[0]
    out = []

    @jax.jit
    def one(tables, dense, ids, numerical):
        embs = [combine(t, i, None, numerical.shape[0], precision)
                for t, i in zip(tables, ids)]
        return logits(dense, embs, numerical, n_bottom, precision)

    for a in range(0, n, block):
        pad = max(0, a + block - n)
        idb = [np.pad(i[a:a + block], (0, pad)) for i in ids]
        nb = np.pad(numerical[a:a + block], ((0, pad), (0, 0)))
        out.append(np.asarray(one(tables, dense, idb, nb))[:block - pad])
    return np.concatenate(out)


def compact(ids_per_step: Sequence[Sequence[np.ndarray]], lengths,
            valid: Optional[Sequence[Sequence[int]]] = None):
    """Per table: the sorted distinct ids over all steps, padded with id 0 to
    ``lengths[table]`` (a shape that does not change with the seed, so one
    compiled reference serves every run), each step's ids mapped into them,
    and how many distinct ids there are before the padding.
    ``valid[step][table]`` bounds the live prefix of a padded id array
    (positions past it map to 0)."""
    n_tables = len(ids_per_step[0])
    uniq, mapped, counts = [], [[] for _ in ids_per_step], []
    for t in range(n_tables):
        live = [s[t] if valid is None else s[t][:valid[k][t]]
                for k, s in enumerate(ids_per_step)]
        u = np.unique(np.concatenate(live))
        if len(u) > lengths[t]:
            raise ValueError(f"table {t}: {len(u)} distinct ids, room for "
                             f"{lengths[t]}")
        for k, s in enumerate(ids_per_step):
            m = np.zeros(len(s[t]), np.int32)
            m[:len(live[k])] = np.searchsorted(u, live[k])
            mapped[k].append(m)
        uniq.append(np.pad(u, (0, lengths[t] - len(u))).astype(np.int32))
        counts.append(len(u))
    return uniq, mapped, counts
