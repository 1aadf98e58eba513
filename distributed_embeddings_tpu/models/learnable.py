"""A planted-signal recommender task that DLRM can provably learn.

The reference publishes trained quality (AUC 0.80248/0.80262 on Criteo,
``examples/dlrm/README.md:7-8``) as its end-to-end evidence that the stack
learns. Criteo itself is not bundled here, so this module plants a
DLRM-shaped signal in synthetic data instead:

* every categorical id carries a hidden scalar preference
  ``s_f[id] ~ N(0, 1)``;
* the click logit mixes PAIRWISE interactions — exactly what DLRM's
  dot-interaction models (``models/dlrm.py:dot_interact``; reference
  ``examples/dlrm/utils.py:92-113``) — with a linear numerical term:
  ``logit = scale * (sum over pairs (2k, 2k+1) of s[2k][i]*s[2k+1][j])
  + w . x_num + bias``;
* labels draw ``Bernoulli(sigmoid(logit))``.

A model that learns nothing scores AUC 0.5 on held-out draws; the Bayes
ceiling is well above 0.8 for the default scale. Used by the slow
convergence test (``tests/test_convergence.py``).
"""

from __future__ import annotations

from typing import List, Sequence, Tuple

import numpy as np


class LearnableClicks:
    """Planted-signal synthetic CTR task.

    Args:
      table_sizes: vocab per categorical feature (pairs ``(2k, 2k+1)``
        interact; an odd trailing feature is noise).
      num_numerical: dense feature count (linear signal).
      seed: ground-truth seed (fixed per task instance).
      scale: interaction strength; higher = more separable.
    """

    def __init__(self, table_sizes: Sequence[int], num_numerical: int = 13,
                 seed: int = 0, scale: float = 1.0):
        self.table_sizes = [int(s) for s in table_sizes]
        self.num_numerical = int(num_numerical)
        self.scale = float(scale)
        rng = np.random.default_rng(seed)
        self._scores = [rng.normal(size=s).astype(np.float32)
                        for s in self.table_sizes]
        self._wnum = rng.normal(size=num_numerical).astype(np.float32) * 0.3
        self._bias = 0.0

    def sample(self, rng: np.random.Generator, batch: int
               ) -> Tuple[np.ndarray, List[np.ndarray], np.ndarray]:
        """One batch ``(numerical [B,F] f32, cats list of [B] i32,
        labels [B,1] f32)``."""
        cats = [rng.integers(0, s, size=batch).astype(np.int32)
                for s in self.table_sizes]
        num = rng.normal(size=(batch, self.num_numerical)).astype(np.float32)
        logit = num @ self._wnum + self._bias
        for k in range(0, len(cats) - 1, 2):
            logit = logit + self.scale * (
                self._scores[k][cats[k]] * self._scores[k + 1][cats[k + 1]])
        p = 1.0 / (1.0 + np.exp(-logit))
        labels = (rng.random(batch) < p).astype(np.float32)[:, None]
        return num, cats, labels


def train_dlrm_convergence(task: LearnableClicks, *, world_size: int = 1,
                           mesh=None, steps: int = 360, batch: int = 8192,
                           embedding_dim: int = 16, lr_schedule=0.01,
                           param_dtype=None, eval_n: int = 16384,
                           seed: int = 0, optimizer: str = "adam",
                           dense_lr=None, emb_init_scale=None):
    """Train DLRM on ``task`` through the FULL hybrid path and return
    ``(auc_start, auc_mid, auc_end)`` on a held-out draw.

    The convergence driver of the slow tests (one device or the
    8-device CPU mesh) — sparse embedding optimizer, optax
    dense side, eval via :func:`~..parallel.make_hybrid_eval_step` +
    exact AUC.

    ``optimizer="adam"`` (default): :class:`~..parallel.SparseAdam` +
    ``optax.adam`` — the historical capture. ``optimizer="sgd"``:
    :class:`~..parallel.SparseSGD` + ``optax.sgd``, the reference's
    flagship recipe (its DLRM trains with plain SGD lr=24 to AUC
    0.80248) and the ROADMAP 1 diagnostic subject: under the default
    DLRM table init (uniform ``±1/sqrt(vocab)`` ≈ ±0.022 at vocab 2000)
    the pairwise-product signal puts SGD at a saddle — gradients w.r.t.
    one table's rows are proportional to the OTHER table's tiny rows, so
    escape is multiplicative with rate ~ ``lr * |e|^2`` and lr=0.01
    learns only the linear numerical part (AUC ~0.636). Raising the
    embedding lr toward the reference's recipe (or the init scale via
    ``emb_init_scale``, which multiplies the default initializer)
    restores convergence; see ``docs/perf_tpu.md`` Round 9 for the
    measured (lr, init) matrix.

    ``dense_lr`` decouples the dense side's lr when the embedding lr is
    cranked SGD-style (defaults to ``lr_schedule``)."""
    import jax
    import jax.numpy as jnp
    import optax

    from ..parallel import (DistributedEmbedding, SparseAdam, SparseSGD,
                            init_hybrid_state, make_hybrid_eval_step,
                            make_hybrid_train_step)
    from ..utils import binary_auc
    from .dlrm import DLRMConfig, DLRMDense, bce_with_logits

    cfg = DLRMConfig(table_sizes=task.table_sizes,
                     embedding_dim=embedding_dim,
                     num_numerical_features=task.num_numerical,
                     bottom_mlp_dims=[2 * embedding_dim, embedding_dim],
                     top_mlp_dims=[64, 32, 1])
    emb_configs = cfg.embedding_configs()
    if emb_init_scale is not None:
        def scaled(base, s=float(emb_init_scale)):
            return lambda key, shape, dtype=jnp.float32: (
                s * base(key, shape, dtype))
        for c in emb_configs:
            c["embeddings_initializer"] = scaled(
                c["embeddings_initializer"])
    de = DistributedEmbedding(emb_configs,
                              world_size=world_size,
                              strategy="memory_balanced")
    dense = DLRMDense(cfg)
    dp = dense.init(
        jax.random.key(seed),
        jnp.zeros((2, task.num_numerical), jnp.float32),
        [jnp.zeros((2, embedding_dim), jnp.float32)
         for _ in task.table_sizes])
    if dense_lr is None:
        dense_lr = lr_schedule
    if optimizer == "adam":
        tx = optax.adam(dense_lr)
        emb_opt = SparseAdam()
    elif optimizer == "sgd":
        tx = optax.sgd(dense_lr)
        emb_opt = SparseSGD()
    elif optimizer == "mixed":
        # dense Adam + embedding SparseSGD: isolates whether the SPARSE
        # path learns under plain SGD when the dense half is not the
        # bottleneck — the ROADMAP 1 control that separates "sparse-path
        # defect" from "task conditioning starves the whole model"
        tx = optax.adam(dense_lr)
        emb_opt = SparseSGD()
    else:
        raise ValueError(f"optimizer must be 'adam' | 'sgd' | 'mixed', "
                         f"got {optimizer!r}")

    def loss_fn(d, outs, batch_):
        num, y = batch_
        return bce_with_logits(dense.apply(d, num, outs), y)

    state = init_hybrid_state(
        de, emb_opt, dp, tx, jax.random.key(seed + 1), mesh=mesh,
        **({"dtype": param_dtype} if param_dtype is not None else {}))
    # convergence probe, not a training loop: keep the 2-tuple step
    # contract even when the environment sets DETPU_OBS=1
    step = make_hybrid_train_step(de, loss_fn, tx, emb_opt, mesh=mesh,
                                  lr_schedule=lr_schedule,
                                  with_metrics=False)
    eval_fn = make_hybrid_eval_step(
        de, lambda d, outs, num: jax.nn.sigmoid(dense.apply(d, num, outs)),
        mesh=mesh)

    def put(x):
        if mesh is None:
            return jnp.asarray(x)
        from jax.sharding import NamedSharding, PartitionSpec as P
        return jax.device_put(jnp.asarray(x),
                              NamedSharding(mesh, P(de.axis_name)))

    ev_num, ev_cats, ev_y = task.sample(np.random.default_rng(999), eval_n)
    ev_num = put(ev_num)
    ev_cats = [put(c) for c in ev_cats]

    def auc(st):
        return float(binary_auc(ev_y, np.asarray(eval_fn(st, ev_cats,
                                                         ev_num))))

    auc0 = auc(state)
    rng = np.random.default_rng(seed + 7)
    mid = None
    for i in range(steps):
        num, cats, y = task.sample(rng, batch)
        _, state = step(state, [put(c) for c in cats],
                        (put(num), put(y)))
        if i == steps // 3:
            mid = auc(state)
    return auc0, mid, auc(state)
