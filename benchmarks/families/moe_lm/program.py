"""The system under test, built from a configuration file of family
``moe_lm``. The family's adapter half: with ``benchmarks/families/system.py``
and the other families' the only code of the benchmark that imports
``distributed_embeddings_tpu``; it takes from the program its entry points
and nothing that decides a metric or ``correct``.

The token table is one table of a ``DistributedEmbedding`` (no combiner, the
step's ``sequences * seq_len`` tokens as so many samples of one id); the
layers, the head and the loss are ``models.moe_lm``'s ``loss_fn``; the step is
``make_hybrid_train_step`` with ``SparseAdam`` and ``optax.adamw``, NaN guard
as the library sets it.
"""

from __future__ import annotations

import collections
import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import optax

try:
    from distributed_embeddings_tpu.models import moe_lm
except ImportError as e:    # a checkout from before the model: fail at once
    raise SystemExit(f"family moe_lm: this checkout's program has no "
                     f"models/moe_lm.py ({e})")
from distributed_embeddings_tpu.parallel import (  # noqa: E402
    DistributedEmbedding, SparseAdam, init_hybrid_state,
    make_hybrid_eval_step, make_hybrid_train_step)
from distributed_embeddings_tpu.utils import obs  # noqa: E402

from . import weights


@dataclasses.dataclass
class Built:
    """One configuration, built and holding its state on the device."""
    config: dict
    model: moe_lm.MoELMConfig
    de: Any
    state: Any


def model_config(config: dict) -> moe_lm.MoELMConfig:
    """The configuration file's keys as the program's model takes them."""
    return moe_lm.MoELMConfig(
        hidden_size=int(config["hidden_size"]),
        num_heads=int(config["num_attention_heads"]),
        num_kv_heads=int(config["num_key_value_heads"]),
        head_dim=int(config["head_dim"]),
        expert_width=int(config["moe_ffn_hidden_size"]),
        router_outputs=int(config["moe_router_outputs"]),
        experts_per_token=int(config["moe_num_active_primary_experts"]),
        experts_held=tuple(int(e) for e in config["experts_held"]),
        vocab_held=int(config["vocab_size"]),
        seq_len=int(config["train_sequence_length"]),
        window_layout=tuple(config["sliding_window_layout"]),
        rope_layout=tuple(config["rope_layout"]),
        window=int(config["sliding_window_size"]),
        rope_theta=float(config["rope_theta"]),
        rms_eps=float(config["rms_norm_eps"]),
        **{k: int(v) for k, v in config.get("program", {}).items()})


def optimizers(tr: dict):
    """``(dense_tx, emb_optimizer)`` of a training traffic file."""
    a = tr["adam"]
    b1, b2, eps = float(a["b1"]), float(a["b2"]), float(a["eps"])
    return (optax.adamw(float(tr["dense_lr"]), b1=b1, b2=b2, eps=eps,
                        weight_decay=float(a["weight_decay"])),
            SparseAdam(b1=b1, b2=b2, eps=eps))


def build(config: dict, tr: dict, seed: int) -> Built:
    if int(config["chips"]) != 1:
        raise SystemExit("family moe_lm runs one chip's share on one chip")
    model = model_config(config)
    # the hook runs inside the program's compiled init: the table is made on
    # the device there, and nothing but the seed's key is baked in
    de = DistributedEmbedding(
        [{"input_dim": model.vocab_held, "output_dim": model.hidden_size,
          "combiner": None,
          "embeddings_initializer":
              lambda key, shape, dtype: weights.token_table(
                  config, seed).astype(dtype)}],
        world_size=1, compute_dtype=jnp.float32, dp_input=True)
    dense_tx, emb_opt = optimizers(tr)
    state = init_hybrid_state(de, emb_opt, weights.dense_params(config, seed),
                              dense_tx, jax.random.key(0),
                              dtype=jnp.float32)
    return Built(config=config, model=model, de=de, state=state)


class CountedStep:
    """The compiled step as the window drives it, ``(state, cats, batch) ->
    (loss, state)``. The routing counts that each step hands out beside its
    loss wait in a queue and are read, and added to the program's counters,
    only once ``lag`` later steps have been dispatched: by then the window
    has waited for that step, so the read never stalls the device."""

    def __init__(self, step, lag: int):
        self.step, self.lag = step, lag
        self.pending = collections.deque()

    def __call__(self, state, *staged):
        loss, state, counts = self.step(state, *staged)
        self.pending.append(counts)
        if len(self.pending) > self.lag:
            self._count(self.pending.popleft())
        return loss, state

    @staticmethod
    def _count(counts) -> None:
        for k, v in jax.device_get(counts).items():
            obs.counter_inc(k, int(v.sum()))
        obs.counter_inc("moe_steps_counted")

    def drain(self) -> None:
        """Read every step still waiting (set-up's: it blocks)."""
        while self.pending:
            self._count(self.pending.popleft())


def train_step(built: Built, tr: dict, lag: int) -> CountedStep:
    dense_tx, emb_opt = optimizers(tr)
    return CountedStep(make_hybrid_train_step(
        built.de, moe_lm.make_loss_fn(built.model), dense_tx, emb_opt,
        lr_schedule=float(tr["emb_lr"]), with_metrics=False,
        telemetry=False, has_aux=True), lag)


def stage(built: Built, batch):
    """One ``[sequences, seq_len]`` batch on the device, as the step takes
    it: the tokens as the table's ids, and again for the labels."""
    ids = jnp.asarray(batch.reshape(-1))
    return [ids], ids


def row_observer(built: Built):
    """``observe(state, [ids], (want, mask)) -> sum of mask * (rows(ids) -
    want)**2``: the table's rows (or a moment's, where the state given holds
    that slab as its ``emb_params``) read through the program's own lookup,
    which is how the check sees them without knowing the slab's layout."""
    def fn(dp, outs, extra):
        del dp
        want, mask = extra
        d = (outs[0].astype(jnp.float32) - want) ** 2
        return jnp.sum(d * mask[:, None])
    return make_hybrid_eval_step(built.de, fn)


def first_moment(state):
    """``(dense mu tree, table mu as emb_params)`` of the step's Adam
    states."""
    mu = next(s.mu for s in state.dense_opt_state if hasattr(s, "mu"))
    return mu, {k: v[0] for k, v in state.emb_opt_state.items()}
