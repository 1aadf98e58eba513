"""The system under test, built from a configuration file of family
``mla_lm``. The family's adapter half: with ``benchmarks/families/system.py``
and the other families' the only code of the benchmark that imports
``distributed_embeddings_tpu``; it takes from the program its entry points
and nothing that decides a metric or ``correct``.

The token table is one bfloat16 table of a ``DistributedEmbedding`` (no
combiner, a token a sample); the layers and the head are ``models.mla_lm``'s;
the runtime is ``parallel.lm_serving.SessionRuntime``, which prefills the
traffic's documents and opens the sessions on them as set-up.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

try:
    from distributed_embeddings_tpu.models import mla_lm
    from distributed_embeddings_tpu.parallel import lm_serving
except ImportError as e:    # a checkout from before the model: fail at once
    raise SystemExit(f"family mla_lm: this checkout's program has no "
                     f"models/mla_lm.py or parallel/lm_serving.py ({e})")
from distributed_embeddings_tpu.parallel import (  # noqa: E402
    DistributedEmbedding)

from . import traffic, weights  # noqa: E402


@dataclasses.dataclass
class Built:
    """One configuration, built and holding its weights on the device."""
    config: dict
    traffic: dict
    seed: int
    model: Any
    de: Any
    state: Any


def model_config(config: dict) -> "mla_lm.MLALMConfig":
    """The configuration file's keys as the program's model takes them."""
    r = config["rope_scaling"]
    return mla_lm.MLALMConfig(
        hidden_size=int(config["hidden_size"]),
        num_heads=int(config["num_attention_heads"]),
        q_lora_rank=int(config["q_lora_rank"]),
        kv_lora_rank=int(config["kv_lora_rank"]),
        qk_nope_dim=int(config["qk_nope_head_dim"]),
        qk_rope_dim=int(config["qk_rope_head_dim"]),
        v_head_dim=int(config["v_head_dim"]),
        dense_width=int(config["intermediate_size"]),
        expert_width=int(config["moe_intermediate_size"]),
        shared_width=int(config["moe_intermediate_size"])
        * int(config["n_shared_experts"]),
        num_layers=int(config["num_hidden_layers"]),
        num_dense_layers=int(config["first_k_dense_replace"]),
        router_outputs=int(config["router_outputs"]),
        experts_per_token=int(config["num_experts_per_tok"]),
        n_group=int(config["n_group"]), topk_group=int(config["topk_group"]),
        routed_scale=float(config["routed_scaling_factor"]),
        norm_topk=bool(config["norm_topk_prob"]),
        experts_held=tuple(int(e) for e in config["experts_held"]),
        vocab_held=int(config["vocab_size"]),
        rope_theta=float(config["rope_theta"]),
        rope_factor=float(r["factor"]),
        rope_original=int(r["original_max_position_embeddings"]),
        beta_fast=float(r["beta_fast"]), beta_slow=float(r["beta_slow"]),
        mscale=float(r["mscale"]), mscale_all_dim=float(r["mscale_all_dim"]),
        rms_eps=float(config["rms_norm_eps"]),
        **{k: int(v) for k, v in config.get("program", {}).items()})


def build(config: dict, tr: dict, seed: int) -> Built:
    if int(config["chips"]) != 1:
        raise SystemExit("family mla_lm runs one chip's share on one chip")
    model = model_config(config)
    # the hook runs inside the program's compiled init: the table is made on
    # the device there, and nothing but the routing seed's key is baked in
    de = DistributedEmbedding(
        [{"input_dim": model.vocab_held, "output_dim": model.hidden_size,
          "combiner": None,
          "embeddings_initializer":
              lambda key, shape, dtype: weights.token_table(
                  config, seed, dtype)}],
        world_size=1, compute_dtype=jnp.bfloat16, dp_input=True)
    state = lm_serving.LMServeState(
        emb_params=de.init(jax.random.key(0), dtype=jnp.bfloat16),
        dense_params=weights.dense_params(config, seed))
    return Built(config=config, traffic=tr, seed=seed, model=model, de=de,
                 state=state)


def serving_runtime(built: Built, serve: dict) -> "lm_serving.SessionRuntime":
    """The runtime with every session open: each document of the seed
    prefilled once and copied into its sessions (set-up)."""
    tr = built.traffic
    n = traffic.sessions(tr)
    if [int(r) for r in serve["rungs"]] != [n]:
        raise SystemExit(f"the decode batch is every session: rungs must be "
                         f"[{n}], not {serve['rungs']}")
    rt = lm_serving.SessionRuntime(
        built.de, mla_lm, built.model, built.state,
        lm_serving.SessionConfig(
            sessions=n, capacity=int(serve["capacity"]),
            prefill_chunk=int(serve["prefill_chunk"]),
            deadline_ms=float(serve["deadline_ms"]),
            max_queue=int(serve["max_queue"])))
    per = int(tr["sessions_per_document"])
    # every document first: the sessions' caches come after the prefills'
    # temporaries have gone
    docs = [rt.prefill_document(tokens) for tokens in traffic.documents(
        tr, built.model.vocab_held, built.seed)]
    for s in range(n):
        rt.open_session(s, docs[s // per])
    del docs
    return rt
