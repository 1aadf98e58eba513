"""Weights made from ``--seed`` by the benchmark, never by the program: every
matrix is ``std * normal(key(seed, leaf))`` by ``jax.random`` (threefry, the
same numbers on any backend) rounded to bfloat16, the dtype the configuration
serves in; the router's weights stay float32 (its scores are float32); norms
are ones. The program gets the bfloat16 leaves, the reference the same values
in float32, a layer at a time. Imports nothing of the program.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

STD = 0.02
# The token table's rows are normal(0, 1) (torch's default for an embedding;
# the source gives no initializer_range), as family moe_lm found: the
# residual stream is then the token's own row first and what the layers add
# second, and the routing follows the token rather than attention's average.
TABLE_STD = 1.0
# The router and the token table are drawn from this, not from the run's seed
# (family moe_lm's reason): they decide which experts a token chooses, so how
# many of the held experts a step touches; the seed draws every other weight
# and every token id.
ROUTING_SEED = 42
# Attention as sharp as a trained model's: the query path (W_uq) and the
# key half of W_ukv are drawn wider, so that a score has a standard deviation
# of some 5.7 (its nope part 0.06 * sqrt(1536) * 0.05 * sqrt(512) *
# sqrt(128) * 0.131 = 3.9, its rope part 0.06 * sqrt(1536) * 0.02 *
# sqrt(7168) * sqrt(64) * 0.131 = 4.2): a query attends to a few of the 32 k
# keys, and what attention adds to the stream is the value of those. At 0.02
# throughout a score's deviation is 0.5, attention over 32 k keys is all but
# uniform, its output all but the same for every query, and nothing about it
# (the scale m ** 2, a key left out) would show in the logits.
Q_STD = 0.06        # W_uq
K_STD = 0.05        # the key half of W_ukv
# Every projection that writes to the residual stream stays normal(0, 0.02),
# unscaled by depth: each of the five layers then adds to the stream about
# what the token's own row holds, and the logits depend on every layer.
NORMS = ("norm_attn", "norm_q", "norm_kv", "norm_ffn")
FLOAT32 = ("router",)


def held(config: dict) -> int:
    lo, hi = config["experts_held"]
    return int(hi) - int(lo)


def num_dense(config: dict) -> int:
    return int(config["first_k_dense_replace"])


def layer_shapes(config: dict, l: int) -> dict:
    h, nh = int(config["hidden_size"]), int(config["num_attention_heads"])
    ql, kl = int(config["q_lora_rank"]), int(config["kv_lora_rank"])
    dn, dr = int(config["qk_nope_head_dim"]), int(config["qk_rope_head_dim"])
    dv = int(config["v_head_dim"])
    out = {"norm_attn": (h,), "wq_a": (h, ql), "norm_q": (ql,),
           "wq_b": (ql, nh * (dn + dr)), "wkv_a": (h, kl + dr),
           "norm_kv": (kl,), "wkv_b": (kl, nh * (dn + dv)),
           "wo": (nh * dv, h), "norm_ffn": (h,)}
    if l < num_dense(config):
        f = int(config["intermediate_size"])
        out.update(ffn_gate=(h, f), ffn_up=(h, f), ffn_down=(f, h))
    else:
        f, e = int(config["moe_intermediate_size"]), held(config)
        fs = f * int(config["n_shared_experts"])
        out.update(router=(h, int(config["router_outputs"])),
                   shared_gate=(h, fs), shared_up=(h, fs),
                   shared_down=(fs, h), gate=(e, h, f), up=(e, h, f),
                   down=(e, f, h))
    return out


def leaf_paths(config: dict) -> list:
    """Every dense leaf as ``(path, shape)``, in a fixed order; a path is
    ``("layers", l, name)``, ``("norm_f",)`` or ``("head",)``."""
    out = [(("layers", l, n), s)
           for l in range(int(config["num_hidden_layers"]))
           for n, s in layer_shapes(config, l).items()]
    h = int(config["hidden_size"])
    return out + [(("norm_f",), (h,)),
                  (("head",), (h, int(config["vocab_size"])))]


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _normal(key, shape, std, dtype):
    return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def _key(seed: int, leaf: int):
    seed = int(seed)
    key = jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)
    return jax.random.fold_in(key, leaf)


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _normal_keys_wider(key, shape, split, dtype):
    """``W_ukv [kl, heads * (dn + dv)]``: each head's first ``split``
    columns (its keys) normal(0, K_STD), the others (its values) normal(0,
    STD)."""
    w = jax.random.normal(key, shape, jnp.float32)
    heads = w.reshape(shape[0], -1, split[0] + split[1])
    std = jnp.concatenate([jnp.full((split[0],), K_STD),
                           jnp.full((split[1],), STD)])
    return (heads * std).reshape(shape).astype(dtype)


def leaf(config: dict, seed: int, index: int, dtype=None) -> jax.Array:
    """Leaf ``index`` of :func:`leaf_paths` for the seed: bfloat16 (the
    router and the norms float32), or its values in ``dtype``."""
    path, shape = leaf_paths(config)[index]
    name = path[-1]
    if name in NORMS or name == "norm_f":
        return jnp.ones(shape, dtype or jnp.float32)
    own = jnp.float32 if name in FLOAT32 else jnp.bfloat16
    if name == "router":
        seed = ROUTING_SEED
    if name == "wkv_b":
        v = _normal_keys_wider(_key(seed, index), tuple(shape),
                               (int(config["qk_nope_head_dim"]),
                                int(config["v_head_dim"])), own)
    else:
        v = _normal(_key(seed, index), tuple(shape),
                    Q_STD if name == "wq_b" else STD, own)
    return v if dtype is None else v.astype(dtype)


def dense_params(config: dict, seed: int) -> dict:
    """The program's tree (``{"layers": [...], "norm_f", "head"}``)."""
    layers = [{} for _ in range(int(config["num_hidden_layers"]))]
    out = {"layers": layers}
    for i, (path, _) in enumerate(leaf_paths(config)):
        if path[0] == "layers":
            layers[path[1]][path[2]] = leaf(config, seed, i)
        else:
            out[path[0]] = leaf(config, seed, i)
    return out


def layer(config: dict, seed: int, l: int) -> dict:
    """Layer ``l``'s leaves in float32, for the reference."""
    return {path[2]: leaf(config, seed, i, jnp.float32)
            for i, (path, _) in enumerate(leaf_paths(config))
            if path[0] == "layers" and path[1] == l}


def top(config: dict, seed: int, name: str) -> jax.Array:
    """``norm_f`` or ``head`` in float32, for the reference."""
    i = next(i for i, (p, _) in enumerate(leaf_paths(config))
             if p == (name,))
    return leaf(config, seed, i, jnp.float32)


def token_table(config: dict, seed: int, dtype=jnp.bfloat16) -> jax.Array:
    """The token table's held rows ``[vocab_size, hidden_size]``: bfloat16
    values, the same for every seed (:data:`ROUTING_SEED`)."""
    del seed
    v = _normal(_key(ROUTING_SEED, 1 << 20),
                (int(config["vocab_size"]), int(config["hidden_size"])),
                TABLE_STD, jnp.bfloat16)
    return v.astype(dtype)
