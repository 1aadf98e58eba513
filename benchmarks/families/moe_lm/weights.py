"""Weights made from ``--seed`` by the benchmark, never by the program: every
leaf is ``std * normal(key(seed, leaf))`` by ``jax.random`` (threefry, the same
numbers on any backend), the key a traced argument, so one compiled program a
shape serves every seed. The tree's names are the reference's; the adapter
half hands the same tree to the program.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

STD = 0.02          # every matrix, the router's among them
# the token table's rows: normal(0, 1), torch's default for an embedding (the
# source gives no initializer_range). The residual stream is then the token's
# own row first and what the layers add second, the router's logits are of
# order 1 (0.02 * sqrt(2560)), as a trained router's are, and the routing
# follows the token. With rows of 0.02 the stream is carried by attention's
# output, which under a flat softmax is all but one vector for every query:
# every router after the first then sends most tokens to the same expert,
# and whether that expert is held here, so how much a step computes, would
# be the seed's to say.
TABLE_STD = 1.0
NORMS = ("norm_in", "norm_post")
# The router's weights and the token table are made from this, not from the
# run's seed: they say which experts a token chooses, so how many of a step's
# pairs fall to the experts held here, and a tenth of the tokens are two or
# three ids. Drawn from the seed, three seeds read 3.83, 3.72 and 3.67
# sequences/s (my chip runs, PR 36): the seed decided how many of the six
# experts of id 0 this chip holds. A deployment's routing is its model's, not
# a run's; the seed draws every other weight and every token id. 42 is, of
# the draws 36 to 59, the one whose four routers give the held experts most
# nearly their deployment load on the tokens' own rows: 48 894, 49 414,
# 47 513 and 50 807 pairs a layer for 49 152 expected.
ROUTING_SEED = 42
# The two projections that write to the residual stream (attention's output,
# the experts' down) are normal(0, STD / sqrt(2 * layers)) over the source's
# own depth, the usual scaled initialisation. At STD what the layers add is a
# third of the token's own row on the stream, a quarter of the deeper
# routers' choices follow it, and it moves with the seed and with every step
# of training: two seeds' steps held 192 k and 210 k pairs and read 1010 and
# 1018 ms (my chip runs, PR 36: every other operation to the digit), and
# within a run the count drifted as the loss fell.
OUTPUTS = ("wo", "down")


def held(config: dict) -> int:
    lo, hi = config["experts_held"]
    return int(hi) - int(lo)


def layer_shapes(config: dict) -> dict:
    h, d = int(config["hidden_size"]), int(config["head_dim"])
    nq = int(config["num_attention_heads"]) * d
    nkv = int(config["num_key_value_heads"]) * d
    f, e = int(config["moe_ffn_hidden_size"]), held(config)
    return {"router": (h, int(config["moe_router_outputs"])),
            "norm_in": (h,), "norm_post": (h,),
            "wq": (h, nq), "wk": (h, nkv), "wv": (h, nkv), "wo": (nq, h),
            "gate": (e, h, f), "up": (e, h, f), "down": (e, f, h)}


def leaf_paths(config: dict) -> list:
    """Every dense leaf as ``(path, shape)``, in a fixed order; a path is
    ``("layers", l, name)``, ``("norm_f",)`` or ``("head",)``."""
    shapes = layer_shapes(config)
    out = [(("layers", l, n), s)
           for l in range(int(config["num_hidden_layers"]))
           for n, s in shapes.items()]
    h = int(config["hidden_size"])
    return out + [(("norm_f",), (h,)),
                  (("head",), (h, int(config["vocab_size"])))]


@functools.partial(jax.jit, static_argnums=(1, 2))
def _normal(key, shape, std):
    return std * jax.random.normal(key, shape, jnp.float32)


def _key(seed: int, leaf: int):
    seed = int(seed)
    key = jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)
    return jax.random.fold_in(key, leaf)


def output_std(config: dict) -> float:
    layers = config.get("published", {}).get(
        "num_hidden_layers", config["num_hidden_layers"])
    return STD / math.sqrt(2.0 * int(layers))


def leaf(config: dict, seed: int, index: int) -> jax.Array:
    """Leaf ``index`` of :func:`leaf_paths` for the seed, float32."""
    path, shape = leaf_paths(config)[index]
    if path[-1] in NORMS or path[-1] == "norm_f":
        return jnp.ones(shape, jnp.float32)
    if path[-1] == "router":
        seed = ROUTING_SEED
    return _normal(_key(seed, index), shape,
                   output_std(config) if path[-1] in OUTPUTS else STD)


def dense_params(config: dict, seed: int) -> dict:
    layers = [{} for _ in range(int(config["num_hidden_layers"]))]
    out = {"layers": layers}
    for i, (path, _) in enumerate(leaf_paths(config)):
        if path[0] == "layers":
            layers[path[1]][path[2]] = leaf(config, seed, i)
        else:
            out[path[0]] = leaf(config, seed, i)
    return out


def leaf_of(tree: dict, path):
    for p in path:
        tree = tree[p]
    return tree


def token_table(config: dict, seed: int) -> jax.Array:
    """The token table's held rows ``[vocab_size, hidden_size]``, float32;
    the same for every seed (:data:`ROUTING_SEED`)."""
    del seed
    return _normal(_key(ROUTING_SEED, 1 << 20),
                   (int(config["vocab_size"]), int(config["hidden_size"])),
                   TABLE_STD)
