"""Operations and bytes that each kernel's work needs, from the cell's shapes
and the counted routing alone, so that a roofline share reads the same work
whatever implements it. A sample is a sequence of the configuration's
``train_sequence_length`` tokens. ``WORK`` and ``FLOPS`` are what the readers
``roofline`` and ``mfu`` find by the name in a metric's file. Imports nothing
of the program."""

from __future__ import annotations

import numpy as np


def _dims(config: dict):
    h, d = int(config["hidden_size"]), int(config["head_dim"])
    return (h, d, int(config["num_attention_heads"]),
            int(config["num_key_value_heads"]),
            int(config["moe_ffn_hidden_size"]),
            int(config["train_sequence_length"]))


def unmasked_pairs(config: dict, l: int) -> float:
    """Query-key pairs of one sequence that layer ``l``'s mask leaves."""
    s = int(config["train_sequence_length"])
    if not config["sliding_window_layout"][l]:
        return s * (s + 1) / 2
    w = min(int(config["sliding_window_size"]), s)
    return w * (w + 1) / 2 + (s - w) * w


def attention_forward_flops(config: dict, layers=None) -> float:
    """Scores and weighted values of one sequence, unmasked pairs only."""
    _, d, nh, _, _, _ = _dims(config)
    layers = range(int(config["num_hidden_layers"])) if layers is None \
        else layers
    return sum(2 * 2 * nh * d * unmasked_pairs(config, l) for l in layers)


def expert_pair_flops(config: dict) -> float:
    """One (token, expert) pair's three products, forward."""
    h, _, _, _, f, _ = _dims(config)
    return 3 * 2 * h * f


def expected_pairs_per_token(config: dict) -> float:
    """Pairs of held experts a token and layer under balanced routing."""
    lo, hi = config["experts_held"]
    return int(config["moe_num_active_primary_experts"]) * (hi - lo) \
        / int(config["moe_router_outputs"])


def forward_flops_per_sequence(config: dict) -> float:
    """Matmul FLOP of one sequence's forward: projections, router, unmasked
    attention pairs, the held experts at balanced routing, the head."""
    h, d, nh, nkv, _, s = _dims(config)
    layers = int(config["num_hidden_layers"])
    proj = 2 * (h * nh * d + 2 * h * nkv * d + nh * d * h) \
        + 2 * h * int(config["moe_router_outputs"])
    per_token = layers * (proj + expected_pairs_per_token(config)
                          * expert_pair_flops(config)) \
        + 2 * h * int(config["vocab_size"])
    return float(s * per_token + attention_forward_flops(config))


def train_flops_per_sequence(config: dict) -> float:
    """Forward, input gradient and weight gradient; nothing recomputed."""
    return 3.0 * forward_flops_per_sequence(config)


def pairs_held_per_step(config: dict, w: dict, ctx: dict) -> float:
    """The (token, expert) pairs of held experts a step, all layers: what
    the window counted, else balanced routing's."""
    c = ctx.get("counters") or {}
    if c.get("moe_steps_counted"):
        return c["moe_pairs_held"] / c["moe_steps_counted"]
    return w["tokens_per_step"] * int(config["num_hidden_layers"]) \
        * expected_pairs_per_token(config)


def experts_work(config: dict, w: dict, ctx: dict):
    """The three grouped products over the pairs held, forward and backward
    (x 3); bytes: the held experts' bfloat16 weights read in each of the
    three passes and their float32 gradient written, the dispatched rows in
    and out in bfloat16."""
    h, _, _, _, f, _ = _dims(config)
    lo, hi = config["experts_held"]
    pairs = pairs_held_per_step(config, w, ctx)
    weights = int(config["num_hidden_layers"]) * (hi - lo) * 3 * h * f
    return (3.0 * pairs * expert_pair_flops(config),
            weights * (3 * 2 + 4) + 3.0 * pairs * (2 * h + 2 * f) * 2)


def attention_work(config: dict, w: dict, ctx: dict):
    """2 matmuls forward and 4 backward over the unmasked pairs."""
    return (3.0 * attention_forward_flops(config) * w["sequences_per_step"],
            0.0)


def lookup_bytes(config: dict, w: dict, ctx: dict):
    """A float32 table row read and a float32 output row written an id."""
    return 0.0, 2.0 * w["tokens_per_step"] * int(config["hidden_size"]) * 4


def apply_bytes(config: dict, w: dict, ctx: dict):
    """Lazy Adam: each distinct touched row's weight and two moments read and
    written once in float32, a float32 gradient row streamed in an id."""
    h = int(config["hidden_size"])
    return 0.0, h * 4 * (6.0 * w["distinct_rows_per_step"]
                         + w["tokens_per_step"])


def step_work(config: dict, traffic: dict, batches) -> dict:
    return {"tokens_per_step": float(batches[0].size),
            "sequences_per_step": float(batches[0].shape[0]),
            "distinct_rows_per_step": float(np.mean(
                [len(np.unique(b)) for b in batches]))}


WORK = {
    "lookup": lookup_bytes,
    "apply": apply_bytes,
    "dense_train": lambda cfg, w, ctx: (
        train_flops_per_sequence(cfg) * ctx["samples"] / ctx["steps"], 0.0),
    "attention": attention_work,
    "moe_experts": experts_work,
}
FLOPS = {"dense_train": train_flops_per_sequence}
