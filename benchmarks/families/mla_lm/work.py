"""Operations and bytes of each kernel's work in a serving window, from the
configuration's shapes and what the window counted, so that a roofline share
reads the same work whatever implements it. ``WORK`` is what the reader
``lm_serve_roofline`` finds by the name in a metric's file; each function
returns the work of one step. Imports nothing of the program."""

from __future__ import annotations


def _per_step(ctx: dict, key: str) -> float:
    steps = (ctx.get("stats") or {}).get("steps")
    c = (ctx.get("counters") or {}).get(key)
    if not steps or c is None:
        return 0.0
    return c / steps


def mla_decode_work(config: dict, ctx: dict):
    """The absorbed attention of every decoding slot: its context's latent
    rows (``kv_lora_rank + qk_rope_head_dim`` bfloat16 values a token and
    layer) read once, the absorbed queries read and the latent outputs
    written (float32 each, small); ``2 * heads * context * (latent +
    kv_lora_rank)`` FLOP a slot and layer. The context a step is what the
    window counted (``lm_decode_context_tokens`` over ``steps``)."""
    nh, kl = int(config["num_attention_heads"]), int(config["kv_lora_rank"])
    lat = kl + int(config["qk_rope_head_dim"])
    layers = int(config["num_hidden_layers"])
    ctx_tokens = _per_step(ctx, "lm_decode_context_tokens")
    slots = _per_step(ctx, "lm_decode_tokens")
    flops = 2.0 * nh * ctx_tokens * (lat + kl) * layers
    nbytes = (ctx_tokens * lat * 2 + slots * nh * (lat + kl) * 4) * layers
    return flops, nbytes


def mla_prefill_work(config: dict, ctx: dict):
    """The absorbed attention of the prompt chunks: each chunk's session
    rows up to its last query read once; ``2 * heads * (latent +
    kv_lora_rank)`` FLOP a query-key pair and layer
    (``lm_prefill_key_pairs``)."""
    nh, kl = int(config["num_attention_heads"]), int(config["kv_lora_rank"])
    lat = kl + int(config["qk_rope_head_dim"])
    layers = int(config["num_hidden_layers"])
    pairs = _per_step(ctx, "lm_prefill_key_pairs")
    rows = _per_step(ctx, "lm_prefill_context_tokens")
    return (2.0 * nh * pairs * (lat + kl) * layers,
            rows * lat * 2.0 * layers)


def experts_work(config: dict, ctx: dict):
    """The held experts' three products over the pairs held: every touched
    expert's bfloat16 weights read once, the pairs' rows in and out in
    bfloat16; ``3 * 2 * hidden * width`` FLOP a pair."""
    h, f = int(config["hidden_size"]), int(config["moe_intermediate_size"])
    pairs = _per_step(ctx, "moe_pairs_held")
    touched = _per_step(ctx, "moe_experts_touched")
    return (6.0 * h * f * pairs,
            touched * 3 * h * f * 2 + pairs * (2 * h + 2 * f) * 2)


WORK = {"mla_decode": mla_decode_work, "mla_prefill": mla_prefill_work,
        "lm_serve_experts": experts_work}
FLOPS: dict = {}
