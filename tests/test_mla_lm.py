"""The latent-attention expert model (``models/mla_lm.py``) at toy widths on
the CPU, against the plain reference of the benchmark's family ``mla_lm``:
a document's prefill, prefill then absorbed decode through the session
runtime's cache against the reference's full forward pass, absorbed against
decompressed attention, group-limited routing against brute force, the YaRN
frequencies and scale against their formulas, and the four shares of the
experts adding up to the uncut layer.
"""

import ast
import dataclasses
import itertools
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.families import mla_lm as fam  # noqa: E402
from benchmarks.families.mla_lm import reference, weights  # noqa: E402
from benchmarks.lib.traffic import power_law_ids, rng_of  # noqa: E402
from distributed_embeddings_tpu.models import mla_lm  # noqa: E402

# the published schema at toy widths: one dense layer, then expert layers of
# 16 router outputs in 4 groups, 4 of them held here; YaRN as published
CONFIG = {
    "hidden_size": 64, "num_attention_heads": 4, "q_lora_rank": 32,
    "kv_lora_rank": 16, "qk_nope_head_dim": 8, "qk_rope_head_dim": 8,
    "v_head_dim": 8, "intermediate_size": 96, "moe_intermediate_size": 16,
    "n_shared_experts": 1, "num_hidden_layers": 3,
    "first_k_dense_replace": 1, "router_outputs": 16, "n_group": 4,
    "topk_group": 2, "num_experts_per_tok": 4, "experts_held": [0, 4],
    "n_routed_experts": 4, "routed_scaling_factor": 2.5,
    "norm_topk_prob": True, "vocab_size": 96, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 32,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "rms_norm_eps": 1e-6, "chips": 1,
    "program": {"moe_chunk": 64, "ffn_chunk": 32, "attn_block": 32}}
SEED = 5
SHARES = [(0, 4), (4, 8), (8, 12), (12, 16)]


def _cfg(**over):
    return dict(CONFIG, **over)


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def _tokens(n, seed=3):
    return power_law_ids(rng_of(seed, 1), CONFIG["vocab_size"], (n,), 1.05)


def _program_params(config, seed=SEED):
    return weights.dense_params(config, seed)


def test_the_prefill_paths_logits_match_the_reference():
    """A document of four chunks through ``prefill_layer`` (decompressed
    attention, the expert layer a chunk of tokens at a time), then the head,
    against the reference's full forward pass."""
    config = _cfg()
    model = fam.program.model_config(config)
    tokens = _tokens(128)
    params = _program_params(config)
    x = weights.token_table(config, SEED, jnp.float32)[jnp.asarray(tokens)]
    cs = mla_lm.rope_table(model, 128)
    for l, layer in enumerate(params["layers"]):
        x, _, _ = mla_lm.prefill_layer(x, layer, model, l, cs)
    got = mla_lm._mm(mla_lm.rmsnorm(x, params["norm_f"], model.rms_eps),
                     params["head"])
    rows = np.arange(0, 128, 7)
    want = reference.forward_blocks(config, SEED, tokens[:100],
                                    [tokens[100:]], [rows[rows >= 100] - 100])
    assert _rel(got[rows[rows >= 100]], want[0]) < 2e-2


def test_absorbed_attention_equals_decompressed_at_the_same_positions():
    """The decode form over a cache of the latent rows against the prefill
    form over the decompressed keys and values: the same attention."""
    config = _cfg()
    model = fam.program.model_config(config)
    layer = _program_params(config)["layers"][1]
    t = 48
    h = jax.random.normal(jax.random.key(1), (t, model.hidden_size))
    cs = mla_lm.rope_table(model, t)
    want, c, k_pe = mla_lm.attend_decompressed(h, layer, model, cs)
    q_nope, q_pe = mla_lm._queries(h, layer, model, cs)
    o = mla_lm.attend_absorbed(q_nope[None], q_pe[None], c[None], k_pe[None],
                               jnp.arange(t)[None], layer, model)[0]
    got = mla_lm._out(o.astype(jnp.bfloat16), layer)
    assert _rel(got, want) < 2e-2


def _brute_force_route(s, ng, kg, k):
    """Every choice of ``kg`` groups; the one whose groups' two best scores
    sum highest; the ``k`` best experts inside it."""
    t, r = s.shape
    per = r // ng
    idx = np.zeros((t, k), np.int64)
    for i in range(t):
        def group_score(g):
            return np.sort(s[i, g * per:(g + 1) * per])[-2:].sum()
        best = max(itertools.combinations(range(ng), kg),
                   key=lambda gs: sum(group_score(g) for g in gs))
        allowed = [e for g in best for e in range(g * per, (g + 1) * per)]
        idx[i] = sorted(allowed, key=lambda e: -s[i, e])[:k]
    return idx


def test_group_limited_routing_matches_brute_force():
    config = _cfg(router_outputs=32, n_group=8, topk_group=4,
                  num_experts_per_tok=8, experts_held=[0, 32])
    model = fam.program.model_config(config)
    u = jax.random.normal(jax.random.key(2), (40, model.hidden_size))
    w_router = 0.2 * jax.random.normal(jax.random.key(3),
                                       (model.hidden_size, 32))
    idx, w = mla_lm.route(u, w_router, model)
    s = np.asarray(jax.nn.sigmoid(jnp.dot(
        u, w_router, precision=jax.lax.Precision.HIGHEST)), np.float64)
    want = _brute_force_route(s, 8, 4, 8)
    assert (np.sort(np.asarray(idx), 1) == np.sort(want, 1)).all()
    chosen = np.take_along_axis(s, np.asarray(idx), 1)
    np.testing.assert_allclose(
        np.asarray(w), 2.5 * chosen / chosen.sum(1, keepdims=True),
        rtol=1e-5)
    ridx, rw = reference.route(u, w_router, config)
    assert (np.sort(np.asarray(ridx), 1) == np.sort(want, 1)).all()


def test_yarn_frequencies_and_scale_are_the_formulas():
    """At the published rope_scaling: the correction dims of 32 and 1
    rotations over 4096 positions are 10 and 23 of 32, the frequencies below
    10 the original ones and above 23 the original over 32, the linear ramp
    between; the scores' scale 192 ** -0.5 * (0.1 ln 32 + 1) ** 2; the cos and
    sin scale 1."""
    config = _cfg(qk_nope_head_dim=128, qk_rope_head_dim=64)
    model = fam.program.model_config(config)
    base = 10000.0 ** (-np.arange(0, 64, 2) / 64)

    def dim(rot):
        return 64 * math.log(4096 / (rot * 2 * math.pi)) / (2 * math.log(1e4))

    assert (math.floor(dim(32)), math.ceil(dim(1))) == (10, 23)
    ramp = np.clip((np.arange(32) - 10) / 13, 0, 1)
    want = base / 32 * ramp + base * (1 - ramp)
    np.testing.assert_allclose(mla_lm.yarn_inv_freq(model), want, rtol=1e-12)
    np.testing.assert_allclose(reference.inv_freq(config), want, rtol=1e-12)
    m2 = (0.1 * math.log(32) + 1) ** 2
    assert math.isclose(mla_lm.softmax_scale(model), m2 / math.sqrt(192),
                        rel_tol=1e-12)
    assert math.isclose(reference.score_scale(config), m2 / math.sqrt(192),
                        rel_tol=1e-12)
    cs = np.asarray(mla_lm.rope_table(model, 5000))
    np.testing.assert_allclose(cs[4999, 0], np.cos(4999 * want), atol=1e-6)
    np.testing.assert_allclose(cs[4999, 1], np.sin(4999 * want), atol=1e-6)


def test_the_four_shares_add_up_to_the_uncut_layer():
    """Sixteen experts in four groups: each of four shares computes its own
    four experts' part; with the shared expert and the residual (what every
    chip computes alike) counted once, the shares add up to the reference's
    layer holding all sixteen."""
    full = _cfg(experts_held=[0, 16])
    layer = _program_params(full)["layers"][1]
    x1 = jax.random.normal(jax.random.key(4), (24, full["hidden_size"]))
    u = mla_lm.rmsnorm(x1, layer["norm_ffn"], 1e-6)
    common = x1 + mla_lm._swiglu(u, layer["shared_gate"], layer["shared_up"],
                                 layer["shared_down"])
    total = common
    for lo, hi in SHARES:
        model = fam.program.model_config(_cfg(experts_held=[lo, hi]))
        mine = dict(layer, **{k: layer[k][lo:hi] for k in ("gate", "up",
                                                          "down")})
        y, counts = mla_lm.ffn(x1, mine, model, 1)
        assert int(counts[1]) == 0      # dropless
        total = total + (y - common)
    f32 = {k: v.astype(jnp.float32) for k, v in layer.items()}
    want = reference._moe(x1, f32, full, "float32")
    assert _rel(total, want) < 1e-2


@pytest.mark.parametrize("name", ["reference", "weights", "work", "traffic"])
def test_the_reference_half_imports_nothing_of_the_program(name):
    path = os.path.join(ROOT, "benchmarks", "families", "mla_lm",
                        name + ".py")
    with open(path) as f:
        tree = ast.parse(f.read())
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom):
            assert "distributed_embeddings_tpu" not in (node.module or "")
        elif isinstance(node, ast.Import):
            assert all("distributed_embeddings_tpu" not in a.name
                       for a in node.names)


def test_the_published_configuration_keeps_every_width():
    """The cell's file: every catalog key as published but the three cut,
    which ``published`` holds; the family's router keeps all 192 outputs."""
    import json
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "ax-k1-ep16.json")) as f:
        cfg = json.load(f)
    assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts",
                              "vocab_size"]
    assert cfg["published"] == {"num_hidden_layers": 61,
                                "n_routed_experts": 192,
                                "vocab_size": 163840}
    widths = {"hidden_size": 7168, "q_lora_rank": 1536, "kv_lora_rank": 512,
              "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
              "v_head_dim": 128, "num_attention_heads": 64,
              "moe_intermediate_size": 2048, "intermediate_size": 18432,
              "num_experts_per_tok": 8, "n_group": 8, "topk_group": 4,
              "routed_scaling_factor": 2.5, "router_outputs": 192}
    assert {k: cfg[k] for k in widths} == widths
    model = fam.program.model_config(cfg)
    assert dataclasses.replace(model).num_held == 12 == cfg["n_routed_experts"]


@pytest.mark.parametrize("queries", [1, 8])
def test_the_cache_kernel_follows_its_equations_in_interpret_mode(queries):
    """``ops.latent_attention`` in Pallas's interpreter: entries reading
    session slots out of order, one idle (length 0), each query of an entry
    seeing one position more than the one before."""
    from distributed_embeddings_tpu.ops.latent_attention import (
        latent_attention)
    nh, kl, dr, cap = 4, 16, 8, 64
    k = jax.random.split(jax.random.key(7), 4)
    cache_c = jax.random.normal(k[0], (3, cap, kl))
    cache_pe = jax.random.normal(k[1], (3, cap, dr))
    slots = jnp.array([2, 0, 1], jnp.int32)
    lengths = jnp.array([5, 0, 40], jnp.int32)
    q_c = jax.random.normal(k[2], (3, queries * nh, kl))
    q_pe = jax.random.normal(k[3], (3, queries * nh, dr))
    got = np.asarray(latent_attention(
        q_c, q_pe, cache_c, cache_pe, slots, lengths, heads=nh, scale=0.3,
        block=16, rows=2 * nh if queries > 1 else nh, interpret=True))
    for b in range(3):
        for r in range(queries * nh):
            n = int(lengths[b]) + r // nh if lengths[b] else 0
            if not n:
                assert not got[b, r].any()
                continue
            c = np.asarray(cache_c[slots[b], :n])
            pe = np.asarray(cache_pe[slots[b], :n])
            s = (c @ np.asarray(q_c[b, r]) + pe @ np.asarray(q_pe[b, r])) \
                * 0.3
            p = np.exp(s - s.max())
            np.testing.assert_allclose(got[b, r], p @ c / p.sum(),
                                       rtol=2e-5, atol=2e-5)
