"""The sparse-expert language model (``models/moe_lm.py``) at toy widths on
the CPU, against the plain reference of the benchmark's family ``moe_lm``:
loss, every gradient leaf and three optimizer steps through the hybrid step;
the per-layer choice of window + RoPE or full + no position; the four shares
of the experts adding up to the uncut layer; dropless dispatch; the router's
input; and the step's way out for the loss's counts (``has_aux``).
"""

import ast
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.families import moe_lm as fam  # noqa: E402
from benchmarks.families.moe_lm import reference, weights, work  # noqa: E402
from benchmarks.lib.traffic import power_law_ids, rng_of  # noqa: E402
from distributed_embeddings_tpu.models import moe_lm  # noqa: E402
from distributed_embeddings_tpu.parallel import (  # noqa: E402
    DistributedEmbedding, SparseSGD, init_hybrid_state,
    make_hybrid_train_step)
from distributed_embeddings_tpu.utils import obs  # noqa: E402

# one period (full, window, window, window) at toy widths; the sequence is
# four windows long, so the window's mask bites
CONFIG = {
    "hidden_size": 32, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 8, "moe_ffn_hidden_size": 16, "moe_router_outputs": 8,
    "moe_num_active_primary_experts": 3, "experts_held": [0, 8],
    "num_hidden_layers": 4, "rope_layout": [0, 1, 1, 1],
    "sliding_window_layout": [0, 1, 1, 1], "sliding_window_size": 8,
    "rope_theta": 1500000, "rms_norm_eps": 1e-6, "vocab_size": 50,
    "train_sequence_length": 32, "chips": 1,
    "program": {"moe_chunk": 64, "loss_chunk": 32, "attn_block": 8}}
TRAFFIC = {"sequences": 2, "id_alpha": 1.05, "distinct_batches": 3,
           "emb_lr": 1e-3, "dense_lr": 3e-4,
           "adam": {"b1": 0.9, "b2": 0.95, "eps": 1e-8, "weight_decay": 0.1}}
SHARES = [(0, 2), (2, 4), (4, 6), (6, 8)]


def _cfg(**over):
    return dict(CONFIG, **over)


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def _tokens(seed=3, config=CONFIG, sequences=2):
    return power_law_ids(rng_of(seed, 1), config["vocab_size"],
                         (sequences, config["train_sequence_length"]), 1.05)


@pytest.mark.parametrize("held", [(0, 8), (2, 6)])
def test_loss_and_every_gradient_leaf_match_the_reference(held):
    config = _cfg(experts_held=list(held))
    model = fam.program.model_config(config)
    params, table = weights.dense_params(config, 5), \
        weights.token_table(config, 5)
    tokens = _tokens()
    flat = jnp.asarray(tokens.reshape(-1))
    (loss, counts), (gd, gt) = jax.jit(jax.value_and_grad(
        lambda p, t: moe_lm.forward_loss(p, t[flat], flat, model),
        argnums=(0, 1), has_aux=True))(params, table)
    want, wd, wt = reference.make_gradients(config, "float32")(
        params, table, jnp.asarray(tokens))
    assert abs(float(loss) - want) / want < 2e-3
    assert int(counts[1]) == 0      # nothing dropped
    for path, _ in weights.leaf_paths(config):
        # a router's gradient is what is left of the chosen experts' outputs
        # after their weighted mean is taken off: small, and the bfloat16
        # rounding of each output is a larger part of it. At these widths a
        # logit's spread is a sixth of the cell's, so a deeper layer's
        # near-tie flips here and there and moves every leaf before it
        limit = 0.35 if path[-1] == "router" else 0.15
        assert _rel(weights.leaf_of(gd, path), weights.leaf_of(wd, path)) \
            < limit, path
    assert _rel(gt, wt) < 0.05


def test_three_optimizer_steps_through_the_hybrid_step_match_the_reference():
    batches = fam.train_batches(CONFIG, TRAFFIC, 7)
    built = fam.build(CONFIG, TRAFFIC, 7)
    step = fam.train_step(built, TRAFFIC)
    staged = [fam.stage(built, b) for b in batches]
    prog, state = fam.first_steps(built, TRAFFIC, step, staged, batches, 7)
    ref = fam.reference_numbers(CONFIG, TRAFFIC, batches, 7)
    numbers = fam.train_numbers(prog, ref)
    assert all(numbers[f"loss{k}"] < 2e-3 for k in (1, 2, 3)), numbers
    assert numbers["grad1_dense"] < 0.05 and numbers["grad1_table"] < 0.02
    assert numbers["delta3_dense"] < 0.05 and numbers["delta3_table"] < 0.02
    assert numbers["grad1_half_rows"] < 0.02
    # Adam moved every leaf, and the step counted what it routed
    assert min(prog["delta3_dense"]) > 0 and prog["delta3_table"] > 0
    assert int(state.step) == 3
    assert obs.counters()["moe_pairs_dropped"] == 0
    # the planted fault and the control both read far above the program
    half = fam.train_numbers(fam.reference_numbers(
        CONFIG, TRAFFIC, batches, 7, fault="half_batch"), ref)
    assert half["grad1_half_rows"] > 0.5
    low = fam.train_numbers(fam.reference_numbers(
        CONFIG, TRAFFIC, batches, 7, precision=fam.CONTROL_PRECISION), ref)
    assert low["loss1"] > 5 * numbers["loss1"]


@pytest.mark.parametrize("window,rotary", [(0, 0), (1, 1), (1, 0), (0, 1)])
def test_a_layer_attends_as_its_two_layout_bits_say(window, rotary):
    config = _cfg(num_hidden_layers=1, sliding_window_layout=[window],
                  rope_layout=[rotary])
    model = fam.program.model_config(config)
    layer = dict(weights.dense_params(config, 11)["layers"][0])
    # scores of order 1, as a trained model's: at the seed's 0.02 every
    # softmax is all but flat and the rotation moves nothing
    layer["wq"], layer["wk"] = 15 * layer["wq"], 15 * layer["wk"]
    h = jax.random.normal(jax.random.key(1), (2, 32, 32), jnp.float32)
    got = moe_lm.attention(h, layer, model, 0)
    want = jnp.stack([reference._mm(reference.attention(
        h[i], layer, config, 0, "float32"), layer["wo"], "float32")
        for i in range(2)])
    assert _rel(got, want) < 0.03
    # the other three choices are another function: the mask bites at this
    # length and the rotation moves the scores
    for w, r in [(0, 0), (1, 1), (1, 0), (0, 1)]:
        if (w, r) != (window, rotary):
            other = _cfg(num_hidden_layers=1, sliding_window_layout=[w],
                         rope_layout=[r])
            wrong = jnp.stack([reference._mm(reference.attention(
                h[i], layer, other, 0, "float32"), layer["wo"], "float32")
                for i in range(2)])
            assert _rel(got, wrong) > 0.1, (w, r)


def _layer_inputs(config, seed=13):
    layer = weights.dense_params(config, seed)["layers"][1]
    g = jax.random.normal(jax.random.key(2), (64, 32), jnp.float32)
    x = 0.05 * jax.random.normal(jax.random.key(3), (64, 32), jnp.float32)
    idx, p = moe_lm.route(x, layer["router"], 3)
    return layer, g, idx, p


@pytest.mark.parametrize("share", SHARES)
def test_a_share_computes_its_own_experts_part(share):
    config = _cfg(experts_held=list(share))
    layer, g, idx, p = _layer_inputs(config)
    y, counts = moe_lm.moe_experts(g, idx, p, layer,
                                   fam.program.model_config(config))
    want = reference.experts(g, idx, p, layer, config, "float32")
    assert _rel(y, want) < 0.02
    lo, hi = share
    assert int(counts[0]) == int(np.sum((idx >= lo) & (idx < hi)))
    assert int(counts[1]) == 0


def test_the_four_shares_add_up_to_the_uncut_layer():
    """Each chip's partial ``y`` (its own experts, weights of a softmax over
    all chosen) summed over the four chips is the whole layer's ``y``."""
    whole = _cfg(experts_held=[0, 8])
    full = weights.dense_params(whole, 13)["layers"][1]
    _, g, idx, p = _layer_inputs(whole)
    total, pairs = 0.0, 0
    for lo, hi in SHARES:
        config = _cfg(experts_held=[lo, hi])
        mine = dict(full, **{n: full[n][lo:hi]
                             for n in ("gate", "up", "down")})
        y, counts = moe_lm.moe_experts(g, idx, p, mine,
                                       fam.program.model_config(config))
        total, pairs = total + y, pairs + int(counts[0])
    assert pairs == idx.size        # every chosen pair computed once
    assert _rel(total, reference.experts(g, idx, p, full, whole,
                                         "float32")) < 0.02


@pytest.mark.parametrize("routing", ["all_to_one", "zipf", "none_held"])
def test_dispatch_is_dropless(routing):
    """Every chosen pair of a held expert is computed, whatever the routing:
    all tokens to the same held experts fill three chunks of the buffer."""
    config = _cfg(experts_held=[2, 6])
    model = fam.program.model_config(config)
    layer, g, idx, p = _layer_inputs(config)
    if routing == "all_to_one":
        idx = jnp.broadcast_to(jnp.asarray([2, 3, 5], jnp.int32), idx.shape)
    elif routing == "none_held":
        idx = jnp.broadcast_to(jnp.asarray([0, 1, 7], jnp.int32), idx.shape)
    else:   # the Zipf traffic's routing in layer 0: a function of the token
        table = weights.token_table(config, 13)
        x = table[jnp.asarray(_tokens(sequences=2).reshape(-1))]
        idx, p = moe_lm.route(x, layer["router"], 3)
    y, counts = jax.jit(
        lambda g, idx, p: moe_lm.moe_experts(g, idx, p, layer, model))(
            g, idx, p)
    held = int(np.sum((np.asarray(idx) >= 2) & (np.asarray(idx) < 6)))
    assert [int(c) for c in counts[:2]] == [held, 0]
    if routing == "all_to_one":
        assert held == 64 * 3 > model.moe_chunk      # three chunks' worth
        assert [int(c) for c in counts[2:]] == [64, 3]
    want = reference.experts(g, idx, p, layer, config, "float32")
    assert float(jnp.max(jnp.abs(y - want))) < 0.02 * max(
        float(jnp.max(jnp.abs(want))), 1e-6) + 1e-6


def test_the_router_reads_the_layers_input_ahead_of_norm_and_attention(
        monkeypatch):
    """Fails if the router is given ``h`` (the normed input) or ``g`` (the
    expert layer's own input): it sees the residual stream as it arrives."""
    config = _cfg(num_hidden_layers=1, sliding_window_layout=[1],
                  rope_layout=[1])
    model = fam.program.model_config(config)
    layer = dict(weights.dense_params(config, 17)["layers"][0])
    layer["norm_in"] = 1.0 + jax.random.uniform(jax.random.key(4), (32,))
    x = 0.05 * jax.random.normal(jax.random.key(5), (2, 32, 32), jnp.float32)
    seen = []
    real = moe_lm.route
    monkeypatch.setattr(moe_lm, "route", lambda x, w, k: (
        seen.append(x), real(x, w, k))[1])
    out, _ = moe_lm.block(x, layer, model, 0)
    assert len(seen) == 1
    np.testing.assert_array_equal(np.asarray(seen[0]),
                                  np.asarray(x.reshape(64, 32)))
    h = moe_lm.rmsnorm(x, layer["norm_in"], model.rms_eps).reshape(64, 32)
    assert _rel(seen[0], h) > 0.5
    # and the whole layer is the reference's, whose router reads x
    want = jnp.stack([reference.layer_forward(x[i], layer, config, 0,
                                              "float32") for i in range(2)])
    assert _rel(out, want) < 0.02


@pytest.mark.parametrize("name", ["reference", "weights", "work"])
def test_the_reference_half_imports_nothing_of_the_program(name):
    path = os.path.join(ROOT, "benchmarks", "families", "moe_lm",
                        name + ".py")
    tree = ast.parse(open(path).read())
    for node in ast.walk(tree):
        names = []
        if isinstance(node, ast.Import):
            names = [a.name for a in node.names]
        elif isinstance(node, ast.ImportFrom):
            names = [(node.module or "")] + [a.name for a in node.names]
        for n in names:
            assert "distributed_embeddings_tpu" not in n, (name, n)
            assert n not in ("program", "train"), (name, n)


@pytest.mark.parametrize("layer", [0, 1])
def test_unmasked_pairs_are_counted_as_the_mask_leaves_them(layer):
    s, w = CONFIG["train_sequence_length"], CONFIG["sliding_window_size"]
    i, j = np.arange(s)[:, None], np.arange(s)[None, :]
    mask = j <= i
    if CONFIG["sliding_window_layout"][layer]:
        mask &= i - j < w
    assert work.unmasked_pairs(CONFIG, layer) == mask.sum()


def test_the_published_layers_flop_are_the_issues():
    from benchmarks.lib import manifest
    config = manifest.Cell("smallthinker_train_seq8192").config
    s = config["train_sequence_length"]
    per_token = work.forward_flops_per_sequence(config) / s
    assert per_token == pytest.approx(0.62e9, rel=0.02)
    assert work.attention_forward_flops(config) / s / 4 \
        == pytest.approx(47.6e6, rel=0.01)
    assert work.expected_pairs_per_token(config) == 1.5
    # 4 sequences: each of 16 held experts sees 3072 pairs a layer
    assert 4 * s * 1.5 / 16 == 3072


def test_the_routing_drawn_once_gives_the_held_experts_their_load():
    """The cell's work is what ``weights.ROUTING_SEED`` routes to the 16
    experts held: each of the four routers, read on the token's own row of
    the table (the residual stream's first part), sends a step's tokens some
    49 152 pairs. An edit of ``weights.py`` that shifts it shifts the cell."""
    from benchmarks.lib import manifest
    cell = manifest.Cell("smallthinker_train_seq8192")
    config, tr = cell.config, dict(cell.traffic, distinct_batches=1)
    tokens = fam.train_batches(config, tr, 2200000000)[0].reshape(-1)
    ids, times = np.unique(tokens, return_counts=True)
    rows = weights.token_table(config, 0)[jnp.asarray(ids)]
    lo, hi = config["experts_held"]
    expected = tokens.size * work.expected_pairs_per_token(config)
    assert expected == 49152
    held = []
    for i, (path, _) in enumerate(weights.leaf_paths(config)):
        if path[-1] != "router":
            continue
        _, idx = jax.lax.top_k(jnp.dot(rows, weights.leaf(config, 0, i),
                                       precision="highest"), 6)
        idx = np.asarray(idx)
        held.append(int((((idx >= lo) & (idx < hi)).sum(1) * times).sum()))
    assert len(held) == 4
    assert held == pytest.approx([expected] * 4, rel=0.04), held
    assert sum(held) == pytest.approx(4 * expected, rel=0.01), held


def _toy_hybrid(world, has_aux):
    de = DistributedEmbedding(
        [{"input_dim": 40, "output_dim": 8, "combiner": None}] * world,
        world_size=world, dp_input=True)
    mesh = Mesh(np.array(jax.devices()[:world]), ("data",)) \
        if world > 1 else None
    dense = {"w": jnp.ones((8, 1), jnp.float32)}
    tx = optax.sgd(0.1)
    state = init_hybrid_state(de, SparseSGD(), dense, tx, jax.random.key(0),
                              mesh=mesh)

    def loss_fn(dp, outs, batch):
        loss = jnp.mean((sum(outs).astype(jnp.float32) @ dp["w"] - batch) ** 2)
        if not has_aux:
            return loss
        return loss, {"rows_seen": jnp.asarray([outs[0].shape[0]], jnp.int32),
                      "constant": jnp.ones((1,), jnp.int32)}
    step = make_hybrid_train_step(de, loss_fn, tx, SparseSGD(), mesh=mesh,
                                  with_metrics=False, has_aux=has_aux)
    ids, target = np.arange(16, dtype=np.int32), np.ones((16, 1), np.float32)
    if mesh is not None:
        shard = NamedSharding(mesh, P("data"))
        ids, target = jax.device_put(ids, shard), jax.device_put(target, shard)
    return step, state, jnp.asarray(ids), jnp.asarray(target)


@pytest.mark.parametrize("world", [1, 2])
def test_has_aux_hands_the_losss_counts_out_of_the_step(world):
    step, state, ids, target = _toy_hybrid(world, has_aux=True)
    loss, state, aux = step(state, [ids] * world, target)
    assert set(aux) == {"rows_seen", "constant"}
    # an entry a rank, as the step's metrics are stacked
    assert np.asarray(aux["rows_seen"]).tolist() == [16 // world] * world
    assert np.asarray(aux["constant"]).tolist() == [1] * world
    plain, state2, ids, target = _toy_hybrid(world, has_aux=False)
    out = plain(state2, [ids] * world, target)
    assert len(out) == 2 and float(out[0]) == pytest.approx(float(loss))


def test_counts_are_read_only_once_the_window_has_waited_for_their_step():
    read = []

    class Step(fam.program.CountedStep):
        @staticmethod
        def _count(counts):
            read.append(counts)

    step = Step(lambda state, x: (0.0, state, x), lag=3)
    for n in range(5):
        step(None, n)
    assert read == [0, 1]       # step n is read as step n + 3 is dispatched
    step.drain()
    assert read == [0, 1, 2, 3, 4] and not step.pending


def test_counter_ratio_reads_one_rise_over_another():
    from benchmarks.lib import manifest
    ctx = {"counters": {"moe_pairs_held": 600, "moe_steps_counted": 3,
                        "moe_hottest_expert_pairs": 75},
           "config": {"moe_num_primary_experts": 16}}
    assert manifest.read_metric("moe_pairs_held_per_step", ctx) == 200.0
    assert manifest.read_metric("moe_hottest_expert_share", ctx) == 2.0
    # a program without the counters: nothing to read, nothing raised
    assert manifest.read_metric("moe_pairs_held_per_step",
                                {"counters": {}, "config": {}}) is None
    assert manifest.read_metric("moe_pairs_dropped", {"counters": {}}) is None
