"""The plain reference: the layer equations of the configuration's source in
straightforward float32 ``jax.numpy`` at ``highest`` matmul precision, with
its own AdamW and its own lazy Adam for the token table. It imports nothing of
the program.

For ``x [S, H]`` of one sequence, layer ``l``:

1. router: ``r = x @ W_r`` in float32 from the layer's input AS IT ARRIVES
   (ahead of attention and of its norm); the ``k`` largest logits; ``p`` a
   softmax over those. ``h = rmsnorm(x, w_in)``.
2. ``q, k, v = h @ W_q, h @ W_k, h @ W_v``, scale ``1/sqrt(D)``, causal.
   Where ``sliding_window_layout[l]`` is 1: rotate-half RoPE on ``q`` and
   ``k`` and query ``i`` sees key ``j`` iff ``0 <= i - j < window``; where 0:
   no position encoding and every ``j <= i``. ``x1 = x + attn @ W_o``.
3. ``g = rmsnorm(x1, w_post)``; ``y = sum over the chosen experts e HELD HERE
   of p_e * (relu(g @ W_gate_e) * (g @ W_up_e)) @ W_down_e``; ``x2 = x1 + y``.
4. after the last layer ``rmsnorm``, logits ``@ W_head``, mean next-token
   cross-entropy over the vocabulary held.

Masks are dense, a block of queries at a time (``[block, S]``, the whole
``[S, S]`` where a sequence is one block), every held expert is computed for
every token, the logits exist a block of positions at a time, and the
gradient is accumulated a sequence at a time (the loss is a mean over tokens
and routing is per token, so that is exact). A layer and a block of queries are
recomputed in the backward so that the cell's size fits beside the state.

``precision`` is the control's knob: ``float8`` rounds both operands of every
matmul (the router's too) and the table's rows to float8_e4m3fn, scaled per
tensor, the nearest precision below the bfloat16 the configuration states.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 1024      # queries whose scores exist at a time
HEAD_BLOCK = 1024   # positions whose logits exist at a time


def _q(x, precision: str):
    if precision == "float32":
        return x
    if precision == "float8":
        scale = 224.0 / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
        return jax.lax.reduce_precision(x * scale, 4, 3) / scale
    if precision == "bfloat16":
        return jax.lax.reduce_precision(x, 8, 7)
    raise ValueError(f"unknown precision {precision!r}")


def _mm(a, b, precision: str):
    return jnp.matmul(_q(a, precision), _q(b, precision), precision=HIGHEST)


def rmsnorm(x, w, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope(x, theta: float):
    """``x [n, S, D]``: rotate-half over ``D``."""
    s, d = x.shape[-2], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], axis=-1)
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], axis=-1)
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * cos + rot * sin


def attention(h, layer: dict, config: dict, l: int, precision: str):
    s = h.shape[0]
    d = int(config["head_dim"])
    nh, nkv = int(config["num_attention_heads"]), \
        int(config["num_key_value_heads"])
    heads = lambda x, n: x.reshape(s, n, d).transpose(1, 0, 2)  # noqa: E731
    q = heads(_mm(h, layer["wq"], precision), nh)
    k = heads(_mm(h, layer["wk"], precision), nkv)
    v = heads(_mm(h, layer["wv"], precision), nkv)
    if config["rope_layout"][l]:
        theta = float(config["rope_theta"])
        q, k = rope(q, theta), rope(k, theta)
    k = jnp.repeat(k, nh // nkv, axis=0)    # each query head's key-value head
    v = jnp.repeat(v, nh // nkv, axis=0)
    window = int(config["sliding_window_size"]) \
        if config["sliding_window_layout"][l] else None

    @jax.checkpoint
    def block(qb, i0):
        sc = jnp.einsum("hqd,hsd->hqs", _q(qb, precision), _q(k, precision),
                        precision=HIGHEST) / math.sqrt(d)
        i = i0 + jnp.arange(qb.shape[1])[:, None]
        j = jnp.arange(s)[None, :]
        mask = j <= i
        if window is not None:
            mask = mask & (i - j < window)
        p = jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), axis=-1)
        return jnp.einsum("hqs,hsd->hqd", _q(p, precision), _q(v, precision),
                          precision=HIGHEST)

    qb = min(Q_BLOCK, s)
    blocks = q.reshape(nh, s // qb, qb, d).transpose(1, 0, 2, 3)
    out = jax.lax.map(lambda a: block(*a),
                      (blocks, jnp.arange(0, s, qb)))      # [n, nh, qb, d]
    return out.transpose(0, 2, 1, 3).reshape(s, nh * d)


def experts(g, idx, p, layer: dict, config: dict, precision: str):
    """The held experts' part of the sum: every held expert over every token
    (one batched product an operand), a token that did not choose the expert
    weighing 0."""
    lo, hi = (int(e) for e in config["experts_held"])
    held = jnp.arange(lo, hi)
    # [T, held]: the weight of expert e for token t, 0 where not chosen
    w = jnp.sum(jnp.where(idx[:, :, None] == held[None, None, :],
                          p[:, :, None], 0.0), axis=1)
    gq = _q(g, precision)
    a = jax.nn.relu(jnp.einsum("th,ehf->etf", gq, _q(layer["gate"], precision),
                               precision=HIGHEST)) \
        * jnp.einsum("th,ehf->etf", gq, _q(layer["up"], precision),
                     precision=HIGHEST)
    out = jnp.einsum("etf,efh->eth", _q(a, precision),
                     _q(layer["down"], precision), precision=HIGHEST)
    return jnp.einsum("eth,te->th", out, w, precision=HIGHEST)


def route(x, layer: dict, config: dict, precision: str):
    logits = _mm(x, layer["router"], precision)
    top, idx = jax.lax.top_k(logits,
                             int(config["moe_num_active_primary_experts"]))
    return idx, jax.nn.softmax(top, axis=-1)


def layer_forward(x, layer: dict, config: dict, l: int, precision: str):
    eps = float(config["rms_norm_eps"])
    idx, p = route(x, layer, config, precision)
    h = rmsnorm(x, layer["norm_in"], eps)
    x1 = x + _mm(attention(h, layer, config, l, precision), layer["wo"],
                 precision)
    g = rmsnorm(x1, layer["norm_post"], eps)
    return x1 + experts(g, idx, p, layer, config, precision)


def sequence_nll(params: dict, table, tokens, config: dict, precision: str):
    """Summed next-token cross-entropy of one sequence ``tokens [S]``."""
    x = _q(table, precision)[tokens]
    for l, layer in enumerate(params["layers"]):
        x = jax.checkpoint(functools.partial(
            layer_forward, config=config, l=l, precision=precision))(x, layer)
    xn = rmsnorm(x, params["norm_f"], float(config["rms_norm_eps"]))
    s = tokens.shape[0]
    labels = jnp.roll(tokens, -1)
    live = jnp.arange(s) < s - 1     # the last position predicts nothing
    rows = min(HEAD_BLOCK, s)

    @jax.checkpoint
    def block(xb, lab, lv):
        logits = _mm(xb, params["head"], precision)
        nll = jax.nn.logsumexp(logits, axis=-1) \
            - jnp.take_along_axis(logits, lab[:, None], axis=-1)[:, 0]
        return jnp.sum(jnp.where(lv, nll, 0.0))

    return jnp.sum(jax.lax.map(lambda a: block(*a), (
        xn.reshape(s // rows, rows, -1), labels.reshape(s // rows, rows),
        live.reshape(s // rows, rows))))


def make_gradients(config: dict, precision: str):
    """``gradients(params, table, batch [B, S]) -> (mean loss, gradient of the
    dense leaves, of the table)``, a sequence at a time through one compiled
    program."""
    one = jax.jit(jax.value_and_grad(
        lambda pr, tb, tok, scale: scale * sequence_nll(
            pr, tb, tok, config, precision), argnums=(0, 1)))
    add = jax.jit(lambda a, c: jax.tree.map(jnp.add, a, c), donate_argnums=0)

    def gradients(params, table, batch):
        b, s = batch.shape
        loss, grads = 0.0, None
        for tokens in batch:
            v, g = one(params, table, tokens, 1.0 / (b * (s - 1)))
            loss += float(v)
            grads = g if grads is None else add(grads, g)
        return loss, grads[0], grads[1]
    return gradients


@functools.partial(jax.jit, static_argnames=("lr", "b1", "b2", "eps", "wd"),
                   donate_argnums=(0, 1, 2))
def adamw(p, m, v, g, t, *, lr, b1, b2, eps, wd):
    """One leaf of ``optax.adamw``'s numbers: decoupled decay on every
    leaf."""
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    step = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + eps)
    return p - lr * (step + wd * p), m, v


@functools.partial(jax.jit, static_argnames=("lr", "b1", "b2", "eps"),
                   donate_argnums=(0, 1, 2))
def lazy_adam(table, mu, nu, grad, touched, t, *, lr, b1, b2, eps):
    """Adam on the rows a step touched (``touched [V]``); the others keep
    their weights and their moments. The bias correction counts every step."""
    m = b1 * mu + (1 - b1) * grad
    v = b2 * nu + (1 - b2) * grad * grad
    step = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + eps)
    on = touched[:, None]
    return (jnp.where(on, table - lr * step, table), jnp.where(on, m, mu),
            jnp.where(on, v, nu))
