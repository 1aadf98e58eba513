"""A sparse-expert language model's dense stack, for the hybrid step.

The token table is a :class:`~distributed_embeddings_tpu.parallel.
DistributedEmbedding` (one table, no combiner, a token a sample); everything
after it is here, as the ``loss_fn(dense_params, emb_outputs, batch)`` that
:func:`~distributed_embeddings_tpu.parallel.make_hybrid_train_step` takes
(:func:`make_loss_fn`). The layer is the one SmallThinker's ``config.json``
describes (``PowerInfer/SmallThinker-21BA3B-Instruct``), every width an
argument:

* the **router** reads the layer's input as it arrives on the residual
  stream, ahead of attention and of its norm; the best ``experts_per_token``
  of its ``router_outputs`` logits, a softmax over those;
* **grouped-query attention**, causal, per layer either over a window with
  RoPE or over the whole prefix with no position encoding
  (``window_layout`` / ``rope_layout``);
* a **ReGLU expert layer** that is told which experts it holds
  (``experts_held``, a range of the router's outputs): it routes over all of
  them, computes its own experts' part of the sum with the weights taken over
  all chosen experts, and on one chip runs without an exchange. What the
  absent experts would have added is left out. Dispatch is sorted and
  **dropless**: the pairs of held experts lie first in the sorted order and
  are computed ``moe_chunk`` rows at a time, as many chunks as hold pairs, so
  no routing overflows a buffer;
* final norm, an untied head over the vocabulary held, mean next-token
  cross-entropy in float32, chunked over tokens.

Matmul operands are bfloat16 copies of float32 master weights, accumulation,
softmaxes, norms and the loss float32; the router's matmul is float32
throughout. Each layer is recomputed in the backward.

On the TPU attention is the Pallas splash kernel (block-sparse: masked key
blocks are skipped) and the experts' products the megablox grouped matmul;
on any other backend XLA alone: attention a block of queries at a time over
its unmasked key range, the experts ``lax.ragged_dot``. The backend decides
(:func:`_on_tpu`), no argument (``PERF.md`` section 6, PR 36, has what each
form read on the chip at the benchmark's shapes).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..utils import obs

_F32 = jnp.float32
_BF16 = jnp.bfloat16
# what the step's auxiliary output holds, each a per-device ``[1]`` count
COUNT_KEYS = ("moe_pairs_held", "moe_pairs_dropped",
              "moe_hottest_expert_pairs", "moe_chunks_run")


@dataclasses.dataclass(frozen=True)
class MoELMConfig:
    """One chip's share of the model. ``experts_held`` is the range
    ``[lo, hi)`` of the router's ``router_outputs`` experts whose weights
    live here; ``vocab_held`` the rows of the token table and the columns of
    the head."""
    hidden_size: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    expert_width: int
    router_outputs: int
    experts_per_token: int
    experts_held: Tuple[int, int]
    vocab_held: int
    seq_len: int
    window_layout: Tuple[int, ...]   # per layer: 1 = sliding window
    rope_layout: Tuple[int, ...]     # per layer: 1 = RoPE, 0 = no position
    window: int
    rope_theta: float = 1.5e6
    rms_eps: float = 1e-6
    moe_chunk: int = 98304           # sorted pairs computed at a time
    loss_chunk: int = 4096           # tokens whose logits exist at a time
    attn_block: int = 1024           # queries a block, both attentions

    @property
    def num_layers(self) -> int:
        return len(self.window_layout)

    @property
    def num_held(self) -> int:
        return self.experts_held[1] - self.experts_held[0]


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def init_params(cfg: MoELMConfig, key, std: float = 0.02) -> dict:
    """Float32 master weights: normal ``std`` matrices, unit norms. (With a
    token table of normal(0, 1) rows the router's logits are of order 1.)"""
    h, d = cfg.hidden_size, cfg.head_dim
    nq, nkv, f = cfg.num_heads * d, cfg.num_kv_heads * d, cfg.expert_width
    e = cfg.num_held
    keys = iter(jax.random.split(key, 8 * cfg.num_layers + 1))

    def normal(shape, s=std):
        return s * jax.random.normal(next(keys), shape, _F32)

    layers = [{
        "router": normal((h, cfg.router_outputs)),
        "norm_in": jnp.ones((h,), _F32), "norm_post": jnp.ones((h,), _F32),
        "wq": normal((h, nq)), "wk": normal((h, nkv)), "wv": normal((h, nkv)),
        "wo": normal((nq, h)),
        "gate": normal((e, h, f)), "up": normal((e, h, f)),
        "down": normal((e, f, h)),
    } for _ in range(cfg.num_layers)]
    return {"layers": layers, "norm_f": jnp.ones((h,), _F32),
            "head": normal((h, cfg.vocab_held))}


def rmsnorm(x, w, eps):
    x = x.astype(_F32)
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _mm(a, b):
    """bfloat16 operands, float32 accumulation."""
    return jnp.dot(a.astype(_BF16), b.astype(_BF16),
                   preferred_element_type=_F32)


def route(x, w_router, k: int):
    """``(chosen experts [T, k], their weights [T, k])``: the router's
    float32 logits of ``x``, the ``k`` largest, a softmax over those. The
    chosen logits are picked by a one-hot product, exact in float32, so that
    their cotangent is a dense product too and no scatter."""
    highest = lax.Precision.HIGHEST
    logits = jnp.dot(x.astype(_F32), w_router, precision=highest)
    _, idx = lax.top_k(lax.stop_gradient(logits), k)
    pick = jax.nn.one_hot(idx, logits.shape[-1], dtype=_F32)
    top = jnp.einsum("tke,te->tk", pick, logits, precision=highest)
    return idx, jax.nn.softmax(top, axis=-1)


def rope(x, theta: float):
    """Rotate-half RoPE over the last dimension of ``[..., S, D]``."""
    s, d = x.shape[-2], x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float64) / d))
    ang = np.arange(s, dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.concatenate([np.cos(ang)] * 2, -1), _F32)
    sin = jnp.asarray(np.concatenate([np.sin(ang)] * 2, -1), _F32)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


def _attend_blocked(q, k, v, window: Optional[int], block: int):
    """XLA alone: ``q [B, KV, G, S, D]`` (scaled), ``k``/``v``
    ``[B, KV, S, D]``, bfloat16. A block of queries at a time over the key
    blocks its mask leaves, each block recomputed in the backward."""
    s = q.shape[-2]
    block = min(block, s)

    @jax.checkpoint
    def one(qb, kb, vb, q0, k0):
        sc = jnp.einsum("bkgqd,bksd->bkgqs", qb, kb,
                        preferred_element_type=_F32)
        i = q0 + lax.broadcasted_iota(jnp.int32, sc.shape[-2:], 0)
        j = k0 + lax.broadcasted_iota(jnp.int32, sc.shape[-2:], 1)
        ok = j <= i
        if window is not None:
            ok &= i - j < window
        p = jax.nn.softmax(jnp.where(ok, sc, -jnp.inf), axis=-1)
        return jnp.einsum("bkgqs,bksd->bkgqd", p.astype(_BF16), vb,
                          preferred_element_type=_F32)

    outs = []
    for q0 in range(0, s, block):
        q1 = min(q0 + block, s)
        k0 = 0 if window is None else \
            max(0, (q0 - window + 1) // block * block)
        outs.append(one(q[..., q0:q1, :], k[..., k0:q1, :], v[..., k0:q1, :],
                        q0, k0))
    return jnp.concatenate(outs, axis=-2)


def _attend_splash(q, k, v, window: Optional[int], block: int):
    """The Pallas splash kernel, one multi-query call a key-value head."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk, splash_attention_mask as sm)
    g, s = q.shape[2], q.shape[3]
    block = min(block, s)
    shape = (s, s)
    one = sm.CausalMask(shape) if window is None else \
        sm.LocalMask(shape, window_size=(window - 1, 0), offset=0)
    # blocks of 1024 with the scores computed 512 keys at a time and the
    # fused backward read 52 and 45 ms a layer (full, window; forward and
    # backward at the cell's shapes) where 512 throughout and two backward
    # kernels read 80 and 59; 2048 does not fit the kernel's memory
    compute = min(block, 512)
    sizes = sk.BlockSizes(
        block_q=block, block_kv=block, block_kv_compute=compute,
        block_q_dkv=block, block_kv_dkv=block, block_kv_dkv_compute=compute,
        use_fused_bwd_kernel=True)
    kernel = sk.make_splash_mqa_single_device(
        sm.MultiHeadMask([one] * g), block_sizes=sizes)
    return jax.vmap(jax.vmap(kernel))(q, k, v)


def attention(h, layer: dict, cfg: MoELMConfig, l: int):
    """``h [B, S, H]`` normed -> attention's part of the residual stream,
    ``[B, S, H]``. The projections go straight to and from the head-major
    layout that the kernels take, so no transposed copy of ``q``, ``k``,
    ``v`` or the output exists beside them."""
    b, s, hid = h.shape
    d, nh, nkv = cfg.head_dim, cfg.num_heads, cfg.num_kv_heads
    g = nh // nkv
    window = cfg.window if cfg.window_layout[l] else None
    hb = h.astype(_BF16)

    def heads(w, n):    # [B, S, H] @ [H, n*D] -> [B, n, S, D]
        return jnp.einsum("bsh,hnd->bnsd", hb,
                          w.astype(_BF16).reshape(hid, n, d),
                          preferred_element_type=_F32)

    q, k, v = heads(layer["wq"], nh), heads(layer["wk"], nkv), \
        heads(layer["wv"], nkv)
    if cfg.rope_layout[l]:
        q, k = rope(q, cfg.rope_theta), rope(k, cfg.rope_theta)
    q = (q * (1.0 / math.sqrt(d))).astype(_BF16).reshape(b, nkv, g, s, d)
    k, v = k.astype(_BF16), v.astype(_BF16)
    attend = _attend_splash if _on_tpu() else _attend_blocked
    with obs.scope("attn_window" if window else "attn_full"):
        out = attend(q, k, v, window, cfg.attn_block)
    return jnp.einsum("bnsd,ndh->bsh", out.reshape(b, nh, s, d).astype(_BF16),
                      layer["wo"].astype(_BF16).reshape(nh, d, hid),
                      preferred_element_type=_F32)


def _grouped(lhs, rhs, sizes):
    """``lhs[rows of group i] @ rhs[i]``; rows past the groups come out 0
    (from ``lax.ragged_dot`` on the CPU at least: on the TPU it leaves
    products there, which is one more reason it is not the chip's form)."""
    if not _on_tpu():
        return lax.ragged_dot(lhs, rhs, sizes, preferred_element_type=_F32)
    from jax.experimental.pallas.ops.tpu.megablox import ops as megablox
    m, kdim, n = lhs.shape[0], lhs.shape[1], rhs.shape[2]
    tm = min(512, m)
    while tm > 8 and m % tm:    # rows a tile: a divisor of the rows
        tm -= 8
    tiling = (tm, min(1024, kdim), min(1024, n))
    # The rows past the held experts' ride as one more group, with no weights
    # here: the kernel's own form for a shard of the experts. This leans on
    # ``gmm`` zeroing the rows of groups beyond ``rhs.shape[0]`` (its
    # ``_zero_uninitialized_memory``), forward and, through its VJP, in the
    # cotangent of ``lhs``: ``moe_experts`` multiplies those rows by a weight
    # of 0, and a row left unwritten could hold a NaN. ``chip_smoke.py``'s
    # ``experts`` phase holds the kernel to it on the chip.
    rest = (m - jnp.sum(sizes)).astype(jnp.int32)[None]
    return megablox.gmm(lhs, rhs, jnp.concatenate([sizes, rest]), _F32,
                        tiling)


# The dispatch moves rows by gathers alone, forward and backward: a TPU
# scatter of rows that are not declared sorted goes a row at a time (some
# 75 ns each, ``parallel/optimizers.py``), and XLA's transpose of a gather is
# such a scatter. Every map here is a permutation or a fan-out of exactly
# ``k`` slots a token, so its transpose is a gather by the inverse map, which
# the custom VJPs below say.

@jax.custom_vjp
def _permute(x, order, inverse):
    """``x[order]`` for a permutation ``order`` with inverse ``inverse``."""
    del inverse
    return jnp.take(x, order, mode="clip")


_permute.defvjp(
    lambda x, order, inverse: (jnp.take(x, order, mode="clip"),
                               (order, inverse)),
    lambda res, dy: (jnp.take(dy, res[1], mode="clip"), None, None))


def _rows_of_slots(buf, pos, at: int):
    """``sum_j buf[pos[:, j] - at]`` over a token's ``k`` slots, a slot
    whose position lies outside ``buf``'s rows adding nothing: ``[T, H]``
    float32 from ``buf [C, H]`` and ``pos [T, k]``."""
    rows = buf.shape[0]
    total = 0.0
    for j in range(pos.shape[1]):
        rel = pos[:, j] - at
        inside = (rel >= 0) & (rel < rows)
        got = jnp.take(buf, jnp.clip(rel, 0, rows - 1), axis=0, mode="clip")
        total = total + jnp.where(inside[:, None], got.astype(_F32), 0.0)
    return total


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _dispatch(g, tok, pos, at: int):
    """The chunk's rows ``g[tok]``; the cotangent of ``g`` gathers the
    chunk's row cotangents back through ``pos``."""
    del pos, at
    return jnp.take(g, tok, axis=0, mode="clip")


_dispatch.defvjp(
    lambda g, tok, pos, at: (jnp.take(g, tok, axis=0, mode="clip"),
                             (tok, pos)),
    # the rows' cotangent has the rows' dtype, which is ``g``'s
    lambda at, res, d: (_rows_of_slots(d, res[1], at).astype(d.dtype),
                        None, None))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _combine(out, tok, pos, at: int):
    """Each token's sum over its slots in this chunk, ``[T, H]`` float32;
    the cotangent of a chunk row is its token's."""
    del tok
    return _rows_of_slots(out, pos, at)


_combine.defvjp(
    lambda out, tok, pos, at: (_rows_of_slots(out, pos, at),
                               (tok, jnp.zeros((0,), out.dtype))),
    lambda at, res, dy: (jnp.take(dy, res[0], axis=0, mode="clip")
                         .astype(res[1].dtype), None, None))


def moe_dispatch(idx, p, cfg: MoELMConfig):
    """Sort the chosen (token, expert) pairs so that the held experts' lie
    first, expert by expert. ``(token_of [P], weight [P], pos [T, k], starts
    [E_held], sizes [E_held])``: the token and the weight of the pair at each
    sorted position (a pair of an expert not held keeps weight 0 and lies
    past the held ones), the sorted position of each token's ``k`` pairs, and
    each held expert's range."""
    lo, _ = cfg.experts_held
    k = idx.shape[-1]
    key = ((idx - lo) % cfg.router_outputs).reshape(-1).astype(jnp.int32)
    pair = jnp.arange(key.shape[0], dtype=jnp.int32)
    key_sorted, order = lax.sort_key_val(key, pair)
    _, inverse = lax.sort_key_val(order, pair)
    bounds = jnp.searchsorted(
        key_sorted, jnp.arange(cfg.num_held + 1, dtype=jnp.int32)
    ).astype(jnp.int32)
    weight = jnp.where(key_sorted < cfg.num_held,
                       _permute(p.reshape(-1), order, inverse), 0.0)
    return (order // k, weight, inverse.reshape(-1, k), bounds[:-1],
            bounds[1:] - bounds[:-1])


def moe_experts(g, idx, p, layer: dict, cfg: MoELMConfig, act=jax.nn.relu):
    """The held experts' part of the layer's sum for ``g [T, H]`` (normed),
    with the counts of :data:`COUNT_KEYS`: the pairs held, the pairs dropped
    (held less computed: 0), the fullest expert's pairs, the chunks run.
    An expert is ``down(act(gate(g)) * up(g))``: ReGLU by default, SwiGLU
    with ``act=jax.nn.silu``. ``cfg`` is any configuration with
    ``experts_held``, ``router_outputs``, ``num_held`` and ``moe_chunk``."""
    with obs.scope("moe_route"):
        token_of, weight, pos, starts, sizes = moe_dispatch(idx, p, cfg)
        held = jnp.sum(sizes)
    pairs = token_of.shape[0]
    chunk = min(cfg.moe_chunk, pairs)
    n_chunks = -(-pairs // chunk)
    pad = n_chunks * chunk - pairs
    token_of = jnp.pad(token_of, (0, pad))
    weight = jnp.pad(weight, (0, pad))
    gb = g.astype(_BF16)
    w_gate, w_up, w_down = (layer[n].astype(_BF16)
                            for n in ("gate", "up", "down"))

    def compute(at: int):
        tok, w = token_of[at:at + chunk], weight[at:at + chunk]
        # each held expert's rows inside this chunk
        here = jnp.clip(starts + sizes - at, 0, chunk) \
            - jnp.clip(starts - at, 0, chunk)
        with obs.scope("moe_route"):
            xs = _dispatch(gb, tok, pos, at)
        with obs.scope("moe_experts"):
            a = act(_grouped(xs, w_gate, here)) * _grouped(xs, w_up, here)
            out = _grouped(a.astype(_BF16), w_down, here)
        with obs.scope("moe_route"):
            part = _combine(out * w[:, None], tok, pos, at)
        return part, jnp.sum(here)

    # A Python loop, not a scan: a scan's backward would keep a copy of
    # everything ``compute`` closes over (the dispatched rows, the experts'
    # bfloat16 weights) for every chunk. The first chunk always runs (with no
    # pair held it adds nothing), the later ones where pairs reach them.
    y, done, ran = jnp.zeros(g.shape, _F32), jnp.int32(0), jnp.int32(0)
    for at in range(0, n_chunks * chunk, chunk):
        def add_chunk(y, done, ran, at=at):
            # added outside the recomputed part: a sum needs no residual
            part, n = jax.checkpoint(functools.partial(compute, at))()
            return y + part, done + n, ran + 1
        y, done, ran = add_chunk(y, done, ran) if at == 0 else lax.cond(
            at < held, add_chunk, lambda *a: a, y, done, ran)
    counts = jnp.stack([held, held - done, jnp.max(sizes), ran])
    return y, counts


def block(x, layer: dict, cfg: MoELMConfig, l: int):
    """One layer: ``x [B, S, H]`` float32 -> ``(x2, counts)``."""
    b, s, h = x.shape
    with obs.scope("moe_route"):
        idx, p = route(x.reshape(b * s, h), layer["router"],
                       cfg.experts_per_token)
    hn = rmsnorm(x, layer["norm_in"], cfg.rms_eps)
    x1 = x + attention(hn, layer, cfg, l)
    g = rmsnorm(x1, layer["norm_post"], cfg.rms_eps)
    y, counts = moe_experts(g.reshape(b * s, h), idx, p, layer, cfg)
    return x1 + y.reshape(b, s, h), counts


def head_loss(x, tokens, params: dict, cfg: MoELMConfig):
    """Mean next-token cross-entropy of ``x [B, S, H]`` against ``tokens
    [B, S]``: position ``t`` predicts token ``t + 1``, a sequence's last
    position nothing. ``loss_chunk`` tokens' logits exist at a time."""
    b, s, h = x.shape
    xn = rmsnorm(x, params["norm_f"], cfg.rms_eps).reshape(b * s, h)
    labels = jnp.roll(tokens, -1, axis=1).reshape(b * s)
    live = (jnp.arange(b * s) % s != s - 1).astype(_F32)
    n = b * s
    chunk = min(cfg.loss_chunk, n)
    if n % chunk:
        raise ValueError(f"{n} tokens do not divide into chunks of {chunk}")
    w_head = params["head"].astype(_BF16)

    @jax.checkpoint
    def one(xc, lab, lv):
        logits = _mm(xc, w_head)
        nll = jax.nn.logsumexp(logits, axis=-1) \
            - jnp.take_along_axis(logits, lab[:, None], axis=-1)[:, 0]
        return jnp.sum(nll * lv)

    total = jnp.float32(0.0)
    for a in range(0, n, chunk):
        total = total + one(xn[a:a + chunk], labels[a:a + chunk],
                            live[a:a + chunk])
    return total / (b * (s - 1))


def forward_loss(params: dict, emb, tokens, cfg: MoELMConfig):
    """``emb [B*S, H]`` (the token table's rows), ``tokens [B*S]`` ->
    ``(loss, counts)``, the counts summed over the layers in the order of
    :data:`COUNT_KEYS`."""
    s = cfg.seq_len
    x = emb.astype(_F32).reshape(-1, s, cfg.hidden_size)
    tokens = tokens.reshape(-1, s)
    counts = jnp.zeros((len(COUNT_KEYS),), jnp.int32)
    for l, layer in enumerate(params["layers"]):
        x, c = jax.checkpoint(functools.partial(block, cfg=cfg, l=l))(
            x, layer)
        counts = counts + c
    with obs.scope("lm_head"):
        loss = head_loss(x, tokens, params, cfg)
    return loss, counts


def make_loss_fn(cfg: MoELMConfig):
    """``loss_fn(dense_params, emb_outputs, batch) -> (loss, aux)`` for
    ``make_hybrid_train_step(..., has_aux=True)``: ``emb_outputs[0]`` the
    token table's rows of the step's ``[B*S]`` ids and ``batch`` the same ids
    again, for the labels. ``aux`` holds :data:`COUNT_KEYS`, each ``[1]``."""
    def loss_fn(dense_params, emb_outputs, batch):
        loss, counts = forward_loss(dense_params, emb_outputs[0], batch, cfg)
        return loss, {k: counts[i].reshape(1)
                      for i, k in enumerate(COUNT_KEYS)}
    return loss_fn
