"""A latent-attention expert language model's stack, for serving sessions.

The layer is the one of the DeepSeek-V3 schema (A.X-K1's ``config.json``,
``skt/A.X-K1``), every width an argument:

* **multi-head latent attention (MLA)**: queries through a low-rank path
  (``q = RMSNorm(h W_dq) W_uq``, per head ``[q_nope; q_pe]``), keys and values
  through a latent ``[c_kv; k_pe] = h W_dkv`` with ``c_kv`` normed, ``k_pe``
  one vector a token shared by every head, ``[k_nope; v] = c_kv W_ukv``. YaRN
  RoPE over interleaved pairs on ``q_pe`` and ``k_pe``; scores scaled by
  ``qk_head_dim ** -0.5 * m ** 2`` (:func:`softmax_scale`). Two forms of the
  same attention: **decompressed** keys and values, for a document's prefill
  from scratch (:func:`prefill_layer`), and **absorbed** over a per-session
  latent cache that holds ``c_kv`` and ``k_pe`` alone (:func:`step`): the
  query ``q_nope W_uk^T`` meets ``c_kv`` directly and the output
  ``(sum_s p c_kv) W_uv``;
* the first ``num_dense_layers`` layers a dense SwiGLU;
* the others a **sigmoid group-limited router** (scores ``sigmoid(u W_r)``
  over all ``router_outputs``, the best ``topk_group`` of ``n_group`` groups
  by the sum of each group's two best scores, the best ``experts_per_token``
  inside them, weights ``routed_scale * s / sum s``), a **shared** SwiGLU
  expert beside the routed ones, and SwiGLU experts of which this chip holds
  ``experts_held``: :func:`~.moe_lm.moe_experts`'s sorted dropless dispatch
  computes the held ones' part of the sum, the weights taken over all
  chosen experts; what the absent experts would add is left out;
* final norm and an untied head over the vocabulary held.

Weights are bfloat16 (the router's float32), matmul accumulation, norms,
softmaxes, router scores and logits float32; the cache is bfloat16.

The token table is a :class:`~distributed_embeddings_tpu.parallel.
DistributedEmbedding`; :class:`~distributed_embeddings_tpu.parallel.
lm_serving.SessionRuntime` looks the tokens up through it and calls
:func:`prefill_layer` and :func:`step`. On the TPU a document's attention is
the Pallas splash kernel (causal, block-sparse) and a step's, decode and
prompt chunk alike, the repo's own flash-decoding kernel
(``ops/latent_attention.py``: each session's cache rows read once, up to what
its queries see); elsewhere XLA, a block of queries at a time and each
session's whole cache. The backend decides
(``moe_lm._on_tpu``).
"""

from __future__ import annotations

import dataclasses
import math
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..utils import obs
from . import moe_lm

_F32 = jnp.float32
_BF16 = jnp.bfloat16
# what :func:`step` counts, summed over the expert layers, each an int32
COUNT_KEYS = ("moe_pairs_held", "moe_pairs_dropped", "moe_experts_touched")


@dataclasses.dataclass(frozen=True)
class MLALMConfig:
    """One chip's share of the model. ``experts_held`` is the range
    ``[lo, hi)`` of the router's ``router_outputs`` experts whose weights
    live here; ``vocab_held`` the rows of the token table and the columns of
    the head."""
    hidden_size: int
    num_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_dim: int
    qk_rope_dim: int
    v_head_dim: int
    dense_width: int
    expert_width: int
    shared_width: int
    num_layers: int
    num_dense_layers: int
    router_outputs: int
    experts_per_token: int
    n_group: int
    topk_group: int
    routed_scale: float
    experts_held: Tuple[int, int]
    vocab_held: int
    rope_theta: float = 10000.0
    rope_factor: float = 1.0
    rope_original: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 1.0
    rms_eps: float = 1e-6
    norm_topk: bool = True
    moe_chunk: int = 4096       # sorted pairs computed at a time
    ffn_chunk: int = 4096       # a document's tokens through an FFN at a time
    attn_block: int = 512       # queries a block (XLA and splash)

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_dim + self.qk_rope_dim

    @property
    def num_held(self) -> int:
        return self.experts_held[1] - self.experts_held[0]

    @property
    def latent_width(self) -> int:
        """What the cache holds a token and layer: ``c_kv`` and ``k_pe``."""
        return self.kv_lora_rank + self.qk_rope_dim

    @property
    def pe_lanes(self) -> int:
        """``k_pe``'s width in the cache: ``qk_rope_dim`` zero-padded to
        whole 128-lane rows, the layout the chip keeps it in anyway (a
        64-wide cache is relaid out, a copy of the whole cache, for every
        kernel call)."""
        return -(-self.qk_rope_dim // 128) * 128


# ------------------------------------------------------------------- YaRN


def yarn_mscale(scale: float, m: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * m * math.log(scale) + 1.0


def softmax_scale(cfg: MLALMConfig) -> float:
    """``qk_head_dim ** -0.5 * m ** 2``, ``m = yarn_mscale(factor,
    mscale_all_dim)``."""
    m = yarn_mscale(cfg.rope_factor, cfg.mscale_all_dim)
    return cfg.qk_head_dim ** -0.5 * m * m


def yarn_inv_freq(cfg: MLALMConfig) -> np.ndarray:
    """The ``qk_rope_dim / 2`` inverse frequencies, float64: the original and
    the interpolated (``/ factor``) blended by the linear ramp between the
    correction dims of ``beta_fast`` and ``beta_slow`` rotations over the
    original context."""
    d, base = cfg.qk_rope_dim, cfg.rope_theta
    extra = 1.0 / base ** (np.arange(0, d, 2, dtype=np.float64) / d)
    if cfg.rope_factor <= 1:
        return extra
    inter = extra / cfg.rope_factor

    def dim_of(rot):
        return d * math.log(cfg.rope_original / (rot * 2 * math.pi)) \
            / (2 * math.log(base))

    lo = max(math.floor(dim_of(cfg.beta_fast)), 0)
    hi = min(math.ceil(dim_of(cfg.beta_slow)), d - 1)
    if lo == hi:
        hi += 0.001
    ramp = np.clip((np.arange(d // 2, dtype=np.float64) - lo) / (hi - lo),
                   0.0, 1.0)
    return inter * ramp + extra * (1.0 - ramp)


def rope_table(cfg: MLALMConfig, positions: int) -> jax.Array:
    """``[positions, 2, qk_rope_dim / 2]`` float32: cos and sin of every
    position's angles, from float64 on the host, times the cos/sin scale
    ``yarn_mscale(factor, mscale) / yarn_mscale(factor, mscale_all_dim)``."""
    ang = np.arange(positions, dtype=np.float64)[:, None] \
        * yarn_inv_freq(cfg)[None, :]
    s = yarn_mscale(cfg.rope_factor, cfg.mscale) \
        / yarn_mscale(cfg.rope_factor, cfg.mscale_all_dim)
    return jnp.asarray(np.stack([np.cos(ang) * s, np.sin(ang) * s], axis=1),
                       _F32)


def rope(x, cs):
    """Rotate interleaved pairs ``(x[2i], x[2i+1])`` of the last dimension by
    the angles ``cs [..., 2, d / 2]`` (cos, sin), which broadcast against
    ``x[..., ::2]`` once their axis 2 is taken."""
    cos, sin = cs[..., 0, :], cs[..., 1, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


# ------------------------------------------------------------- the layers

rmsnorm = moe_lm.rmsnorm
_mm = moe_lm._mm


def _queries(h, layer: dict, cfg: MLALMConfig, cs):
    """``(q_nope [T, nh, dn], q_pe [T, nh, dr])`` float32, ``q_pe`` rotated;
    ``cs [T, 2, dr / 2]``."""
    cq = rmsnorm(_mm(h, layer["wq_a"]), layer["norm_q"], cfg.rms_eps)
    q = _mm(cq, layer["wq_b"]).reshape(h.shape[0], cfg.num_heads,
                                        cfg.qk_head_dim)
    return q[..., :cfg.qk_nope_dim], rope(q[..., cfg.qk_nope_dim:],
                                          cs[:, None])


def latent(h, layer: dict, cfg: MLALMConfig, cs):
    """``(c_kv [T, kl], k_pe [T, dr])``: the normed latent and the rotated
    shared key, bfloat16 as the cache holds them."""
    kv = _mm(h, layer["wkv_a"])
    c = rmsnorm(kv[:, :cfg.kv_lora_rank], layer["norm_kv"], cfg.rms_eps)
    return c.astype(_BF16), rope(kv[:, cfg.kv_lora_rank:], cs).astype(_BF16)


def _ukv(layer: dict, cfg: MLALMConfig):
    """``(W_uk [kl, nh, dn], W_uv [kl, nh, dv])``."""
    w = layer["wkv_b"].reshape(cfg.kv_lora_rank, cfg.num_heads,
                               cfg.qk_nope_dim + cfg.v_head_dim)
    return w[..., :cfg.qk_nope_dim], w[..., cfg.qk_nope_dim:]


def _lanes(x, cfg: MLALMConfig):
    """The last dimension, ``qk_rope_dim`` wide, zero-padded to
    ``pe_lanes``: the cache's ``k_pe`` and the query it meets."""
    pad = cfg.pe_lanes - x.shape[-1]
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)]) if pad else x


def _out(o, layer: dict):
    """``o [T, nh, dv]`` -> ``[T, H]`` float32."""
    return _mm(o.reshape(o.shape[0], -1), layer["wo"])


def _attend_blocked(q, k, v, block: int):
    """XLA alone, causal: ``q``/``k`` ``[nh, T, d]``, ``v [nh, T, dv]``
    bfloat16, ``q`` scaled. A block of queries at a time over the keys up to
    its end."""
    t = q.shape[1]
    block = min(block, t)
    outs = []
    for q0 in range(0, t, block):
        q1 = min(q0 + block, t)
        sc = jnp.einsum("hqd,hkd->hqk", q[:, q0:q1], k[:, :q1],
                        preferred_element_type=_F32)
        i = q0 + lax.broadcasted_iota(jnp.int32, sc.shape[1:], 0)
        j = lax.broadcasted_iota(jnp.int32, sc.shape[1:], 1)
        p = jax.nn.softmax(jnp.where(j <= i, sc, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("hqk,hkd->hqd", p.astype(_BF16), v[:, :q1],
                               preferred_element_type=_F32))
    return jnp.concatenate(outs, axis=1)


def _attend_splash(q, k, v, block: int):
    """The Pallas splash kernel, causal, one call over every head; ``v`` may
    be narrower than ``q`` and ``k``."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk, splash_attention_mask as sm)
    nh, t = q.shape[0], q.shape[1]
    block = min(block, t)
    kernel = sk.make_splash_mha(
        sm.MultiHeadMask([sm.CausalMask((t, t))] * nh),
        block_sizes=sk.BlockSizes(block_q=block, block_kv=block,
                                  block_kv_compute=min(block, 512)),
        head_shards=1, q_seq_shards=1)
    return kernel(q, k, v)


def attend_decompressed(h, layer: dict, cfg: MLALMConfig, cs):
    """A document's causal self-attention from scratch, keys and values
    decompressed: ``h [T, H]`` normed -> ``(attention's part [T, H], c_kv,
    k_pe)``. The projections run ``ffn_chunk`` tokens at a time."""
    t, nh = h.shape[0], cfg.num_heads
    w_uk, w_uv = _ukv(layer, cfg)
    scale = softmax_scale(cfg)

    def project(hc, csc):
        q_nope, q_pe = _queries(hc, layer, cfg, csc)
        c, k_pe = latent(hc, layer, cfg, csc)
        q = (jnp.concatenate([q_nope, q_pe], -1) * scale).astype(_BF16)
        k_nope = jnp.einsum("tc,chd->thd", c, w_uk,
                            preferred_element_type=_F32).astype(_BF16)
        k = jnp.concatenate([k_nope, jnp.broadcast_to(
            k_pe[:, None], (hc.shape[0], nh, cfg.qk_rope_dim))], -1)
        v = jnp.einsum("tc,chd->thd", c, w_uv,
                       preferred_element_type=_F32).astype(_BF16)
        return q, k, v, c, k_pe

    chunk = cfg.ffn_chunk
    if t > chunk and t % chunk == 0:
        parts = lax.map(lambda a: project(*a),
                        (h.reshape(t // chunk, chunk, -1),
                         cs.reshape(t // chunk, chunk, *cs.shape[1:])))
        q, k, v, c, k_pe = (a.reshape(t, *a.shape[2:]) for a in parts)
    else:
        q, k, v, c, k_pe = project(h, cs)
    attend = _attend_splash if moe_lm._on_tpu() else _attend_blocked
    o = attend(*(a.transpose(1, 0, 2) for a in (q, k, v)), cfg.attn_block)
    o = o.transpose(1, 0, 2).astype(_BF16)
    return _by_chunks(lambda oc: _out(oc, layer), o, chunk), c, k_pe


def attend_absorbed(q_nope, q_pe, cache_c, cache_pe, qpos, layer: dict,
                    cfg: MLALMConfig):
    """Absorbed attention of ``Tq`` queries a session over its latent cache.
    ``q_nope [B, Tq, nh, dn]``, ``q_pe [B, Tq, nh, dr]`` float32, ``cache_c
    [B, C, kl]``, ``cache_pe [B, C, dr]`` bfloat16, ``qpos [B, Tq]`` each
    query's position: it sees the cache rows at positions up to its own.
    Returns ``[B, Tq, nh, dv]`` float32."""
    w_uk, w_uv = _ukv(layer, cfg)
    qa = jnp.einsum("bthd,chd->bthc", q_nope.astype(_BF16), w_uk,
                    preferred_element_type=_F32)
    sc = jnp.einsum("bthc,bsc->bhts", qa.astype(_BF16), cache_c,
                    preferred_element_type=_F32) \
        + jnp.einsum("bthr,bsr->bhts", q_pe.astype(_BF16), cache_pe,
                     preferred_element_type=_F32)
    s = lax.broadcasted_iota(jnp.int32, (cache_c.shape[1],), 0)
    seen = s[None, None, None, :] <= qpos[:, None, :, None]
    p = jax.nn.softmax(jnp.where(seen, sc * softmax_scale(cfg), -jnp.inf),
                       axis=-1)
    o = jnp.einsum("bhts,bsc->bhtc", p.astype(_BF16), cache_c,
                   preferred_element_type=_F32)
    return jnp.einsum("bhtc,chd->bthd", o.astype(_BF16), w_uv,
                      preferred_element_type=_F32)


def attend_cache(q_nope, q_pe, cache_c, cache_pe, slots, lengths,
                 layer: dict, cfg: MLALMConfig, rows: int):
    """Absorbed attention of ``T`` queries an entry over a session slot's
    cache: ``q_nope [B, T, nh, dn]``, ``q_pe [B, T, nh, dr]``, entry ``b``
    reading slot ``slots[b]``, its query ``t`` the rows at positions below
    ``lengths[b] + t`` -> ``[B, T, nh, dv]`` float32. An entry of length 0
    (an idle slot) comes back as zeros on the chip and as anything finite
    elsewhere. On the TPU the Pallas kernel
    :func:`~..ops.latent_attention.latent_attention` (``rows`` query rows a
    block) reads each entry's rows once, up to what its queries see;
    elsewhere XLA over each entry's whole cache."""
    b, t, nh, _ = q_nope.shape
    if not moe_lm._on_tpu():
        qpos = jnp.maximum(lengths - 1, 0)[:, None] + jnp.arange(t)
        return attend_absorbed(q_nope, q_pe, cache_c[slots],
                               cache_pe[slots, :, :cfg.qk_rope_dim], qpos,
                               layer, cfg)
    from ..ops.latent_attention import latent_attention
    w_uk, w_uv = _ukv(layer, cfg)
    qa = jnp.einsum("bthd,chd->bthc", q_nope.astype(_BF16), w_uk,
                    preferred_element_type=_F32)
    o = latent_attention(
        qa.astype(_BF16).reshape(b, t * nh, -1),
        _lanes(q_pe.astype(_BF16), cfg).reshape(b, t * nh, -1), cache_c,
        cache_pe, slots, lengths, heads=nh, scale=softmax_scale(cfg),
        rows=rows)
    return jnp.einsum("bthc,chd->bthd", o.astype(_BF16).reshape(b, t, nh, -1),
                      w_uv, preferred_element_type=_F32)


def route(u, w_router, cfg: MLALMConfig, live=None):
    """Sigmoid group-limited routing of ``u [T, H]`` (normed): ``(chosen
    experts [T, k], their weights [T, k])`` float32. A row that ``live``
    marks False chooses, with weight 0, only the expert just below the held
    range, so that the dispatch sorts it past the held experts."""
    s = jax.nn.sigmoid(jnp.dot(u.astype(_F32), w_router,
                               precision=lax.Precision.HIGHEST))
    t, r = s.shape
    groups = s.reshape(t, cfg.n_group, r // cfg.n_group)
    best2 = jnp.sum(lax.top_k(groups, 2)[0], axis=-1)          # [T, G]
    _, keep = lax.top_k(best2, cfg.topk_group)
    kept = jnp.sum(jax.nn.one_hot(keep, cfg.n_group, dtype=_F32), axis=1)
    masked = jnp.where(jnp.repeat(kept, r // cfg.n_group, axis=1) > 0, s,
                       -1.0)
    _, idx = lax.top_k(masked, cfg.experts_per_token)
    w = jnp.take_along_axis(s, idx, axis=1)
    if cfg.norm_topk:
        w = w / jnp.sum(w, axis=1, keepdims=True)
    w = w * cfg.routed_scale
    if live is not None:
        dead = (cfg.experts_held[0] - 1) % cfg.router_outputs
        idx = jnp.where(live[:, None], idx, dead)
        w = jnp.where(live[:, None], w, 0.0)
    return idx, w


def _swiglu(u, gate, up, down):
    a = jax.nn.silu(_mm(u, gate)) * _mm(u, up)
    return _mm(a, down)


def _by_chunks(fn, u, chunk: int):
    """``fn`` over ``u [T, H]`` a chunk of rows at a time (``lax.map``),
    where ``T`` is more than a chunk."""
    t = u.shape[0]
    if t <= chunk or t % chunk:
        return fn(u)
    out = lax.map(fn, u.reshape(t // chunk, chunk, -1))
    return out.reshape(t, -1)


def ffn(x1, layer: dict, cfg: MLALMConfig, l: int, live=None):
    """The layer's second half: ``x1 [T, H]`` float32 -> ``(x2, counts)``,
    counts those of :data:`COUNT_KEYS` (zeros for a dense layer). More than
    ``ffn_chunk`` tokens (a document) go ``ffn_chunk`` at a time."""
    u = rmsnorm(x1, layer["norm_ffn"], cfg.rms_eps)
    if l < cfg.num_dense_layers:
        y = _by_chunks(lambda v: _swiglu(v, layer["ffn_gate"], layer["ffn_up"],
                                         layer["ffn_down"]), u, cfg.ffn_chunk)
        return x1 + y, jnp.zeros((len(COUNT_KEYS),), jnp.int32)

    def experts(v, lv):
        with obs.scope("moe_route"):
            idx, w = route(v, layer["router"], cfg, lv)
            lo, hi = cfg.experts_held
            mine = (idx >= lo) & (idx < hi) & (w > 0)
            touched = jnp.sum(jnp.any(
                mine[..., None] & (idx[..., None] == jnp.arange(lo, hi)),
                axis=(0, 1))).astype(jnp.int32)
        y, c = moe_lm.moe_experts(v, idx, w, layer, cfg, act=jax.nn.silu)
        with obs.scope("moe_shared"):
            y = y + _swiglu(v, layer["shared_gate"], layer["shared_up"],
                            layer["shared_down"])
        return y, jnp.stack([c[0], c[1], touched])

    t, chunk = u.shape[0], cfg.ffn_chunk
    if t > chunk and t % chunk == 0:
        y, counts = lax.map(lambda v: experts(v, None),
                            u.reshape(t // chunk, chunk, -1))
        return x1 + y.reshape(t, -1), jnp.sum(counts, axis=0)
    y, counts = experts(u, live)
    return x1 + y, counts


def prefill_layer(x, layer: dict, cfg: MLALMConfig, l: int, cs):
    """Layer ``l`` over a whole document from scratch: ``x [T, H]`` float32,
    ``cs [T, 2, dr / 2]`` its positions' angles -> ``(x, c_kv, k_pe)``, the
    latent rows as the cache holds them."""
    h = rmsnorm(x, layer["norm_attn"], cfg.rms_eps)
    with obs.scope("mla_prefill"):
        a, c, k_pe = attend_decompressed(h, layer, cfg, cs)
    x, _ = ffn(x + a, layer, cfg, l)
    return x, c, _lanes(k_pe, cfg)


def step(params: dict, x, caches: List[Tuple[jax.Array, jax.Array]],
         pos, active, chunk, cfg: MLALMConfig, cs):
    """One step of continuous batching. ``x [S + P, H]``: the token rows of
    the ``S`` session slots (one a slot, its next token) and of a prompt
    chunk of ``P`` tokens (``P`` may be 0). ``caches[l] = (c [S, C, kl],
    pe [S, C, pe_lanes])``. ``pos [S]`` each slot's position (its cache's length),
    ``active [S]`` which slots decode; ``chunk = (session, start, valid)``
    int32 scalars, or None without a chunk (``valid`` 0: an empty chunk,
    computed as a real one is, whose rows land where nothing reads them
    before they are written and which changes no slot's logits). ``cs [S + P, 2, dr / 2]`` every
    row's angles. Writes every active slot's and the chunk's latent rows at
    their positions and returns ``(caches, logits [S, V], counts)``: each
    slot's logits for its next token, the chunk's session's slot holding the
    chunk's last valid row's instead."""
    n_slots = pos.shape[0]
    p = x.shape[0] - n_slots
    cap = caches[0][0].shape[1]
    live = active
    if p:
        sid, start, valid = chunk
        live = jnp.concatenate([active, jnp.arange(p) < valid])
    wpos = jnp.where(active, pos, cap)      # an idle slot writes nothing
    slots = jnp.arange(n_slots)
    counts = jnp.zeros((len(COUNT_KEYS),), jnp.int32)
    out = []
    for l, (layer, (cc, cpe)) in enumerate(zip(params["layers"], caches)):
        h = rmsnorm(x, layer["norm_attn"], cfg.rms_eps)
        q_nope, q_pe = _queries(h, layer, cfg, cs)
        c, k_pe = latent(h, layer, cfg, cs)
        k_pe = _lanes(k_pe, cfg)
        cc = cc.at[slots, wpos].set(c[:n_slots], mode="drop")
        cpe = cpe.at[slots, wpos].set(k_pe[:n_slots], mode="drop")
        if p:
            cc = lax.dynamic_update_slice(cc, c[None, n_slots:], (sid, start, 0))
            cpe = lax.dynamic_update_slice(cpe, k_pe[None, n_slots:],
                                           (sid, start, 0))
        with obs.scope("mla_decode"):
            o = attend_cache(q_nope[:n_slots, None], q_pe[:n_slots, None],
                             cc, cpe, slots, jnp.where(active, pos + 1, 0),
                             layer, cfg, rows=cfg.num_heads)[:, 0]
        if p:
            with obs.scope("mla_prefill"):
                oc = attend_cache(q_nope[None, n_slots:], q_pe[None, n_slots:],
                                  cc, cpe, sid[None], (start + 1)[None],
                                  layer, cfg, rows=cfg.attn_block)[0]
            o = jnp.concatenate([o, oc])
        out.append((cc, cpe))
        x, c_l = ffn(x + _out(o.astype(_BF16), layer), layer, cfg, l, live)
        counts = counts + c_l
    with obs.scope("lm_head"):
        rows = x[:n_slots]
        if p:       # an empty chunk (valid 0) leaves every slot's row
            last = lax.dynamic_index_in_dim(x, n_slots + valid - 1)
            rows = jnp.where(((slots == sid) & (valid > 0))[:, None], last,
                             rows)
        xn = rmsnorm(rows, params["norm_f"], cfg.rms_eps)
        logits = _mm(xn, params["head"])
    return out, logits, counts


def cache_shapes(cfg: MLALMConfig, slots: int, capacity: int
                 ) -> Sequence[Tuple[Tuple[int, ...], Tuple[int, ...]]]:
    """Each layer's ``(c shape, pe shape)`` for ``slots`` sessions of
    ``capacity`` tokens."""
    return [((slots, capacity, cfg.kv_lora_rank),
             (slots, capacity, cfg.pe_lanes))] * cfg.num_layers
