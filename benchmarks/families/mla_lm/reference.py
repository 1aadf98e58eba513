"""The plain reference: the layer equations of the configuration's source
(the DeepSeek-V3 schema of A.X-K1's ``config.json``) in straightforward
float32 ``jax.numpy`` at ``highest`` matmul precision, with keys and values
decompressed, nothing absorbed, no cache and a Python loop over the experts.
It imports nothing of the program.

For ``x [T, H]`` at positions ``t``, layer ``l``:

1. ``h = rmsnorm(x)``; ``c_q = rmsnorm(h W_dq)``; ``[q_nope; q_pe] = c_q
   W_uq`` per head; ``[c_kv; k_pe] = h W_dkv``, ``c_kv = rmsnorm(c_kv)``;
   ``[k_nope; v] = c_kv W_ukv`` per head; YaRN RoPE on ``q_pe`` and ``k_pe``
   (rotation of interleaved pairs, DeepSeek-V3's ``yarn_find_correction_range``
   ramp); scores ``(q_nope . k_nope + q_pe . k_pe) * qk_head_dim ** -0.5 *
   m ** 2``, causal; ``x1 = x + concat_h(sum p v) W_o``.
2. ``u = rmsnorm(x1)``; the first ``first_k_dense_replace`` layers ``x2 =
   x1 + W_down(silu(u W_gate) * u W_up)``; the others: ``s = sigmoid(u
   W_r)`` over all ``router_outputs``; each of ``n_group`` groups scored by
   the sum of its two best ``s``; the best ``topk_group`` groups kept; the
   best ``num_experts_per_tok`` experts of those; ``w = routed_scaling_factor
   * s / sum of the chosen s``; ``x2 = x1 + shared(u) + sum over the chosen
   experts held here of w_e expert_e(u)``, every expert a SwiGLU.
3. After the last layer ``rmsnorm``, logits ``@ W_head`` over the vocabulary
   held.

A session's context is computed as the full forward pass in blocks: each
distinct document once (the block's per-layer ``c_kv`` and ``k_pe`` kept in
float32), then each session's suffix (its turns' prompts and generated
tokens) as a further block of queries over the document's and its own keys.
The same pass in blocks, not a serving cache. Attention runs a block of
queries at a time over the key blocks up to its last position, with the
softmax's running maximum and sum (the same sum, reordered).

``precision`` is the control's knob: ``float8`` rounds both operands of every
matmul (the router's too) and the latent rows ``c_kv`` and ``k_pe``
(what a cache holds) to float8_e4m3fn, scaled per tensor. ``fault``
``no_mscale`` leaves ``m ** 2`` out of the scores' scale.
"""

from __future__ import annotations

import functools
import math
import sys
import time
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import weights

HIGHEST = lax.Precision.HIGHEST
Q_BLOCK = 512       # queries whose scores exist at a time
K_BLOCK = 2048      # keys a block of queries meets at a time
ROWS = 4096         # tokens through a dense FFN at a time
# Shapes come in few sizes, so that a run compiles few programs and the next
# run finds them in the compile cache: a suffix is padded to a multiple of
# SUFFIX_BUCKET tokens, the rows asked of a session to a multiple of
# ROW_BUCKET, an expert's rows to a power of two from EXPERT_BUCKET.
SUFFIX_BUCKET = 1024
ROW_BUCKET = 64
EXPERT_BUCKET = 256
# Where ``readings.py`` sets a list, every expert layer appends ``(block,
# layer, chosen experts [T, k])`` to it: ``block`` is "doc" or a suffix's
# index. None in a run.
ROUTES = None


def _bucket(n: int, step: int) -> int:
    return -(-max(n, 1) // step) * step


def _q(x, precision: str):
    if precision == "float32":
        return x
    if precision == "float8":
        scale = 224.0 / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
        return lax.reduce_precision(x * scale, 4, 3) / scale
    if precision == "bfloat16":
        return lax.reduce_precision(x, 8, 7)
    raise ValueError(f"unknown precision {precision!r}")


def mm(a, b, precision: str):
    return jnp.matmul(_q(a, precision), _q(b, precision), precision=HIGHEST)


def rmsnorm(x, w, eps: float):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


# ------------------------------------------------------------- YaRN RoPE


def yarn_get_mscale(scale: float, mscale: float) -> float:
    if scale <= 1:
        return 1.0
    return 0.1 * mscale * math.log(scale) + 1.0


def _rope_cfg(config: dict):
    r = config.get("rope_scaling") or {}
    return (float(config["rope_theta"]), int(config["qk_rope_head_dim"]),
            float(r.get("factor", 1.0)),
            int(r.get("original_max_position_embeddings", 4096)),
            float(r.get("beta_fast", 32)), float(r.get("beta_slow", 1)),
            float(r.get("mscale", 1)), float(r.get("mscale_all_dim", 0)))


def inv_freq(config: dict) -> np.ndarray:
    """DeepSeek-V3's ``DeepseekV3YarnRotaryEmbedding`` inverse frequencies,
    float64."""
    base, dim, factor, orig, fast, slow, _, _ = _rope_cfg(config)
    extra = 1.0 / (base ** (np.arange(0, dim, 2, dtype=np.float64) / dim))
    if factor <= 1:
        return extra
    inter = 1.0 / (factor * base ** (np.arange(0, dim, 2,
                                               dtype=np.float64) / dim))

    def correction_dim(rot):
        return (dim * math.log(orig / (rot * 2 * math.pi))) \
            / (2 * math.log(base))

    low = max(math.floor(correction_dim(fast)), 0)
    high = min(math.ceil(correction_dim(slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0, 1)
    mask = 1.0 - ramp
    return inter * (1 - mask) + extra * mask


def score_scale(config: dict, fault=None) -> float:
    _, _, factor, _, _, _, _, mscale_all = _rope_cfg(config)
    d = int(config["qk_nope_head_dim"]) + int(config["qk_rope_head_dim"])
    scale = d ** -0.5
    if mscale_all and fault != "no_mscale":
        m = yarn_get_mscale(factor, mscale_all)
        scale = scale * m * m
    return scale


def cos_sin(config: dict, positions: np.ndarray):
    """``(cos, sin) [T, d / 2]`` float32 of the positions, times
    ``yarn_get_mscale(factor, mscale) / yarn_get_mscale(factor,
    mscale_all_dim)``."""
    _, _, factor, _, _, _, msc, msc_all = _rope_cfg(config)
    s = yarn_get_mscale(factor, msc) / yarn_get_mscale(factor, msc_all)
    ang = np.asarray(positions, np.float64)[:, None] * inv_freq(config)[None]
    return (jnp.asarray(np.cos(ang) * s, jnp.float32),
            jnp.asarray(np.sin(ang) * s, jnp.float32))


def rope(x, cos, sin):
    """Rotation of the interleaved pairs ``(x[2i], x[2i+1])`` by angle
    ``i``: ``x [T, ..., d]``, ``cos``/``sin`` broadcast against ``[T, ...,
    d / 2]``."""
    ev, od = x[..., 0::2], x[..., 1::2]
    return jnp.stack([ev * cos - od * sin, od * cos + ev * sin],
                     axis=-1).reshape(x.shape)


# ------------------------------------------------------------- attention


@functools.partial(jax.jit, static_argnames=("precision",))
def causal(q, k, v, pos0, scale, precision: str):
    """``q [T, nh, d]`` at positions ``pos0 + i``, ``k [K, nh, d]`` and ``v
    [K, nh, dv]`` at positions ``0..K-1``: query ``i`` sees every key at a
    position up to its own. ``[T, nh, dv]``."""
    t, nh, _ = q.shape
    kn, dv = k.shape[0], v.shape[-1]
    nq, nk = -(-t // Q_BLOCK), -(-kn // K_BLOCK)
    q = jnp.pad(q, ((0, nq * Q_BLOCK - t), (0, 0), (0, 0)))
    k = jnp.pad(k, ((0, nk * K_BLOCK - kn), (0, 0), (0, 0)))
    v = jnp.pad(v, ((0, nk * K_BLOCK - kn), (0, 0), (0, 0)))

    def block(args):
        i, qi = args
        qpos = pos0 + i * Q_BLOCK + jnp.arange(Q_BLOCK)
        n = jnp.minimum((pos0 + (i + 1) * Q_BLOCK + K_BLOCK - 1) // K_BLOCK,
                        nk)

        def body(j, carry):
            m, l, acc = carry
            kj = lax.dynamic_slice_in_dim(k, j * K_BLOCK, K_BLOCK)
            vj = lax.dynamic_slice_in_dim(v, j * K_BLOCK, K_BLOCK)
            s = jnp.einsum("qhd,khd->hqk", _q(qi, precision),
                           _q(kj, precision), precision=HIGHEST) * scale
            kpos = j * K_BLOCK + jnp.arange(K_BLOCK)
            s = jnp.where(kpos[None, None, :] <= qpos[None, :, None], s,
                          -jnp.inf)
            mn = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - mn[..., None])
            a = jnp.exp(m - mn)
            return (mn, l * a + jnp.sum(p, axis=-1),
                    acc * a[..., None] + jnp.einsum(
                        "hqk,khd->hqd", _q(p, precision), _q(vj, precision),
                        precision=HIGHEST))

        init = (jnp.full((nh, Q_BLOCK), -jnp.inf, jnp.float32),
                jnp.zeros((nh, Q_BLOCK), jnp.float32),
                jnp.zeros((nh, Q_BLOCK, dv), jnp.float32))
        _, l, acc = lax.fori_loop(0, n, body, init)
        return (acc / l[..., None]).transpose(1, 0, 2)

    out = lax.map(block, (jnp.arange(nq), q.reshape(nq, Q_BLOCK, nh, -1)))
    return out.reshape(nq * Q_BLOCK, nh, dv)[:t]


@functools.partial(jax.jit, static_argnames=("config_key", "precision",
                                             "fault"))
def _attention(x, w, cos, sin, prior_c, prior_pe, *, config_key,
               precision: str, fault):
    config = _unkey(config_key)
    t = x.shape[0]
    nh = int(config["num_attention_heads"])
    dn, dr = int(config["qk_nope_head_dim"]), int(config["qk_rope_head_dim"])
    dv, kl = int(config["v_head_dim"]), int(config["kv_lora_rank"])
    eps = float(config["rms_norm_eps"])
    h = rmsnorm(x, w["norm_attn"], eps)
    cq = rmsnorm(mm(h, w["wq_a"], precision), w["norm_q"], eps)
    q = mm(cq, w["wq_b"], precision).reshape(t, nh, dn + dr)
    q = jnp.concatenate([q[..., :dn], rope(q[..., dn:], cos[:, None],
                                           sin[:, None])], axis=-1)
    kv = mm(h, w["wkv_a"], precision)
    c = rmsnorm(kv[:, :kl], w["norm_kv"], eps)
    pe = rope(kv[:, kl:], cos, sin)
    c, pe = _q(c, precision), _q(pe, precision)
    cc = jnp.concatenate([prior_c, c])
    pp = jnp.concatenate([prior_pe, pe])
    kvb = mm(cc, w["wkv_b"], precision).reshape(-1, nh, dn + dv)
    k = jnp.concatenate([kvb[..., :dn], jnp.broadcast_to(
        pp[:, None], (pp.shape[0], nh, dr))], axis=-1)
    o = causal(q, k, kvb[..., dn:], prior_c.shape[0],
               score_scale(config, fault), precision)
    return x + mm(o.reshape(t, nh * dv), w["wo"], precision), c, pe


# ------------------------------------------------------- FFN and routing


def _swiglu(u, gate, up, down, precision: str):
    a = jax.nn.silu(mm(u, gate, precision)) * mm(u, up, precision)
    return mm(a, down, precision)


def _rows(fn, u):
    t = u.shape[0]
    if t <= ROWS or t % ROWS:
        return fn(u)
    return lax.map(fn, u.reshape(t // ROWS, ROWS, -1)).reshape(t, -1)


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _dense(x1, w, *, eps, precision):
    u = rmsnorm(x1, w["norm_ffn"], eps)
    return x1 + _rows(lambda v: _swiglu(v, w["ffn_gate"], w["ffn_up"],
                                        w["ffn_down"], precision), u)


def route(u, w_router, config: dict, precision: str = "float32"):
    """``(chosen experts [T, k], weights [T, k])``: sigmoid scores, the best
    ``topk_group`` groups by the sum of each one's two best scores, the best
    ``k`` experts inside them, weights normalized over the chosen and
    scaled."""
    s = jax.nn.sigmoid(mm(u, w_router, precision))
    t, r = s.shape
    ng, kg = int(config["n_group"]), int(config["topk_group"])
    k = int(config["num_experts_per_tok"])
    by_group = jnp.sort(s.reshape(t, ng, r // ng), axis=-1)
    group_score = by_group[..., -1] + by_group[..., -2]
    order = jnp.argsort(-group_score, axis=-1)[:, :kg]
    allowed = jnp.zeros((t, ng), bool).at[
        jnp.arange(t)[:, None], order].set(True)
    allowed = jnp.repeat(allowed, r // ng, axis=1)
    idx = jnp.argsort(-jnp.where(allowed, s, -1.0), axis=-1)[:, :k]
    w = jnp.take_along_axis(s, idx, axis=-1)
    if config.get("norm_topk_prob", True):
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return idx, w * float(config["routed_scaling_factor"])


@functools.partial(jax.jit, static_argnames=("config_key", "precision"))
def _moe_front(x1, w, *, config_key, precision):
    config = _unkey(config_key)
    u = rmsnorm(x1, w["norm_ffn"], float(config["rms_norm_eps"]))
    idx, wt = route(u, w["router"], config, precision)
    y = x1 + _rows(lambda v: _swiglu(v, w["shared_gate"], w["shared_up"],
                                     w["shared_down"], precision), u)
    return u, idx, wt, y


@functools.partial(jax.jit, static_argnames=("precision",),
                   donate_argnums=0)
def _expert_add(y, u, rows, wts, gate, up, down, *, precision):
    out = _swiglu(u[rows], gate, up, down, precision)
    return y.at[rows].add(out * wts[:, None])


def _moe(x1, w, config: dict, precision: str, tag=None):
    u, idx, wt, y = _moe_front(x1, w, config_key=_key(config),
                               precision=precision)
    idx, wt = np.asarray(idx), np.asarray(wt)
    if ROUTES is not None:
        ROUTES.append((tag, idx))
    lo, hi = (int(e) for e in config["experts_held"])
    for e in range(lo, hi):         # the experts held here, one at a time
        tok, slot = np.nonzero(idx == e)
        if not len(tok):
            continue
        n = max(EXPERT_BUCKET, 1 << (len(tok) - 1).bit_length())
        rows = np.zeros(n, np.int32)
        rows[:len(tok)] = tok
        wts = np.zeros(n, np.float32)
        wts[:len(tok)] = wt[tok, slot]
        y = _expert_add(y, u, jnp.asarray(rows), jnp.asarray(wts),
                        w["gate"][e - lo], w["up"][e - lo],
                        w["down"][e - lo], precision=precision)
    return y


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _head(x, norm_f, head, *, eps, precision):
    return mm(rmsnorm(x, norm_f, eps), head, precision)


def _key(config: dict):
    """The configuration's numbers as a hashable static argument."""
    keep = ("num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "kv_lora_rank", "rms_norm_eps", "n_group",
            "topk_group", "num_experts_per_tok", "norm_topk_prob",
            "routed_scaling_factor", "rope_theta")
    out = tuple((k, config[k]) for k in keep)
    r = config.get("rope_scaling") or {}
    return out + (("rope_scaling", tuple(sorted(r.items()))),)


def _unkey(config_key) -> dict:
    d = dict(config_key)
    d["rope_scaling"] = dict(d["rope_scaling"])
    return d


# ------------------------------------------------------------ the blocks


def _layer(x, w, config, l, pos0, prior, precision, fault, ffn=True,
           tag=None):
    """Layer ``l`` over a block ``x [T, H]`` at positions ``pos0 ..``
    after ``prior = (c, pe)`` (the keys at positions ``0 .. pos0 - 1``).
    Returns ``(x, c, pe)``; ``tag`` names the block for :data:`ROUTES`."""
    cos, sin = cos_sin(config, np.arange(pos0, pos0 + x.shape[0]))
    x, c, pe = _attention(x, w, cos, sin, *prior, config_key=_key(config),
                          precision=precision, fault=fault)
    if ffn:
        if l < int(config["first_k_dense_replace"]):
            x = _dense(x, w, eps=float(config["rms_norm_eps"]),
                       precision=precision)
        else:
            x = _moe(x, w, config, precision, (tag, l))
    return x, c, pe


def forward_blocks(config: dict, seed: int, document: np.ndarray,
                   suffixes: Sequence[np.ndarray],
                   rows: Sequence[np.ndarray], precision: str = "float32",
                   fault=None) -> List[np.ndarray]:
    """The full forward pass over ``document`` followed by each suffix, in
    blocks: the logits ``[len(rows[s]), vocab]`` at the positions
    ``rows[s]`` of suffix ``s`` (0 its first token). Suffixes are padded to
    one length (the padding lies after every row asked for)."""
    kl, dr = int(config["kv_lora_rank"]), int(config["qk_rope_head_dim"])
    t = len(document)
    u = _bucket(max(len(s) for s in suffixes), SUFFIX_BUCKET)
    table = weights.token_table(config, seed, jnp.float32)
    x_doc = table[jnp.asarray(document)]
    xs = [table[jnp.asarray(np.pad(s, (0, u - len(s))))] for s in suffixes]
    del table
    none = (jnp.zeros((0, kl), jnp.float32), jnp.zeros((0, dr), jnp.float32))
    layers = int(config["num_hidden_layers"])
    with jax.default_matmul_precision("highest"):
        for l in range(layers):
            w = weights.layer(config, seed, l)
            x_doc, c, pe = _layer(x_doc, w, config, l, 0, none, precision,
                                  fault, ffn=l < layers - 1, tag="doc")
            xs = [_layer(x, w, config, l, t, (c, pe), precision, fault,
                         tag=j)[0]
                  for j, x in enumerate(xs)]
            del w, c, pe
        del x_doc
        norm_f = weights.top(config, seed, "norm_f")
        head = weights.top(config, seed, "head")
        eps = float(config["rms_norm_eps"])
        out = []
        for x, r in zip(xs, rows):
            at = np.zeros(_bucket(len(r), ROW_BUCKET), np.int32)
            at[:len(r)] = r
            out.append(np.asarray(_head(x[jnp.asarray(at)], norm_f, head,
                                        eps=eps, precision=precision))[
                                            :len(r)])
        return out


def session_logits(config: dict, seed: int, documents: np.ndarray,
                   sessions: Dict[int, Tuple[int, np.ndarray, np.ndarray]],
                   precision: str = "float32", fault=None
                   ) -> Dict[int, np.ndarray]:
    """``sessions[s] = (document, suffix tokens, rows)`` -> the logits at
    the rows of each session's suffix, a document's sessions together."""
    t0 = time.perf_counter()
    out, laps = {}, []
    for d in sorted({v[0] for v in sessions.values()}):
        t1 = time.perf_counter()
        mine = sorted(s for s, v in sessions.items() if v[0] == d)
        got = forward_blocks(config, seed, documents[d],
                             [sessions[s][1] for s in mine],
                             [sessions[s][2] for s in mine], precision, fault)
        out.update(zip(mine, got))
        laps.append(f"document {d} with {len(mine)} sessions "
                    f"({sum(len(sessions[s][1]) for s in mine)} suffix "
                    f"tokens) {time.perf_counter() - t1:.1f} s")
    print(f"reference ({precision}{', ' + fault if fault else ''}): "
          f"{len(sessions)} sessions in {time.perf_counter() - t0:.1f} s: "
          + "; ".join(laps), file=sys.stderr)
    return out
