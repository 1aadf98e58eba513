"""Absorbed latent attention over a per-session latent cache, as a Pallas TPU
kernel: flash decoding over the sessions' ragged lengths.

Entry ``b`` holds ``R`` query rows, ``heads`` rows a query, its queries at
consecutive positions: ``q_c [R, kl]`` (the absorbed ``q_nope W_uk^T``) and
``q_pe [R, dr]``. It reads cache slot ``slots[b]``, rows ``c [C, kl]`` and
``pe [C, dr]``; row ``r`` (query ``r // heads``) sees the cache rows at
positions below ``lengths[b] + r // heads``. Scores ``(q_c c^T + q_pe pe^T)
* scale``, a softmax over them, and ``sum_s p_s c_s``: ``[R, kl]`` float32.
A decode step is one query an entry, every session slot an entry (an idle
slot has length 0); a prompt chunk is one entry of its queries.

The grid is ``(entries, R / rows, C / block)``: a key block is read once for
each block of ``rows`` query rows, and a block past what the rows see is
neither read (its index is held at the last block they see, so no copy is
issued) nor computed. An entry of length 0 comes back as zeros. The scores
and the softmax stay on the chip: the cache's bytes are the traffic.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_F32 = jnp.float32


def _kernel(slot_ref, len_ref, qc_ref, qpe_ref, c_ref, pe_ref, o_ref, m_sc,
            l_sc, acc_sc, *, block: int, rows: int, heads: int,
            scale: float):
    del slot_ref
    b, r, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    n = len_ref[b]
    q0 = r * (rows // heads)                    # the row block's first query
    seen = n + q0 + rows // heads - 1           # what its last query sees

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full(m_sc.shape, -jnp.inf, _F32)
        l_sc[...] = jnp.zeros(l_sc.shape, _F32)
        acc_sc[...] = jnp.zeros(acc_sc.shape, _F32)

    @pl.when((n > 0) & (j * block < seen))
    def _block():
        dims = (((1,), (1,)), ((), ()))
        c = c_ref[0]
        s = lax.dot_general(qc_ref[0], c, dims, preferred_element_type=_F32)
        s = s + lax.dot_general(qpe_ref[0], pe_ref[0], dims,
                                preferred_element_type=_F32)
        s = s * scale
        col = j * block + lax.broadcasted_iota(jnp.int32, s.shape, 1)
        row_q = q0 + lax.broadcasted_iota(jnp.int32, s.shape, 0) // heads
        s = jnp.where(col < n + row_q, s, -jnp.inf)
        m_prev = m_sc[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_sc[...] = alpha * l_sc[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_sc[...] = alpha * acc_sc[...] + jnp.dot(
            p.astype(c.dtype), c, preferred_element_type=_F32)
        m_sc[...] = m_new

    @pl.when(j == pl.num_programs(2) - 1)
    def _out():
        l = l_sc[...]
        o_ref[0] = jnp.where(l > 0, acc_sc[...] / jnp.where(l > 0, l, 1.0),
                             0.0).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("heads", "scale", "block",
                                             "rows", "interpret"))
def latent_attention(q_c, q_pe, cache_c, cache_pe, slots, lengths, *,
                     heads: int, scale: float, block: int = 1024,
                     rows: int = 512, interpret: bool = False):
    """``q_c [B, R, kl]``, ``q_pe [B, R, dr]``, ``cache_c [S, C, kl]``,
    ``cache_pe [S, C, dr]`` (one dtype, bfloat16 on the chip), ``slots
    [B]`` and ``lengths [B]`` int32 -> ``[B, R, kl]`` float32."""
    nb, nr, kl = q_c.shape
    cap, dr = cache_c.shape[1], cache_pe.shape[2]
    block, rows = min(block, cap), min(rows, nr)
    if cap % block or nr % rows or rows % heads:
        raise ValueError(f"{nr} rows in blocks of {rows} ({heads} a query) "
                         f"over {cap} cache rows in blocks of {block}")

    def kv_map(b, r, j, slot, lens):
        seen = lens[b] + (r + 1) * (rows // heads) - 1
        return slot[b], jnp.minimum(j, jnp.maximum(seen - 1, 0) // block), 0

    def q_map(b, r, j, slot, lens):
        return b, r, 0

    return pl.pallas_call(
        functools.partial(_kernel, block=block, rows=rows, heads=heads,
                          scale=scale),
        out_shape=jax.ShapeDtypeStruct((nb, nr, kl), _F32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(nb, nr // rows, cap // block),
            in_specs=[pl.BlockSpec((1, rows, kl), q_map),
                      pl.BlockSpec((1, rows, dr), q_map),
                      pl.BlockSpec((1, block, kl), kv_map),
                      pl.BlockSpec((1, block, dr), kv_map)],
            out_specs=pl.BlockSpec((1, rows, kl), q_map),
            scratch_shapes=[pltpu.VMEM((rows, 1), _F32),
                            pltpu.VMEM((rows, 1), _F32),
                            pltpu.VMEM((rows, kl), _F32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(slots.astype(jnp.int32), lengths.astype(jnp.int32), q_c, q_pe,
      cache_c, cache_pe)
