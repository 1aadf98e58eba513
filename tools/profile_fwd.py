"""Bisect the ragged forward chain (gather -> scatter combine -> postprocess)
to find where the 10x-over-op-model time goes (VERDICT r3 Weak #2).

Each stage is timed as one jitted program at the bench's exact shapes, with
the slab passed as an argument. Stages:

  g        : gather only
  gs       : gather + sentinel scatter-add combine (fused as XLA likes)
  gs_bar   : same with an optimization_barrier between gather and scatter
  gs_where : gs + mean-where + counts divide
  full     : gs_where + transpose + astype(bf16)  (the real forward tail)
  send     : _build_send_blocks-style concat + slice + decode in front

Usage: python tools/profile_fwd.py [stage ...]
"""

import sys

import jax
import jax.numpy as jnp
import numpy as np

import _profcommon as pc
from _profcommon import slope

CAP_SIZES = pc.CAP_SIZES
B = 16384
N = 26
W = 128


def main(stages):
    rng = np.random.default_rng(0)
    rows_total = sum(CAP_SIZES)

    hots = rng.integers(1, 31, size=(N, B))
    splits = np.zeros((N, B + 1), np.int64)
    np.cumsum(hots, axis=1, out=splits[:, 1:])
    cap = int(splits[:, -1].max())
    print(f"cap={cap} stream={N*cap}", flush=True)

    vals_np = np.zeros((N, cap), np.int32)
    offs = np.zeros(N, np.int64)
    o = 0
    for i, s in enumerate(CAP_SIZES):
        nnz = int(splits[i, -1])
        u = rng.random(nnz)
        vals_np[i, :nnz] = np.minimum((u ** 3 * s).astype(np.int64), s - 1)
        offs[i] = o
        o += s

    grows = jnp.asarray(vals_np) + jnp.asarray(
        offs.astype(np.int32))[:, None]
    lens = jnp.asarray((splits[:, 1:] - splits[:, :-1]).astype(np.int32))
    slab = jnp.zeros((rows_total, W), jnp.float32) + 0.5

    def seg_of(lens_):
        zero = jnp.zeros((N, 1), lens_.dtype)
        sp = jnp.concatenate([zero, jnp.cumsum(lens_, axis=1)], axis=1)
        return jax.vmap(lambda s: jnp.searchsorted(
            s, jnp.arange(cap, dtype=s.dtype), side="right") - 1)(sp)

    seg_const = seg_of(lens)
    sidx_const = jnp.arange(N)[:, None] * (B + 1) + seg_const

    def combine(gath, sidx):
        buf = jnp.zeros((N * (B + 1), W), gath.dtype)
        buf = buf.at[sidx.reshape(-1)].add(
            gath.reshape(-1, W), indices_are_sorted=True)
        return buf.reshape(N, B + 1, W)[:, :B, :]

    def want(s):
        return not stages or s in stages

    if want("g"):
        def mk(k):
            def f(sl, ids):
                acc = jnp.float32(0)
                for _ in range(k):
                    g = jnp.take(sl, ids.reshape(-1), axis=0, mode="clip")
                    acc = acc + g[0, 0] + g[-1, -1]
                    ids = ids + jnp.int32(acc - acc)
                return acc
            return f
        print(f"g: {slope(mk, (slab, grows)):.1f} ms", flush=True)

    if want("gs"):
        def mk(k):
            def f(sl, ids, sidx):
                acc = jnp.float32(0)
                for _ in range(k):
                    g = jnp.take(sl, ids.reshape(-1), axis=0,
                                 mode="clip").reshape(N, cap, W)
                    red = combine(g, sidx)
                    acc = acc + red[0, 0, 0] + red[-1, -1, -1]
                    ids = ids + jnp.int32(acc - acc)
                return acc
            return f
        print(f"gs (fused): {slope(mk, (slab, grows, sidx_const)):.1f} ms",
              flush=True)

    if want("gs_bar"):
        def mk(k):
            def f(sl, ids, sidx):
                acc = jnp.float32(0)
                for _ in range(k):
                    g = jnp.take(sl, ids.reshape(-1), axis=0,
                                 mode="clip").reshape(N, cap, W)
                    g = jax.lax.optimization_barrier(g)
                    red = combine(g, sidx)
                    acc = acc + red[0, 0, 0] + red[-1, -1, -1]
                    ids = ids + jnp.int32(acc - acc)
                return acc
            return f
        print(f"gs_bar (barrier): {slope(mk, (slab, grows, sidx_const)):.1f} "
              "ms", flush=True)

    if want("gs_where"):
        counts = jnp.maximum(lens, 1)

        def mk(k):
            def f(sl, ids, sidx, cnt):
                acc = jnp.float32(0)
                mean = jnp.zeros((N,), jnp.float32)
                for _ in range(k):
                    g = jnp.take(sl, ids.reshape(-1), axis=0,
                                 mode="clip").reshape(N, cap, W)
                    red = combine(g, sidx)
                    red = jnp.where(mean[:, None, None] > 0,
                                    red / cnt[..., None].astype(red.dtype),
                                    red)
                    acc = acc + red[0, 0, 0] + red[-1, -1, -1]
                    ids = ids + jnp.int32(acc - acc)
                return acc
            return f
        print(f"gs_where: {slope(mk, (slab, grows, sidx_const, counts)):.1f} "
              "ms", flush=True)

    if want("full"):
        counts = jnp.maximum(lens, 1)

        def mk(k):
            def f(sl, ids, sidx, cnt):
                acc = jnp.float32(0)
                mean = jnp.zeros((N,), jnp.float32)
                for _ in range(k):
                    g = jnp.take(sl, ids.reshape(-1), axis=0,
                                 mode="clip").reshape(1, N, cap, W)
                    red = combine(g.reshape(N, cap, W), sidx)
                    red = red.reshape(1, N, B, W)
                    red = jnp.where(mean[None, :, None, None] > 0,
                                    red / cnt[None, ..., None].astype(
                                        red.dtype), red)
                    out = red.transpose(0, 2, 1, 3).reshape(
                        1, B, N * W).astype(jnp.bfloat16)
                    acc = acc + out[0, 0, 0].astype(jnp.float32)
                    ids = ids + jnp.int32(acc - acc)
                return acc
            return f
        print(f"full tail: {slope(mk, (slab, grows, sidx_const, counts)):.1f}"
              " ms", flush=True)

    if want("send_cs"):
        # candidate fix: seg via scatter-ones + cumsum instead of searchsorted
        blen = cap + B

        def seg_cs(lens_):
            ends = jnp.cumsum(lens_, axis=1)  # [N, B] ascending
            marks = jnp.zeros((N, cap + 1), jnp.int32)
            marks = marks.at[
                jnp.arange(N, dtype=jnp.int32)[:, None],
                jnp.clip(ends, 0, cap)].add(1, indices_are_sorted=True)
            return jnp.cumsum(marks[:, :cap], axis=1)

        def mk(k):
            def f(sl, ids, lens_):
                acc = jnp.float32(0)
                for _ in range(k):
                    parts = []
                    for i in range(N):
                        parts.append(ids[i])
                        parts.append(lens_[i])
                    blk = jnp.concatenate(parts).reshape(1, N * blen)
                    r3 = blk.reshape(1, N, blen)
                    values = r3[0, :, :cap]
                    ln = r3[0, :, cap:]
                    seg = seg_cs(ln)
                    sidx = jnp.arange(N)[:, None] * (B + 1) + seg
                    g = jnp.take(sl, values.reshape(-1), axis=0,
                                 mode="clip").reshape(N, cap, W)
                    red = combine(g, sidx)
                    acc = acc + red[0, 0, 0] + red[-1, -1, -1]
                    ids = ids + jnp.int32(acc - acc)
                return acc
            return f
        print(f"send_cs (cumsum seg): {slope(mk, (slab, grows, lens)):.1f} "
              "ms", flush=True)

    if want("send"):
        # the real front: concat values+lengths into [1, l_max] then decode
        blen = cap + B

        def mk(k):
            def f(sl, ids, lens_):
                acc = jnp.float32(0)
                for _ in range(k):
                    parts = []
                    for i in range(N):
                        parts.append(ids[i])
                        parts.append(lens_[i])
                    blk = jnp.concatenate(parts).reshape(1, N * blen)
                    r3 = blk.reshape(1, N, blen)
                    values = r3[0, :, :cap]
                    ln = r3[0, :, cap:]
                    seg = seg_of(ln)
                    sidx = jnp.arange(N)[:, None] * (B + 1) + seg
                    g = jnp.take(sl, values.reshape(-1), axis=0,
                                 mode="clip").reshape(N, cap, W)
                    red = combine(g, sidx)
                    acc = acc + red[0, 0, 0] + red[-1, -1, -1]
                    ids = ids + jnp.int32(acc - acc)
                return acc
            return f
        print(f"send+decode+gs: {slope(mk, (slab, grows, lens)):.1f} ms",
              flush=True)


if __name__ == "__main__":
    pc.ensure_backend()
    main(sys.argv[1:])
