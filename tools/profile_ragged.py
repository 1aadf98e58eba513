"""Phase profile of the multi-hot ragged DLRM step (VERDICT r3 Weak #2).

Times each phase of the ragged path at the bench's exact shapes
(batch 16384, 26 features, hotness 1..30 mean 15.5, capped Criteo-Kaggle
vocabs, fp32 params / bf16 compute) with the in-jit repetition-slope
methodology of ``_profcommon.slope``. All large buffers are jit
*arguments* (a captured constant would be baked into the program and
re-uploaded per compile).

Usage: python tools/profile_ragged.py [phase ...]
"""

import sys

import jax
import jax.numpy as jnp
import numpy as np

import _profcommon as pc
from _profcommon import slope, slope_donate

CAP_SIZES = pc.CAP_SIZES
B = 16384
N = 26
HOT_MEAN = 15
W = 128


def main(phases):
    rng = np.random.default_rng(0)
    rows_total = sum(CAP_SIZES)
    print(f"slab rows={rows_total} ({rows_total*W*4/1e9:.1f} GB fp32)",
          flush=True)

    draws = []
    for s in CAP_SIZES:
        hots = rng.integers(1, 2 * HOT_MEAN + 1, size=B)
        splits = np.zeros(B + 1, np.int64)
        np.cumsum(hots, out=splits[1:])
        draws.append((s, splits))
    cap = max(int(sp[-1]) for _, sp in draws)
    print(f"cap={cap} total_rows={N*cap}", flush=True)

    vals_np = np.zeros((N, cap), np.int32)
    lens_np = np.zeros((N, B), np.int32)
    offs = np.zeros(N, np.int64)
    o = 0
    for i, (s, splits) in enumerate(draws):
        nnz = int(splits[-1])
        u = rng.random(nnz)
        vals_np[i, :nnz] = np.minimum((u ** 3 * s).astype(np.int64), s - 1)
        lens_np[i] = np.diff(splits)
        offs[i] = o
        o += s

    dev_lens = jnp.asarray(lens_np)
    grows = jnp.asarray(vals_np) + jnp.asarray(
        offs.astype(np.int32))[:, None]  # [N, cap] global rows
    need_slab = {"gather", "opt_scatter", "opt_scatter_sorted", None}
    slab = (jnp.zeros((rows_total, W), jnp.float32) + 0.5
            if (not phases or set(phases) & need_slab) else None)

    def seg_ss(lens):
        zero = jnp.zeros((N, 1), lens.dtype)
        splits = jnp.concatenate([zero, jnp.cumsum(lens, axis=1)], axis=1)
        return jax.vmap(lambda sp: jnp.searchsorted(
            sp, jnp.arange(cap, dtype=sp.dtype), side="right") - 1)(splits)

    def want(p):
        return not phases or p in phases

    if want("seg_ss"):
        def mk(k):
            def f(lens):
                s = jnp.int32(0)
                for _ in range(k):
                    seg = seg_ss(lens)
                    s = s + seg[0, 0] + seg[-1, -1]
                    lens = lens + (s - s)
                return s
            return f
        print(f"seg searchsorted: {slope(mk, (dev_lens,)):.1f} ms",
              flush=True)

    if want("gather"):
        def mk(k):
            def f(sl, ids):
                s = jnp.float32(0)
                for _ in range(k):
                    g = jnp.take(sl, ids.reshape(-1), axis=0, mode="clip")
                    s = s + g[0, 0] + g[-1, -1]
                    ids = ids + jnp.int32(s - s)
                return s
            return f
        print(f"fwd gather ({N*cap} rows): {slope(mk, (slab, grows)):.1f} ms",
              flush=True)

    if want("combine_sc") or want("combine_cs") or want("bwd_take"):
        seg = seg_ss(dev_lens)
        sidx = (jnp.arange(N)[:, None] * (B + 1) + seg)
        gath = jnp.zeros((N, cap, W), jnp.float32) + 0.5
        zero = jnp.zeros((N, 1), dev_lens.dtype)
        splits = jnp.concatenate(
            [zero, jnp.cumsum(dev_lens, axis=1)], axis=1).astype(jnp.int32)

    if want("combine_sc"):
        def mk(k):
            def f(g, si):
                s = jnp.float32(0)
                for _ in range(k):
                    buf = jnp.zeros((N * (B + 1), W), g.dtype)
                    buf = buf.at[si.reshape(-1)].add(
                        g.reshape(-1, W), indices_are_sorted=True)
                    red = buf.reshape(N, B + 1, W)[:, :B, :]
                    s = s + red[0, 0, 0] + red[-1, -1, -1]
                    g = g + (s - s)
                return s
            return f
        print(f"combine scatter-add fp32: {slope(mk, (gath, sidx)):.1f} ms",
              flush=True)

    if want("combine_cs"):
        def mk(k):
            def f(g, sp):
                s = jnp.float32(0)
                for _ in range(k):
                    pref = jnp.cumsum(g, axis=1)  # [N, cap, W]
                    pz = jnp.concatenate(
                        [jnp.zeros((N, 1, W), pref.dtype), pref], axis=1)
                    hi = jnp.take_along_axis(pz, sp[:, 1:, None], axis=1)
                    lo = jnp.take_along_axis(pz, sp[:, :-1, None], axis=1)
                    red = hi - lo
                    s = s + red[0, 0, 0] + red[-1, -1, -1]
                    g = g + (s - s)
                return s
            return f
        print(f"combine cumsum-prefix fp32: {slope(mk, (gath, splits)):.1f} "
              "ms", flush=True)

    if want("bwd_take"):
        grad = jnp.zeros((N, B, W), jnp.bfloat16) + 0.25

        def mk(k):
            def f(g, si):
                s = jnp.float32(0)
                for _ in range(k):
                    gpad = jnp.concatenate(
                        [g, jnp.zeros((N, 1, W), g.dtype)], axis=1)
                    vals = jnp.take(gpad.reshape(-1, W), si.reshape(-1),
                                    axis=0)
                    s = s + vals[0, 0].astype(jnp.float32)
                    g = g + (s - s).astype(g.dtype)
                return s
            return f
        print(f"bwd grad take bf16: {slope(mk, (grad, sidx)):.1f} ms",
              flush=True)

    if want("opt_scatter"):
        upd = jnp.zeros((N * cap, W), jnp.float32) + 1e-4

        def mk(k):
            def f(sl, ids, u):
                s = jnp.float32(0)
                for _ in range(k):
                    sl = sl.at[ids.reshape(-1)].add(u)
                    s = s + sl[0, 0]
                return s, sl
            return f
        print(f"opt scatter ({N*cap} rows, unsorted): "
              f"{slope_donate(mk, (slab, grows, upd)):.1f} ms", flush=True)

    if want("opt_scatter_sorted"):
        sflat = jnp.asarray(np.sort(np.asarray(grows).reshape(-1)))
        upd = jnp.zeros((N * cap, W), jnp.float32) + 1e-4

        def mk(k):
            def f(sl, ids, u):
                s = jnp.float32(0)
                for _ in range(k):
                    sl = sl.at[ids].add(u, indices_are_sorted=True)
                    s = s + sl[0, 0]
                return s, sl
            return f
        print("opt scatter sorted: "
              f"{slope_donate(mk, (slab, sflat, upd)):.1f} ms", flush=True)


if __name__ == "__main__":
    pc.ensure_backend()
    main(sys.argv[1:])
