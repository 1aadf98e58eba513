"""Bisect the DLRM dense-variant sparse apply (66 ms measured in the step
phase split): how much is the unavoidable SGD scatter, how much is glue
(grad assembly / broadcast / concat / cast)?

Usage: python tools/profile_apply.py
"""

import jax
import jax.numpy as jnp
import numpy as np

import _profcommon as pc
from _profcommon import slope_donate

CAP_SIZES = pc.CAP_SIZES
B = 65536
N = 26
W = 128


def main():
    rng = np.random.default_rng(0)
    rows_total = sum(CAP_SIZES)
    offs = np.concatenate([[0], np.cumsum(CAP_SIZES)[:-1]]).astype(np.int64)
    ids_np = np.zeros((N, B), np.int64)
    for i, s in enumerate(CAP_SIZES):
        u = rng.random(B)
        ids_np[i] = np.minimum((u ** 3 * s).astype(np.int64), s - 1) + offs[i]
    ids = jnp.asarray(ids_np.reshape(-1).astype(np.int32))  # [N*B]

    def fresh_slab():  # each phase donates (and so deletes) its slab
        return jnp.zeros((rows_total, W), jnp.float32) + 0.5

    vals_bf16 = jnp.zeros((N * B, W), jnp.bfloat16) + 1e-3

    # (a) raw scatter, fp32 updates
    def mk_a(k):
        def f(sl, ids_, v):
            s = jnp.float32(0)
            for _ in range(k):
                sl = sl.at[ids_].add(v.astype(jnp.float32) * (1.0 + s * 0))
                s = s + sl[0, 0]
            return s, sl
        return f
    print(f"raw SGD scatter ({N*B} rows): "
          f"{slope_donate(mk_a, (fresh_slab(), ids, vals_bf16)):.1f} ms", flush=True)

    # (b) scatter from per-feature grad slices [N, B, W] bf16 with the
    # backward's broadcast/transpose/concat glue in front
    grad = jnp.zeros((B, N * W), jnp.bfloat16) + 1e-3  # mp_grad row layout

    def mk_b(k):
        def f(sl, ids_, g):
            s = jnp.float32(0)
            for _ in range(k):
                gsl = g.reshape(1, B, N, W).transpose(0, 2, 1, 3)
                vals = gsl.reshape(-1, W).astype(jnp.float32)
                sl = sl.at[ids_].add(vals * (1.0 + s * 0))
                s = s + sl[0, 0]
            return s, sl
        return f
    print("scatter + transpose/cast glue: "
          f"{slope_donate(mk_b, (fresh_slab(), ids, grad)):.1f} ms", flush=True)

    # (c) sorted-scatter comparison (pre-sorted ids, same payload)
    order = np.argsort(ids_np.reshape(-1), kind="stable")
    ids_s = jnp.asarray(ids_np.reshape(-1)[order].astype(np.int32))

    def mk_c(k):
        def f(sl, ids_, v):
            s = jnp.float32(0)
            for _ in range(k):
                sl = sl.at[ids_].add(v.astype(jnp.float32) * (1.0 + s * 0),
                                     indices_are_sorted=True)
                s = s + sl[0, 0]
            return s, sl
        return f
    print("pre-sorted scatter: "
          f"{slope_donate(mk_c, (fresh_slab(), ids_s, vals_bf16)):.1f} ms",
          flush=True)


if __name__ == "__main__":
    pc.ensure_backend()
    main()
