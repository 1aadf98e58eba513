"""Full DLRMDense fwd/bwd/SGD step at bench shapes: current dot_interact
(gram[:, li, lj] static gather) vs a select-matmul lower-triangle
extraction (MXU-friendly [F*F, P] 0/1 matmul).

Usage: python tools/profile_dense.py [current|matmul]
"""

import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax

import _profcommon as pc  # repo on sys.path + process set-up
import distributed_embeddings_tpu.models.dlrm as dlrm_mod
from bench import BATCH, make_cfg, timed_loop


def dot_interact_mm(emb_outs, bottom_mlp_out):
    feats = jnp.stack([bottom_mlp_out] + list(emb_outs), axis=1)
    gram = jnp.einsum("bfd,bgd->bfg", feats, feats)
    f = feats.shape[1]
    li, lj = np.tril_indices(f, k=-1)
    sel = np.zeros((f * f, len(li)), np.float32)
    sel[li * f + lj, np.arange(len(li))] = 1.0
    lower = gram.reshape(gram.shape[0], f * f) @ jnp.asarray(sel, gram.dtype)
    return jnp.concatenate([lower, bottom_mlp_out], axis=1)


def run(batch):
    cfg = make_cfg([100] * 26, jnp.bfloat16)
    dense = dlrm_mod.DLRMDense(cfg)
    tx = optax.sgd(0.005)
    rng = np.random.default_rng(0)
    num = jnp.asarray(rng.normal(size=(batch, 13)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, 2, size=(batch, 1)), jnp.float32)
    embs = [jnp.asarray(rng.normal(size=(batch, 128)), jnp.bfloat16)
            for _ in range(26)]
    params = dense.init(jax.random.key(0), num[:2], [e[:2] for e in embs])
    opt_state = tx.init(params)

    def step(state, embs_, batch_):
        params, opt_state = state
        n, y = batch_

        def loss_fn(p):
            return dlrm_mod.bce_with_logits(dense.apply(p, n, embs_), y)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return loss, (optax.apply_updates(params, updates), opt_state)

    dt = timed_loop(jax.jit(step, donate_argnums=(0,)),
                    (params, opt_state), (embs, (num, labels)), iters=20)
    return dt * 1e3


if __name__ == "__main__":
    pc.ensure_backend()
    which = sys.argv[1] if len(sys.argv) > 1 else "current"
    if which == "matmul":
        dlrm_mod.dot_interact = dot_interact_mm
    t0 = time.time()
    print(f"{which} dot_interact dense step: {run(BATCH):.1f} ms "
          f"(compile+run {time.time()-t0:.0f}s)", flush=True)
