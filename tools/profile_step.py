"""End-to-end phase split of the ragged DLRM step (VERDICT r3 Weak #2/#4).

Splits the bench's ragged variant into dispatch overhead / embedding fwd /
dense fwd+bwd / sparse apply by timing nested subsets with bench.py's
threaded-state ``timed_loop``.

Usage: python tools/profile_step.py [ragged|dense] [batch]
"""

import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax

import _profcommon as pc  # repo on sys.path + process set-up
from bench import BATCH, build_state, make_cfg, timed_loop
from _profcommon import CAP, CRITEO_KAGGLE_SIZES
from distributed_embeddings_tpu.models.dlrm import DLRMDense, bce_with_logits
from distributed_embeddings_tpu.ops.embedding_lookup import Ragged
from distributed_embeddings_tpu.parallel import (
    DistributedEmbedding, SparseSGD, make_hybrid_train_step)
from distributed_embeddings_tpu.utils import power_law_ids


def main():
    variant = sys.argv[1] if len(sys.argv) > 1 else "ragged"
    batch = int(sys.argv[2]) if len(sys.argv) > 2 else (
        16384 if variant == "ragged" else BATCH)
    # optional 3rd arg: parameter dtype (the bench headline is bf16 params)
    param_dtype = (jnp.bfloat16 if len(sys.argv) > 3
                   and sys.argv[3] == "bf16" else jnp.float32)
    # optional 4th arg: "uncapped" profiles the full Criteo-Kaggle vocabs
    uncapped = len(sys.argv) > 4 and sys.argv[4] == "uncapped"
    table_sizes = (list(CRITEO_KAGGLE_SIZES) if uncapped
                   else [min(s, CAP) for s in CRITEO_KAGGLE_SIZES])
    cfg = make_cfg(table_sizes, jnp.bfloat16)
    combiner = "sum" if variant == "ragged" else None
    de = DistributedEmbedding(cfg.embedding_configs(combiner=combiner),
                              world_size=1, compute_dtype=jnp.bfloat16)
    dense = DLRMDense(cfg)
    emb_opt = SparseSGD()
    tx = optax.sgd(0.005)

    rng = np.random.default_rng(0)
    if variant == "ragged":
        draws = []
        for s in table_sizes:
            hots = rng.integers(1, 31, size=batch)
            splits = np.zeros(batch + 1, np.int32)
            np.cumsum(hots, out=splits[1:])
            draws.append((s, splits))
        cap = max(int(sp[-1]) for _, sp in draws)
        cats = []
        for s, splits in draws:
            nnz = int(splits[-1])
            vals = np.zeros(cap, np.int32)
            vals[:nnz] = power_law_ids(rng, s, (nnz,))
            cats.append(Ragged(values=jnp.asarray(vals),
                               row_splits=jnp.asarray(splits)))
    else:
        cats = [jnp.asarray(power_law_ids(rng, s, (batch,)), jnp.int32)
                for s in table_sizes]

    state, num, labels = build_state(de, dense, cfg, emb_opt, tx,
                                     table_sizes, param_dtype, batch=batch)

    def loss_fn(dp, emb_outs, batch_):
        n, y = batch_
        return bce_with_logits(dense.apply(dp, n, emb_outs), y)

    # --- 0: dispatch floor (trivial jitted fn, threaded) ------------------
    @jax.jit
    def trivial(s, cats_, b_):
        return s.reshape(-1)[0] * 1.0001, s

    # a SMALL threaded state: threading a full slab would allocate a
    # second slab-sized output per call (no donation here) and OOM the
    # uncapped variant
    dt0 = timed_loop(trivial, jnp.zeros((128,), jnp.float32),
                     (cats, (num, labels)), iters=12)
    print(f"dispatch floor: {dt0*1e3:.1f} ms", flush=True)

    # Phases 1-2 thread a small token through the *inputs* (ids depend on
    # the previous iteration's output scalar) so dispatches can't
    # short-circuit, while params stay read-only — threading the params
    # themselves (v + bump) was measured to distort the phase by seconds.
    def _dep_cats(cats_, tok):
        bump = (tok * 0).astype(jnp.int32)

        def dep(c):
            if hasattr(c, "values"):  # Ragged
                return type(c)(values=c.values + bump,
                               row_splits=c.row_splits)
            return c + bump
        return [dep(c) for c in cats_]

    # --- 1: embedding forward only ---------------------------------------
    @jax.jit
    def fwd_only(tok, emb_params, cats_):
        outs, _ = de.forward_with_residuals(emb_params,
                                            _dep_cats(cats_, tok))
        tok2 = outs[0].astype(jnp.float32)[0, 0]
        return tok2, tok2

    # params are read-only in phases 1-2: reuse state's slabs (a second
    # de.init copy would double embedding HBM and can OOM)
    emb_params = state.emb_params
    dt1 = timed_loop(fwd_only, jnp.float32(0), (emb_params, cats), iters=8)
    print(f"embedding fwd: {dt1*1e3:.1f} ms (minus dispatch "
          f"{dt0*1e3:.0f})", flush=True)

    # --- 2: fwd + dense fwd/bwd (no sparse apply) -------------------------
    @jax.jit
    def fwd_dense(tok, emb_params, dp, cats_, batch_):
        outs, _ = de.forward_with_residuals(emb_params,
                                            _dep_cats(cats_, tok))
        loss, (dg, og) = jax.value_and_grad(loss_fn, argnums=(0, 1))(
            dp, outs, batch_)
        # the backward must feed the output or XLA dead-code-eliminates it
        gsum = sum(jnp.sum(g.astype(jnp.float32)) for g in og)
        gsum = gsum + jax.tree.reduce(
            lambda a, g: a + jnp.sum(g.astype(jnp.float32)), dg, 0.0)
        tok2 = loss + gsum * 1e-12
        return tok2, tok2

    dt2 = timed_loop(fwd_dense, jnp.float32(0),
                     (emb_params, state.dense_params, cats, (num, labels)),
                     iters=8)
    print(f"fwd + dense f/b: {dt2*1e3:.1f} ms", flush=True)

    # --- 3: full step -----------------------------------------------------
    step_fn = make_hybrid_train_step(de, loss_fn, tx, emb_opt,
                                     lr_schedule=0.005,
                                     with_metrics=False)
    dt3 = timed_loop(step_fn, state, (cats, (num, labels)), iters=8)
    print(f"full step: {dt3*1e3:.1f} ms -> {batch/dt3:.0f} samples/s",
          flush=True)
    print(f"phases: dispatch {dt0*1e3:.0f} | emb fwd {(dt1-dt0)*1e3:.0f} | "
          f"dense f/b {(dt2-dt1)*1e3:.0f} | sparse apply "
          f"{(dt3-dt2)*1e3:.0f}", flush=True)


if __name__ == "__main__":
    pc.ensure_backend()
    main()
