"""Training a DistributedEmbedding through plain Flax + optax.

The ecosystem-composability counterpart of the reference's Keras packaging
(its ``DistributedEmbedding`` is a ``tf.keras.layers.Layer`` dropped into a
stock ``model.fit``-style loop, ``dist_model_parallel.py:199-259``): here
:class:`~distributed_embeddings_tpu.layers.DistributedEmbeddingLayer` makes
the sharded tables a normal Flax parameter, so the whole model trains with
``flax.training.train_state`` + any optax transform — no sparse trainer, no
custom step builder.

This is the right tool when tables are modest (autodiff produces dense slab
gradients, so each step reads+writes whole slabs); for huge tables use
``parallel.make_hybrid_train_step`` with the sparse optimizers — the SAME
layer and parameter pytree, so you can switch without converting anything.

Run (any backend):
    python examples/flax_training/main.py
Mesh (8 virtual CPU devices):
    DETPU_FORCE_CPU_DEVICES=8 python examples/flax_training/main.py --mesh
Sparse optax (O(touched-rows) updates on one big table):
    python examples/flax_training/main.py --sparse
"""

import os
import sys

if os.environ.get("DETPU_FORCE_CPU_DEVICES"):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count="
        + os.environ["DETPU_FORCE_CPU_DEVICES"])

import flax.linen as nn
import jax

if os.environ.get("DETPU_FORCE_CPU_DEVICES"):
    jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp
import numpy as np
import optax
from flax.training import train_state

from distributed_embeddings_tpu.layers import DistributedEmbeddingLayer
from distributed_embeddings_tpu.parallel import DistributedEmbedding
from distributed_embeddings_tpu.utils import runtime

TABLE_SIZES = [1000, 5000, 20000, 800, 12000, 300, 9000, 2500]
EMBED_DIM = 16
BATCH = 256


class RecModel(nn.Module):
    """Embeddings -> concat -> 2-layer MLP; everything standard Flax."""

    de: DistributedEmbedding

    @nn.compact
    def __call__(self, cats):
        embs = DistributedEmbeddingLayer(de=self.de, name="embeddings")(cats)
        x = jnp.concatenate(embs, axis=-1)
        x = nn.relu(nn.Dense(64)(x))
        return nn.Dense(1)(x)


def sparse_optax_demo():
    """Third mode (``--sparse``): O(touched-rows) training of one BIG
    table under plain optax via ``parallel.sparse_optax`` — the reference
    op layer's IndexedSlices gradient (``embedding_lookup_ops.py:105-122``)
    without the hybrid trainer. Only the looked-up rows of the table and
    the Adagrad accumulator are read or written each step."""
    from distributed_embeddings_tpu.parallel import (
        apply_sparse_updates, sparse_rows_adagrad, sparse_value_and_grad)

    vocab, width, batch = 2_000_000, 32, 4096
    table = jnp.zeros((vocab, width), jnp.float32)
    dense = {"w": jnp.full((width, 1), 0.3, jnp.float32)}
    tx_dense = optax.adam(1e-2)
    tx_rows = sparse_rows_adagrad(1.0)

    def loss_fn(dp, outs, y):
        return jnp.mean((outs[0] @ dp["w"] - y) ** 2)

    f = sparse_value_and_grad(loss_fn, combiners=["sum"])

    import functools

    # donation is what lets the row scatters update the table and the
    # accumulator in place — without it every step copies both slabs
    @functools.partial(jax.jit, donate_argnums=(0, 2, 3))
    def step(table, dense, d_state, r_state, ids, y):
        loss, (dg, sg) = f(dense, [table], [ids], y)
        du, d_state = tx_dense.update(dg, d_state, dense)
        dense = optax.apply_updates(dense, du)
        ru, r_state = tx_rows.update(sg, r_state, [table])
        [table] = apply_sparse_updates([table], ru)
        return table, dense, d_state, r_state, loss

    d_state = tx_dense.init(dense)
    r_state = tx_rows.init([table])
    rng = np.random.default_rng(0)
    loss = None
    for i in range(60):
        ids = jnp.asarray(rng.integers(0, 50_000, size=(batch, 2)),
                          jnp.int32)
        y = jnp.ones((batch, 1), jnp.float32)
        table, dense, d_state, r_state, loss = step(
            table, dense, d_state, r_state, ids, y)
        if i % 20 == 0:
            print(f"step {i:3d} loss {float(loss):.4f}")
    print(f"final loss {float(loss):.4f}  (table {vocab:,} x {width}; "
          f"each step touches <= {batch * 2:,} rows)")


def main():
    runtime.ensure_compile_cache()
    if "--sparse" in sys.argv:
        return sparse_optax_demo()
    mesh_mode = "--mesh" in sys.argv
    world = len(jax.devices()) if mesh_mode else 1
    de = DistributedEmbedding(
        [{"input_dim": s, "output_dim": EMBED_DIM, "combiner": "sum"}
         for s in TABLE_SIZES],
        world_size=world, strategy="memory_balanced")
    model = RecModel(de=de)

    rng = np.random.default_rng(0)
    cats = [jnp.asarray(rng.integers(0, s, size=(BATCH, 4)), jnp.int32)
            for s in TABLE_SIZES]
    labels = jnp.asarray(rng.normal(size=(BATCH, 1)) * 0.1, jnp.float32)

    variables = model.init(jax.random.key(0), cats)
    ts = train_state.TrainState.create(
        apply_fn=model.apply, params=variables["params"],
        tx=optax.adam(1e-2))  # stock optax — that's the point

    if world == 1:
        @jax.jit
        def step(ts, cats, labels):
            def loss_fn(p):
                pred = ts.apply_fn({"params": p}, cats)
                return jnp.mean((pred - labels) ** 2)
            loss, grads = jax.value_and_grad(loss_fn)(ts.params)
            return ts.apply_gradients(grads=grads), loss

        for i in range(100):
            ts, loss = step(ts, cats, labels)
            if i % 20 == 0:
                print(f"step {i:3d} loss {float(loss):.6f}")
        print(f"final loss {float(loss):.6f}")
        return

    # mesh mode: same model — the slab params shard over the axis and the
    # executor runs inside shard_map; dense params stay replicated. Kept
    # stateless (SGD) for brevity; tests/test_flax_adapter.py shows the
    # same pattern with sharded optimizer state.
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.array(jax.devices()), ("data",))
    shard = NamedSharding(mesh, P("data"))
    repl = NamedSharding(mesh, P())

    params = {
        "embeddings": jax.tree.map(lambda a: jax.device_put(a, shard),
                                   ts.params["embeddings"]),
        "Dense_0": jax.tree.map(lambda a: jax.device_put(a, repl),
                                ts.params["Dense_0"]),
        "Dense_1": jax.tree.map(lambda a: jax.device_put(a, repl),
                                ts.params["Dense_1"]),
    }
    lr = 0.05

    def local_step(params, cats, labels):
        def loss_fn(p):
            pred = model.apply({"params": p}, cats)
            return jnp.mean((pred - labels) ** 2)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        # dense grads: average over shards; slab grads: local, 1/world
        params = {
            "embeddings": jax.tree.map(
                lambda p, g: p - lr * g / world,
                params["embeddings"], grads["embeddings"]),
            "Dense_0": jax.tree.map(
                lambda p, g: p - lr * jax.lax.pmean(g, "data"),
                params["Dense_0"], grads["Dense_0"]),
            "Dense_1": jax.tree.map(
                lambda p, g: p - lr * jax.lax.pmean(g, "data"),
                params["Dense_1"], grads["Dense_1"]),
        }
        return params, jax.lax.pmean(loss, "data")

    pspec = {"embeddings": P("data"), "Dense_0": P(), "Dense_1": P()}
    step = jax.jit(jax.shard_map(
        local_step, mesh=mesh,
        in_specs=(pspec, P("data"), P("data")),
        out_specs=(pspec, P())))

    cats_sh = [jax.device_put(c, shard) for c in cats]
    labels_sh = jax.device_put(labels, shard)
    for i in range(100):
        params, loss = step(params, cats_sh, labels_sh)
        if i % 20 == 0:
            print(f"step {i:3d} loss {float(loss):.6f}")
    print(f"final loss {float(loss):.6f}")


if __name__ == "__main__":
    main()
