"""make_hybrid_train_loop: K scanned steps == K individual steps.

The loop driver exists to amortize per-dispatch host overhead; its
contract is exact per-step equivalence with make_hybrid_train_step — same gradients, same optimizer
updates, same step counter — which these tests assert by trajectory
comparison from a shared init.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distributed_embeddings_tpu.parallel import (
    DistributedEmbedding, SparseAdagrad, SparseSGD, init_hybrid_state,
    make_hybrid_train_loop, make_hybrid_train_step)
from distributed_embeddings_tpu.utils import obs

WORLD = 8
K = 3


def _model(world):
    configs = [{"input_dim": 20 + 6 * i, "output_dim": 4,
                "combiner": ["sum", None, "mean"][i % 3]}
               for i in range(10)]
    return DistributedEmbedding(configs, world_size=world), configs


def _data(rng, configs, b, k):
    cats, stacks = [], []
    for cfg in configs:
        hot = 1 if cfg["combiner"] is None else 3
        shape = (k, b) if hot == 1 else (k, b, hot)
        arr = rng.integers(0, cfg["input_dim"], size=shape)
        stacks.append(jnp.asarray(arr, jnp.int32))
        cats.append([jnp.asarray(arr[i], jnp.int32) for i in range(k)])
    num = jnp.asarray(rng.normal(size=(k, b, 3)), jnp.float32)
    y = jnp.asarray(rng.normal(size=(k, b, 1)) * 0.1, jnp.float32)
    return cats, stacks, num, y


def _loss_fn(dp, emb_outs, batch):
    n, y = batch
    x = jnp.concatenate([e.reshape(e.shape[0], -1) for e in emb_outs],
                        axis=1)
    pred = x @ dp["w"] + n @ dp["v"]
    return jnp.mean((pred - y) ** 2)


def _dense_params(configs):
    # every input is [b, w] here: no-combiner tables get 1-hot 1-D inputs,
    # combiner tables reduce their 3-hot inputs
    cols = sum(int(c["output_dim"]) for c in configs)
    return {"w": jnp.zeros((cols, 1)), "v": jnp.zeros((3, 1))}


@pytest.mark.parametrize("world", [1, WORLD])
def test_loop_matches_individual_steps(world):
    rng = np.random.default_rng(0)
    de, configs = _model(world)
    b = 16  # global batch
    cats, stacks, num, y = _data(rng, configs, b, K)
    tx = optax.sgd(0.5)
    emb_opt = SparseAdagrad()
    mesh = (Mesh(np.array(jax.devices()[:world]), ("data",))
            if world > 1 else None)
    dp = _dense_params(configs)

    # each state gets its own dense-param copies: the steps donate their
    # state, and a shared array would be deleted under the other state
    state_a = init_hybrid_state(de, emb_opt, jax.tree.map(jnp.copy, dp), tx,
                                jax.random.key(1), mesh=mesh)
    state_b = init_hybrid_state(de, emb_opt, jax.tree.map(jnp.copy, dp), tx,
                                jax.random.key(1), mesh=mesh)

    step = make_hybrid_train_step(de, _loss_fn, tx, emb_opt, mesh=mesh,
                                  lr_schedule=0.3)
    loop = make_hybrid_train_loop(de, _loss_fn, tx, emb_opt, mesh=mesh,
                                  lr_schedule=0.3)

    if mesh is not None:
        shard = NamedSharding(mesh, P(None, "data"))
        stacks = [jax.device_put(s, shard) for s in stacks]
        num = jax.device_put(num, shard)
        y = jax.device_put(y, shard)

    # sliced up front so the only programs built inside the loop are the
    # step's own
    batches = [([s[i] for s in stacks], (num[i], y[i])) for i in range(K)]
    obs.install_compile_listener()
    losses_step = []
    for i, (c, nb) in enumerate(batches):
        if i == 1:
            built = obs.counters()["recompiles"]
        loss, state_a = step(state_a, c, nb)
        losses_step.append(float(loss))
    # init_hybrid_state hands out the placement the step returns: a state
    # whose dense leaves moved (one device -> replicated over the mesh)
    # would retrace and recompile the whole step on its second call
    assert obs.counters()["recompiles"] == built

    losses_loop, state_b = loop(state_b, stacks, (num, y))
    np.testing.assert_allclose(np.asarray(losses_loop),
                               np.asarray(losses_step), rtol=1e-5)
    assert int(state_b.step) == K
    for k in state_a.emb_params:
        np.testing.assert_allclose(
            np.asarray(state_a.emb_params[k]),
            np.asarray(state_b.emb_params[k]), rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(
            np.asarray(state_a.emb_opt_state[k]),
            np.asarray(state_b.emb_opt_state[k]), rtol=1e-5, atol=1e-6)
    for k in ("w", "v"):
        np.testing.assert_allclose(
            np.asarray(state_a.dense_params[k]),
            np.asarray(state_b.dense_params[k]), rtol=1e-5, atol=1e-6)
