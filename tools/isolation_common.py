"""Shared model factory for the process-isolation drill + tests.

Both sides of the process boundary — the trainer child in
``tools/check_isolation.py`` and the spawned
:mod:`~distributed_embeddings_tpu.parallel.supervisor` serving worker —
must build the SAME model at the SAME world size (the snapshot payload
is the flattened parameter leaves; slab shapes carry the world dim), so
the build lives in ONE importable place and the worker references it by
name: ``"tools.isolation_common:worker_factory"`` (spawn children
inherit ``sys.path``, so anything the parent can import, the worker
can).
"""

from __future__ import annotations

#: static-table vocab sizes; with the streaming table appended the model
#: has 8 tables — one per mesh position at the drill's world=8 (the
#: planner refuses fewer tables than mesh positions)
SIZES = [2000, 1500, 1000, 800, 600, 500, 400]


def build(world: int = 8, seed: int = 0):
    """The isolation-drill model: three static tables + one streaming
    table (so snapshots carry BOTH param and streaming leaves across
    the boundary), a sigmoid head, and a synthetic request template.

    Returns a dict with everything either side needs; the worker
    factory below narrows it to the ``ServingWorker`` surface."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import Mesh

    from distributed_embeddings_tpu.parallel import (
        DistributedEmbedding, ServeConfig, SparseSGD, StreamingConfig,
        init_hybrid_state, init_streaming)
    from distributed_embeddings_tpu.parallel import serving as sv

    mesh = (Mesh(np.array(jax.devices()[:world]), ("data",))
            if world > 1 else None)
    sizes = list(SIZES)
    configs = ([{"input_dim": v, "output_dim": 8} for v in sizes]
               + [{"input_dim": 64 + 16, "output_dim": 8,
                   "streaming": {"capacity": 64, "buckets": 16}}])
    de = DistributedEmbedding(configs, world_size=world)
    scfg = StreamingConfig(admit_min_count=2, evict_margin=1, depth=2,
                           buckets=256)
    tx = optax.sgd(0.05)
    state = init_hybrid_state(
        de, SparseSGD(),
        {"w": jnp.ones((8 * len(configs) + 2, 1), jnp.float32) * 0.01},
        tx, jax.random.key(seed), mesh=mesh)
    sstate = init_streaming(de, scfg, mesh=mesh)

    def pred_fn(dp, outs, batch):
        x = jnp.concatenate(list(outs) + [batch], axis=-1)
        return jax.nn.sigmoid(x @ dp["w"])[:, 0]

    cfg = ServeConfig(max_batch=32, max_wait_ms=5, deadline_ms=4000,
                      max_queue=256, shed_frac=0.5)
    rng = np.random.default_rng(seed)
    tmpl = sv.synthetic_request(rng, sizes + [1], 2, numerical=2)
    return {
        "de": de, "pred_fn": pred_fn, "state": state, "mesh": mesh,
        "config": cfg, "streaming": (scfg, sstate),
        "template": (tmpl.cats, tmpl.batch),
        "sizes": sizes, "scfg": scfg,
    }


def worker_factory(world: int = 8, seed: int = 0):
    """The :class:`~distributed_embeddings_tpu.parallel.supervisor
    .Supervisor` factory entry point (``"tools.isolation_common:
    worker_factory"``): the worker's own model, ladder config, and
    warmup template."""
    built = build(world=world, seed=seed)
    return {k: built[k] for k in
            ("de", "pred_fn", "state", "mesh", "config", "streaming",
             "template")}


def make_request_fn(seed: int = 1):
    """Seeded Zipfian request factory over the drill model's tables
    (one external-id streaming input appended, like the serving drill);
    deterministic per index via a per-request generator."""
    import numpy as np

    from distributed_embeddings_tpu.parallel import serving as sv

    sizes = list(SIZES)

    def make_request(i: int):
        rng = np.random.default_rng(seed * 1_000_003 + i)
        n = int(rng.integers(1, 5))
        req = sv.synthetic_request(rng, sizes, n, numerical=2)
        req.cats = list(req.cats) + [np.asarray(
            rng.integers(0, 1 << 30, size=(n,)), np.int32)]
        req.priority = 1 if i % 8 == 0 else 0
        return req

    return make_request
