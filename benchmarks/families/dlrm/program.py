"""The system under test, built from a configuration file of family ``dlrm``.
The family's adapter half: with ``benchmarks/families/system.py`` the only
code of the benchmark that imports ``distributed_embeddings_tpu``; it takes
from the program its entry points and nothing that decides a metric or
``correct``.
"""

from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Callable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distributed_embeddings_tpu.models.dlrm import (DLRMConfig, DLRMDense,
                                                    bce_with_logits)
from distributed_embeddings_tpu.ops import packed_slab
from distributed_embeddings_tpu.ops.embedding_lookup import Ragged
from distributed_embeddings_tpu.parallel import (
    DistributedEmbedding, ServeConfig, ServingRuntime, SparseSGD,
    init_hybrid_state, make_hybrid_eval_step, make_hybrid_train_step)
from distributed_embeddings_tpu.parallel import dist_embedding

from . import weights


class _SeedFreeInit:
    """``embeddings_initializer`` of one table: ``weights.base_rows``. The
    program calls it once per column slice of the table, in the order in which
    its checkpoint routing consumes the columns (rank order), so the slices'
    widths are counted up to find each call's first column."""

    def __init__(self, table: int, rows: int, width: int):
        self.table, self.rows, self.width = table, rows, width
        self._col = 0

    def __call__(self, key, shape, dtype):
        del key
        n, w = shape
        if n != self.rows:
            raise ValueError(f"table {self.table}: the program asked for {n} "
                             f"rows of {self.rows}; row slices are not handled")
        col0 = self._col
        self._col = (col0 + w) % self.width
        return weights.base_rows(self.table, self.rows, jnp.arange(n), col0,
                                 w, dtype)


def _fill_packed(de, mesh, width: int, dtype) -> jax.Array:
    """The slab of one packed width, ``[world, rows, lanes]`` sharded over the
    mesh, holding ``weights.base_values`` of every table slice where the
    program's checkpoint routing (``_slice_plan``) puts it: logical row
    ``r`` of a slice that starts at slab row ``s`` sits in physical row
    ``(s + r) // pack``, lanes ``(r % pack) * width ..``. Each device's shard
    is one elementwise program over its positions, so nothing but the shard
    itself is ever held."""
    pack = packed_slab.pack_factor(width)
    cap, lanes = de.phys_cap[width], de.phys_w[width]
    plan = de._slice_plan()
    full = [int(c["input_dim"]) for c in de.strategy.global_configs]
    u32 = jnp.uint32

    def shard(rank: int):
        segs = sorted((row_off // pack, weights.table_key(tid),
                       weights.table_scale(full[tid]), row0, col0, rows)
                      for tid, row_off, rows, col0, w, row0 in plan[rank]
                      if w == width)
        i = jax.lax.broadcasted_iota(u32, (cap, 1), 0)
        lane = jax.lax.broadcasted_iota(u32, (1, lanes), 1)

        def of_row(k: int, dt):
            """Field ``k`` of the slice that physical row ``i`` lies in."""
            out = jnp.full((cap, 1), segs[0][k], dt)
            for seg in segs[1:]:
                out = jnp.where(i >= u32(seg[0]), jnp.asarray(seg[k], dt), out)
            return out

        r = (i - of_row(0, u32)) * u32(pack) + lane // u32(width)
        v = weights.base_values(of_row(1, u32), of_row(2, jnp.float32),
                                r + of_row(3, u32),
                                lane % u32(width) + of_row(4, u32), dtype)
        live = (r < of_row(5, u32)) & (lane < u32(pack * width))
        return jnp.where(live, v, jnp.zeros((), dtype))[None]

    shard = jax.jit(shard, static_argnums=0)
    if mesh is None:
        return jnp.concatenate([shard(r) for r in range(de.world_size)])
    shape = (de.world_size, cap, lanes)
    sharding = NamedSharding(mesh, P(de.axis_name))
    arrays = []
    for dev, idx in sharding.devices_indices_map(shape).items():
        rank = idx[0].indices(de.world_size)[0]
        with jax.default_device(dev):
            arrays.append(jax.device_put(shard(rank), dev))
    return jax.make_array_from_single_device_arrays(shape, sharding, arrays)


@dataclasses.dataclass
class Built:
    """One configuration, built and holding its state on the device."""
    config: dict
    de: Any
    dense: Any
    mesh: Any
    state: Any
    put: Callable  # host array -> device array, batch-sharded on a mesh

    @property
    def world(self) -> int:
        return int(self.config["chips"])


def _dtype(name: str):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[name]


def dense_tree(layers) -> dict:
    """``weights.dense_params`` in ``DLRMDense``'s parameter tree."""
    return {"params": {f"Dense_{i}": {"kernel": jnp.asarray(k),
                                      "bias": jnp.asarray(b)}
                       for i, (k, b) in enumerate(layers)}}


def dense_layers(tree) -> list:
    """The inverse of :func:`dense_tree`, on the host."""
    p = jax.device_get(tree)["params"]
    return [(np.asarray(p[f"Dense_{i}"]["kernel"]),
             np.asarray(p[f"Dense_{i}"]["bias"])) for i in range(len(p))]


_scramble_tree = jax.jit(
    lambda tree, words: jax.tree.map(lambda v: weights.scramble(v, words),
                                     tree),
    donate_argnums=0)


def build(config: dict, seed: int, combiner: Optional[str] = None,
          dense_lr: float = 0.0) -> Built:
    """Build the embedding layer, the dense model and the training state of a
    configuration, with the benchmark's weights for ``seed`` on the device."""
    sizes = [int(s) for s in config["table_sizes"]]
    dim = int(config["embedding_dim"])
    cdt = _dtype(config["compute_dtype"])
    tdt = _dtype(config["table_dtype"])
    world = int(config["chips"])
    devices = jax.devices()[:world]
    cfg = DLRMConfig(table_sizes=sizes, embedding_dim=dim,
                     num_numerical_features=int(config["num_numerical"]),
                     bottom_mlp_dims=tuple(config["bottom_mlp"]),
                     top_mlp_dims=tuple(config["top_mlp"]),
                     compute_dtype=cdt)
    plan = config.get("plan", {})
    mesh = None
    put = jnp.asarray
    if world > 1:
        mesh = Mesh(np.array(devices), ("data",))
        shard = NamedSharding(mesh, P("data"))
        put = lambda x: jax.device_put(x, shard)  # noqa: E731

    def embedding(tables):
        return DistributedEmbedding(
            tables, world_size=world, compute_dtype=cdt, dp_input=True,
            **({"strategy": plan["strategy"]} if "strategy" in plan else {}),
            **({"column_slice_threshold": int(plan["column_slice_threshold"])}
               if plan.get("column_slice_threshold") else {}))

    tables = [{"input_dim": s, "output_dim": dim, "combiner": combiner}
              for s in sizes]
    # A slice narrower than a physical row gets its values from _fill_packed,
    # not through the initializer hook: the program packs what a hook returns
    # with strided slices and a concatenate, which the chip's compiler
    # materializes (13.3 GiB of temporaries beside the four-chip cell's
    # 11 GiB slab, my chip run, PR 25). Which tables those are is the plan's
    # to say, so the layer is planned once without hooks and asked.
    packed = {tid for rank in embedding(tables)._slice_plan()
              for tid, _, _, _, w, _ in rank
              if packed_slab.pack_factor(w) > 1}
    de = embedding([
        t if tid in packed else
        dict(t, embeddings_initializer=_SeedFreeInit(tid, t["input_dim"], dim))
        for tid, t in enumerate(tables)])
    dense = DLRMDense(cfg)
    layers = weights.dense_params(seed, cfg.num_numerical_features,
                                  cfg.bottom_mlp_dims, cfg.top_mlp_dims,
                                  len(sizes), dim)
    tx = optax.sgd(dense_lr)
    state = init_hybrid_state(de, SparseSGD(), dense_tree(layers), tx,
                              jax.random.key(0), mesh=mesh, dtype=tdt)
    for w in de.widths:
        if packed_slab.pack_factor(w) > 1:
            key = dist_embedding._wkey(w)
            state.emb_params.pop(key).delete()   # one slab at a time
            state.emb_params[key] = _fill_packed(de, mesh, w, tdt)
    words = jnp.asarray(weights.seed_words(seed))
    state = state._replace(
        emb_params=_scramble_tree(state.emb_params, words))
    return Built(config=config, de=de, dense=dense, mesh=mesh, state=state,
                 put=put)


def _loss_fn(dense):
    def loss_fn(dp, emb_outs, batch):
        numerical, labels = batch
        return bce_with_logits(dense.apply(dp, numerical, emb_outs), labels)
    return loss_fn


def train_step(built: Built, emb_lr: float, dense_lr: float):
    """The library-default hybrid step (NaN guard as the library sets it)."""
    return make_hybrid_train_step(
        built.de, _loss_fn(built.dense), optax.sgd(dense_lr), SparseSGD(),
        mesh=built.mesh, lr_schedule=emb_lr, with_metrics=False,
        telemetry=False)


def stage(built: Built, batch):
    """One ``traffic.TrainBatch`` on the device, as the step takes it."""
    put = built.put
    if batch.splits is None:
        cats = [put(i) for i in batch.ids]
    else:
        cats = [Ragged(values=put(i), row_splits=put(s))
                for i, s in zip(batch.ids, batch.splits)]
    return cats, (put(batch.numerical), put(batch.labels))


def row_observer(built: Built):
    """``observe(state, cats, want_rows, first) -> [tables]``: per table the
    squared norm of ``lookup(cats) - want_rows`` over the samples ``first``
    marks. Reads the state through the program's own lookup; it is how the
    check sees table rows without knowing the slab's layout."""
    def fn(dp, outs, extra):
        del dp
        want, first = extra
        got = jnp.stack(outs, axis=1).astype(jnp.float32)
        d = (got - want.astype(jnp.float32)) ** 2
        return jnp.sum(d * first[:, :, None], axis=2)
    return make_hybrid_eval_step(built.de, fn, mesh=built.mesh)


def logits_fn(dense):
    def fn(dp, outs, numerical):
        return dense.apply(dp, numerical, outs)[:, 0]
    return fn


def serving_runtime(built: Built, serve: dict) -> ServingRuntime:
    cfg = ServeConfig(rungs=[int(r) for r in serve["rungs"]],
                      max_wait_ms=float(serve["max_wait_ms"]),
                      deadline_ms=float(serve["deadline_ms"]),
                      max_queue=int(serve["max_queue"]),
                      shed_frac=float(serve["shed_frac"]))
    return ServingRuntime(built.de, logits_fn(built.dense), built.state,
                          mesh=built.mesh, config=cfg, trace=False)


@contextlib.contextmanager
def exchange_left_out():
    """Plant the fault "the exchange between chips left out": while it is
    entered, the program's three all-to-alls hand back what they were given.
    For the tests and the tool that reads what the fault does to the numbers
    compared; no run of the benchmark enters it."""
    from distributed_embeddings_tpu.parallel import exchange

    class _NoExchange:
        def __getattr__(self, name):
            return getattr(jax.lax, name)

        @staticmethod
        def all_to_all(x, *args, **kwargs):
            return x

    real = exchange.lax
    exchange.lax = _NoExchange()
    try:
        yield
    finally:
        exchange.lax = real
