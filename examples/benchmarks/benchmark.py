"""Single-op embedding lookup microbenchmark.

TPU port of the reference microbenchmark
(``examples/benchmarks/benchmark.py:23-98``): times forward, forward+backward
and forward+backward+SGD of the fused ragged variable-hotness lookup against
the unfused dense gather+reduce formulation.

Timing discipline: loops chain each iteration's output into the next
call's input, and the clock stops on ``jax.block_until_ready`` of the last
output (``chip_smoke.py`` checks on every run that it really waits).
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
from absl import app, flags

from distributed_embeddings_tpu.ops import Ragged, embedding_lookup
from distributed_embeddings_tpu.utils import runtime

FLAGS = flags.FLAGS
flags.DEFINE_integer("batch_size", 65536, "batch size")
flags.DEFINE_integer("vocab", 1000000, "table rows")
flags.DEFINE_integer("width", 128, "embedding width")
flags.DEFINE_integer("hotness", 10, "average ids per sample")
flags.DEFINE_integer("iters", 50, "timed iterations")


def timeit(step, params, *args, iters):
    """``step(params, *args) -> params_like`` timed with params threading
    (data-dependent chain)."""
    out = jax.block_until_ready(step(params, *args))  # compile + drain
    t0 = time.perf_counter()
    for _ in range(iters):
        out = step(out, *args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e3


def main(_):
    runtime.ensure_compile_cache()
    b, v, w, h = FLAGS.batch_size, FLAGS.vocab, FLAGS.width, FLAGS.hotness
    rng = np.random.default_rng(0)
    params = jnp.asarray(rng.normal(size=(v, w)), jnp.float32)
    # variable hotness in [1, 2h-1], mean h (reference generates variable rows)
    hots = rng.integers(1, 2 * h, size=b)
    total = int(hots.sum())
    values = jnp.asarray(rng.integers(0, v, size=total), jnp.int32)
    splits = jnp.asarray(np.concatenate([[0], np.cumsum(hots)]), jnp.int32)
    ragged = Ragged(values=values, row_splits=splits)
    dense_ids = jnp.asarray(rng.integers(0, v, size=(b, h)), jnp.int32)

    # forward: fold a hair of the output back into params to chain iterations
    fwd = jax.jit(lambda p, r: p.at[0, 0].add(
        1e-30 * jnp.sum(embedding_lookup(p, r, combiner="sum")[0])),
        donate_argnums=0)
    print(f"ragged fwd:           {timeit(fwd, params + 0, ragged, iters=FLAGS.iters):8.3f} ms")
    print(f"dense  fwd:           {timeit(fwd, params + 0, dense_ids, iters=FLAGS.iters):8.3f} ms")

    grad = jax.jit(lambda p, r: p - 1e-30 * jax.grad(
        lambda q: embedding_lookup(q, r, combiner="sum").sum())(p),
        donate_argnums=0)
    print(f"ragged fwd+bwd:       {timeit(grad, params + 0, ragged, iters=FLAGS.iters):8.3f} ms")

    sgd = jax.jit(lambda p, r: p - 0.01 * jax.grad(
        lambda q: embedding_lookup(q, r, combiner="sum").sum())(p),
        donate_argnums=0)
    print(f"ragged fwd+bwd+sgd:   {timeit(sgd, params + 0, ragged, iters=FLAGS.iters):8.3f} ms")


if __name__ == "__main__":
    app.run(main)
