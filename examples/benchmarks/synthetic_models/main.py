"""Synthetic model benchmark driver.

TPU port of the reference driver
(``examples/benchmarks/synthetic_models/main.py:54-155``): builds a zoo model
(``--model tiny..colossal``), trains with the hybrid-parallel step, reports
mean iteration time. A collective-synced loss read closes each timing window
like the reference's allreduced-loss print (``main.py:123,138-144``).

Run (single chip):        python main.py --model tiny --row_cap 1000000
Run (8-dev CPU dry run):  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
                          python main.py --model tiny --row_cap 100000 --batch_size 1024
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
from absl import app, flags

from distributed_embeddings_tpu.models import (
    InputGenerator, build_synthetic, synthetic_models_v3)
from distributed_embeddings_tpu.parallel import (
    SparseAdagrad, SparseSGD, init_hybrid_state, make_hybrid_train_step,
    run_resilient)
from distributed_embeddings_tpu.utils import runtime

FLAGS = flags.FLAGS
flags.DEFINE_string("model", "tiny", "model scale from the zoo")
flags.DEFINE_integer("batch_size", 65536, "global batch size")
flags.DEFINE_float("alpha", 1.05, "power-law exponent; 0 = uniform ids")
flags.DEFINE_integer("num_steps", 100, "timed steps")
flags.DEFINE_string("optimizer", "adagrad", "sgd | adagrad (embedding side)")
flags.DEFINE_integer("column_slice_threshold", None, "max elements per slice")
flags.DEFINE_integer("row_cap", None,
                     "clip table vocab (zoo tables reach 2B rows)")
flags.DEFINE_float("learning_rate", 0.01, "learning rate")
flags.DEFINE_string("checkpoint_dir", None,
                    "drive the run through the self-healing driver "
                    "(parallel.resilient.run_resilient) with atomic "
                    "train-state checkpoints in this directory; SIGTERM "
                    "mid-run checkpoints and exits with the resume "
                    "sentinel instead of losing the run")
flags.DEFINE_bool("resume", False,
                  "auto-resume from --checkpoint_dir when a valid "
                  "checkpoint exists (preemption requeue)")
flags.DEFINE_integer("checkpoint_every_steps", 0,
                     "periodic checkpoint cadence for --checkpoint_dir "
                     "(0 = only at exit/preemption)")
flags.DEFINE_integer("keep_last_n", None,
                     "checkpoint-ring size beyond <dir> and <dir>.prev "
                     "(rollback-and-replay recovery candidates); default "
                     "DETPU_CKPT_RING (2)")
flags.DEFINE_integer("rollback_max", None,
                     "NaN-escalation rollback budget before the terminal "
                     "NonFiniteLossError; default DETPU_ROLLBACK_MAX (2)")

_GEN_BATCHES = 4  # distinct pre-generated batches, cycled


def main(_):
    runtime.ensure_compile_cache()
    model_config = synthetic_models_v3[FLAGS.model]
    devices = jax.devices()
    world = len(devices)
    mesh = (jax.sharding.Mesh(np.array(devices), ("data",))
            if world > 1 else None)
    de, dense, hotness = build_synthetic(
        model_config, world,
        column_slice_threshold=FLAGS.column_slice_threshold,
        row_cap=FLAGS.row_cap)
    print(de.strategy.describe())

    gen = InputGenerator(model_config, FLAGS.batch_size, alpha=FLAGS.alpha,
                         num_batches=_GEN_BATCHES, row_cap=FLAGS.row_cap)
    num0, cats0, _ = gen[0]
    out_widths = [
        int(de.strategy.global_configs[t]["output_dim"])
        for t in de.strategy.input_table_map]
    dense_params = dense.init(
        jax.random.key(0), num0[:2],
        [jnp.zeros((2, w), jnp.float32) for w in out_widths])

    emb_opt = SparseSGD() if FLAGS.optimizer == "sgd" else SparseAdagrad()
    tx = (optax.sgd(FLAGS.learning_rate) if FLAGS.optimizer == "sgd"
          else optax.adagrad(FLAGS.learning_rate))

    def loss_fn(dp, emb_outs, batch):
        n, y = batch
        pred = dense.apply(dp, n, emb_outs)
        return jnp.mean((pred - y) ** 2)

    state = init_hybrid_state(de, emb_opt, dense_params, tx,
                              jax.random.key(1), mesh=mesh)
    # telemetry pinned off: this benchmark times the raw step (use the
    # dlrm example or DETPU_TELEMETRY with your own loop for hot-row
    # telemetry)
    step_fn = make_hybrid_train_step(de, loss_fn, tx, emb_opt, mesh=mesh,
                                     lr_schedule=FLAGS.learning_rate,
                                     with_metrics=False, telemetry=False)

    if FLAGS.checkpoint_dir:
        # self-healing path: checkpointed, preemption-safe, resumable —
        # the deterministic batch cycle makes an interrupted+resumed run
        # reproduce the uninterrupted trajectory
        def data(start):
            for i in range(start, FLAGS.num_steps):
                num, cats, labels = gen[i % _GEN_BATCHES]
                yield cats, (num, labels)

        t0 = time.perf_counter()
        res = run_resilient(
            step_fn, state, data, de=de,
            checkpoint_dir=FLAGS.checkpoint_dir,
            checkpoint_every_steps=FLAGS.checkpoint_every_steps,
            keep_last_n=FLAGS.keep_last_n,
            rollback_max=FLAGS.rollback_max,
            resume=FLAGS.resume, emb_optimizer=emb_opt, dense_tx=tx,
            mesh=mesh, exit_on_preempt=True)
        dt = (time.perf_counter() - t0) / max(res.steps_run, 1)
        print(f"{model_config.name}: {dt * 1e3:.3f} ms/iter over "
              f"{res.steps_run} resilient step(s) to step {res.step} "
              f"({res.checkpoints_saved} checkpoint(s)), final loss "
              f"{res.last_loss:.5f}" if res.last_loss is not None else
              f"{model_config.name}: resumed past the end (step {res.step})")
        return

    # compile + warmup, drained before the clock starts
    num, cats, labels = gen[0]
    loss, state = jax.block_until_ready(
        step_fn(state, cats, (num, labels)))
    print(f"{model_config.name}: compiled; warmup loss {float(loss):.5f}")

    t0 = time.perf_counter()
    for i in range(FLAGS.num_steps):
        num, cats, labels = gen[i]
        loss, state = step_fn(state, cats, (num, labels))
    # the timer stops when the last step's outputs exist (the reference
    # stops on an allreduced-loss print, synthetic_models/main.py:123,
    # 138-144 there)
    jax.block_until_ready((loss, state))
    dt = (time.perf_counter() - t0) / FLAGS.num_steps
    final_loss = float(loss)
    print(f"{model_config.name}: {dt * 1e3:.3f} ms/iter "
          f"({FLAGS.batch_size / dt:,.0f} samples/s) on {world} device(s), "
          f"final loss {final_loss:.5f}")


if __name__ == "__main__":
    app.run(main)
