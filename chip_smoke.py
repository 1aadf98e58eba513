"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once, in ONE process, through the entry points a user
calls, at the full width of the MLPerf DLRM this repo benchmarks
(the published widths over the 26 Criteo-Kaggle tables capped at 2 M rows,
global batch 65536, bf16 compute, bf16 tables, ``SparseSGD`` +
``optax.sgd``, seeded power-law ids, random weights from a seed):

1. **device** — platform must be ``tpu`` and the ``device_kind`` must be in
   ``analysis.plan_audit.CHIP_SPECS``; then a large scatter is timed both
   ways to establish that ``jax.block_until_ready`` really waits.
2. **train** — ``make_hybrid_train_step`` on distinct pre-staged batches,
   two more steps under ``obs.profile_trace``, one ``make_hybrid_train_loop``
   call with K = 4; labels all zero (a target the model can fit).
3. **agreement** — before and after training, the hybrid forward against
   the plain model (``models.dlrm.DLRM.apply``, ``jnp.take`` over whole
   tables) fed ``de.get_weights``.
4. **serve** — a ``ServingRuntime`` over the trained state answers mixed
   requests, survives the trainer donating its state, and picks up a newer
   published snapshot without recompiling.
5. **four chips** (``--chips 4``) — the same model and global batch over a
   ``("data",)`` mesh of four devices with all three exchanges running.
6. **experts** — ``models.moe_lm``'s grouped product as the backend gives
   it (the megablox kernel on the chip) over a share of the experts, against
   one plain product a group: the rows past the held experts' must come out
   zero, forward and in the gradient of ``lhs``, because the expert layer
   multiplies them by a weight of 0 and a row left unwritten could hold
   anything.

Any failed check exits non-zero at once with the check's name. No phase is
wrapped in try/except, nothing is retried, nothing falls back to the CPU.
Without a TPU the script exits 2 and prints no result. On success the last
line of stdout is ``{"ok": true, "device": {...}}``.

``--dry-cpu`` runs the same code on the CPU at a toy size so the command can
be debugged where there is no chip. It is never chosen automatically and it
proves nothing about the chip.

Writes only under ``chiprun_out/chip_smoke/`` (the profile capture) and the
compile cache (``JAX_COMPILATION_CACHE_DIR``, else ``<checkout>/.jax_cache``).
"""

import argparse
import glob
import json
import os
import re
import shutil
import sys
import time

_HERE = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(_HERE, "chiprun_out", "chip_smoke")

SEED = 0
CAP = 2_000_000         # rows a table keeps of its Criteo-Kaggle vocabulary
BATCH = 65536           # global batch
LR = 0.1                # both halves; large enough that 8 steps visibly learn
TIMED_STEPS = 8         # make_hybrid_train_step calls inside the timed window
PROFILED_STEPS = 2      # further steady steps, under the profiler
LOOP_K = 4              # steps inside the one make_hybrid_train_loop call
AGREE_ROWS = 4096       # seeded sample compared against the plain model
SERVE_MAX_BATCH = 64    # ladder 8/16/32/64: four rungs
SERVE_REQUESTS = 16
FOUR_CHIP_STEPS = 4     # steps whose losses one chip and four must share

# Hybrid forward vs plain DLRM.apply. The embedding rows themselves are
# compared BIT-EXACTLY (a gather is exact and both sides read bf16 tables
# into bf16 activations). The logits go through the same DLRMDense with the
# same parameters and dtypes but in two separately compiled programs, whose
# fusions may round an intermediate to bf16 at different points — so they
# get 4 bf16 ulps (bf16 keeps 8 significand bits) at the logit scale. A table
# read in a narrower dtype, or one misrouted row, is far outside both.
LOGIT_TOL_ULPS = 4
BF16_ULP = 2.0 ** -8
# One chip vs four: same weights, same global batches, bf16 compute. The
# per-shard means and the dense-gradient sums associate differently, which
# moves a loss by a few bf16 roundings; a dense gradient summed instead of
# averaged (or averaged twice) moves it by tens of percent at this LR.
FOUR_CHIP_LOSS_RTOL = 1e-2
# Per-device bytes after a mesh init: the slab is rectangular
# ([world, rows_cap, width]), so each device should hold a quarter.
QUARTER_BAND = 0.10


def require(name, ok, detail=""):
    """One named check; a failure ends the run at once."""
    if not ok:
        sys.exit(f"CHECK FAILED [{name}] {detail}")


def check(name, ok, detail=""):
    """:func:`require`, and say so when it held."""
    require(name, ok, detail)
    print(f"  ok   {name}" + (f"  ({detail})" if detail else ""), flush=True)


def note(msg):
    print(f"  obs  {msg}", flush=True)


def experts_share():
    """The grouped product of a chunk whose tail (rows of experts not held)
    is not empty, an empty group among the held, against one plain product a
    group: ``(worst gap over out, d lhs, d rhs as a share of the largest
    value; largest |value| in the tail rows of out and of d lhs; all finite;
    the largest |value| that lax.ragged_dot leaves in the tail of out)``."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from distributed_embeddings_tpu.models import moe_lm
    m, k, n, sizes = 2048, 256, 384, (700, 0, 500)
    keys = jax.random.split(jax.random.key(SEED), 3)
    lhs = jax.random.normal(keys[0], (m, k), jnp.bfloat16)
    rhs = jax.random.normal(keys[1], (len(sizes), k, n), jnp.bfloat16)
    cot = jax.random.normal(keys[2], (m, n), jnp.float32)  # the tail's too
    held, group_sizes = sum(sizes), jnp.asarray(sizes, jnp.int32)

    def plain(a, b):
        starts = [sum(sizes[:i]) for i in range(len(sizes))]
        return jnp.concatenate(
            [jnp.dot(a[at:at + size], b[i], preferred_element_type=jnp.float32)
             for i, (at, size) in enumerate(zip(starts, sizes))]
            + [jnp.zeros((m - held, n), jnp.float32)])

    def three(f):   # out, d lhs, d rhs, in float32
        out = jax.jit(lambda a, b: (f(a, b), *jax.grad(
            lambda a, b: jnp.sum(f(a, b) * cot), argnums=(0, 1))(a, b)))(
                lhs, rhs)
        return [x.astype(jnp.float32) for x in out]
    got, want = three(lambda a, b: moe_lm._grouped(a, b, group_sizes)), \
        three(plain)
    ragged = jax.jit(lambda a, b: lax.ragged_dot(
        a, b, group_sizes, preferred_element_type=jnp.float32))(lhs, rhs)
    gap = max(float(jnp.max(jnp.abs(g - w)) / jnp.max(jnp.abs(w)))
              for g, w in zip(got, want))
    tail = max(float(jnp.max(jnp.abs(x[held:]))) for x in got[:2])
    return (gap, tail, all(bool(jnp.isfinite(x).all()) for x in got),
            float(jnp.max(jnp.abs(ragged[held:]))))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 adds the four-chip phase; fails if fewer than "
                         "four devices are visible")
    ap.add_argument("--dry-cpu", action="store_true",
                    help="debug run on the CPU at a toy size; proves "
                         "nothing about the chip")
    args = ap.parse_args(argv)
    dry = args.dry_cpu
    if dry:
        print("DRY MODE (--dry-cpu): CPU, toy table sizes and batch — this "
              "run proves nothing about the chip", flush=True)
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.chips}")

    from distributed_embeddings_tpu.utils import runtime
    cache_dir = runtime.ensure_compile_cache()

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    if dry:
        jax.config.update("jax_platforms", "cpu")

    from tools._profcommon import CRITEO_KAGGLE_SIZES
    from distributed_embeddings_tpu.analysis import audit, plan_audit
    from distributed_embeddings_tpu.models.dlrm import (
        DLRM, DLRMConfig, DLRMDense, bce_with_logits)
    from distributed_embeddings_tpu.parallel import (
        DistributedEmbedding, ServeConfig, Served, ServingRuntime,
        SnapshotPublisher, SparseSGD, bootstrap, init_hybrid_state,
        make_hybrid_eval_step, make_hybrid_train_loop,
        make_hybrid_train_step)
    from distributed_embeddings_tpu.parallel import serving as sv
    from distributed_embeddings_tpu.utils import (obs, power_law_ids,
                                                  traceparse)

    # -------------------------------------------------------------- device
    print("== device", flush=True)
    t0 = time.perf_counter()
    devices = jax.devices()
    dev = {"platform": devices[0].platform,
           "kind": devices[0].device_kind, "count": len(devices)}
    print(f"jax {jax.__version__}  platform={dev['platform']}  "
          f"device_kind={dev['kind']!r}  count={dev['count']}  "
          f"(first backend touch {time.perf_counter() - t0:.1f} s)",
          flush=True)
    if not dry:
        if dev["platform"] != "tpu":
            sys.exit(2)  # no accelerator: no result line
        kinds = [k for s_ in plan_audit.CHIP_SPECS.values()
                 for k in s_.device_kinds]
        check("device.known_chip", dev["kind"] in kinds,
              f"{dev['kind']!r}; CHIP_SPECS knows {kinds}")
        spec = plan_audit.chip_spec_for_device_kind(dev["kind"])
    check("device.count", len(devices) >= args.chips,
          f"{args.chips} asked for, {len(devices)} visible")
    note(f"compile cache: {cache_dir}")
    t0 = time.perf_counter()
    joined = bootstrap.initialize()
    note(f"bootstrap.initialize() -> {joined} in "
         f"{time.perf_counter() - t0:.3f} s")
    obs.install_compile_listener()

    def compiles():
        return obs.counters().get("recompiles", 0)

    # ------------------------------------------- is block_until_ready honest
    rows, upd = (4_000_000, 2_000_000) if not dry else (4096, 2048)
    scatter = jax.jit(lambda t, i, u: t.at[i].add(u), donate_argnums=0)
    tab = jnp.zeros((rows, 128), jnp.bfloat16)
    ids = jax.random.randint(jax.random.key(SEED), (upd,), 0, rows)
    val = jnp.ones((upd, 128), jnp.bfloat16)
    tab = jax.block_until_ready(scatter(tab, ids, val))
    enq, blk, rdb = [], [], []
    for _ in range(3):
        t0 = time.perf_counter()
        tab = scatter(tab, ids, val)
        t1 = time.perf_counter()
        jax.block_until_ready(tab)
        t2 = time.perf_counter()
        tab = scatter(tab, ids, val)
        float(tab[0, 0])  # the value cannot exist before the scatter ran
        t3 = time.perf_counter()
        enq.append((t1 - t0) * 1e3)
        blk.append((t2 - t0) * 1e3)
        rdb.append((t3 - t2) * 1e3)
    e, b, r = (float(np.median(x)) for x in (enq, blk, rdb))
    note(f"{upd}-row scatter into {rows}x128 bf16: enqueue {e:.2f} ms, "
         f"block_until_ready {b:.2f} ms, value readback {r:.2f} ms")
    if not dry:
        check("device.block_until_ready_waits",
              e < 0.5 * b and abs(b - r) <= 0.25 * r,
              f"returns after {b:.1f} ms where the readback takes {r:.1f}")
    del tab, ids, val

    # --------------------------------------------------------------- model
    if dry:
        sizes = [min(s, 2000) for s in CRITEO_KAGGLE_SIZES]
        batch = 256
        agree_rows = 128
    else:
        sizes = [min(s, CAP) for s in CRITEO_KAGGLE_SIZES]
        batch = BATCH
        agree_rows = AGREE_ROWS
        check("model.full_width", sum(sizes) == 10_569_296
              and batch == 65536, f"{sum(sizes)} rows, batch {batch}")
    cfg = DLRMConfig(table_sizes=sizes, embedding_dim=128,
                     num_numerical_features=13,
                     bottom_mlp_dims=(512, 256, 128),
                     top_mlp_dims=(1024, 1024, 512, 256, 1),
                     compute_dtype=jnp.bfloat16)
    dense = DLRMDense(cfg)
    emb_opt = SparseSGD()
    tx = optax.sgd(LR)
    n_steps = TIMED_STEPS + PROFILED_STEPS + LOOP_K + 1
    if args.chips == 4:
        n_steps = FOUR_CHIP_STEPS
    print(f"== model: DLRM, {len(sizes)} tables / {sum(sizes)} rows x "
          f"{cfg.embedding_dim}, bottom {cfg.bottom_mlp_dims} top "
          f"{cfg.top_mlp_dims}, batch {batch}, bf16 compute + bf16 tables",
          flush=True)

    def loss_fn(dp, emb_outs, b_):
        n, y = b_
        return bce_with_logits(dense.apply(dp, n, emb_outs), y)

    def logits_fn(dp, outs, n):
        return dense.apply(dp, n, outs)[:, 0]

    def rows_fn(dp, outs, n):
        del dp, n
        return jnp.stack(outs, axis=1)

    # host copies: every init below gets fresh device buffers (the steps
    # donate their state)
    dense0 = jax.device_get(dense.init(
        jax.random.key(SEED),
        jnp.zeros((2, cfg.num_numerical_features), jnp.float32),
        [jnp.zeros((2, cfg.embedding_dim), jnp.float32) for _ in sizes]))
    rng = np.random.default_rng(SEED)
    cats_h = [power_law_ids(rng, s, (n_steps, batch)).astype(np.int32)
              for s in sizes]
    num_h = rng.normal(size=(n_steps, batch, 13)).astype(np.float32)
    lab_h = np.zeros((batch, 1), np.float32)
    arng = np.random.default_rng(SEED + 1)
    acats_h = [power_law_ids(arng, s, (agree_rows,)).astype(np.int32)
               for s in sizes]
    anum_h = arng.normal(size=(agree_rows, 13)).astype(np.float32)
    plain = jax.jit(DLRM(cfg).apply)

    def agreement(tag, de, state, weights, evals, put):
        """Hybrid forward vs the plain model over ``weights`` (the host
        tables ``de.get_weights`` returned for ``state``)."""
        rows_eval, logit_eval = evals
        cats_d = [put(c) for c in acats_h]
        got_rows = np.asarray(rows_eval(state, cats_d, put(anum_h)))
        want_rows = np.stack([w[c] for w, c in zip(weights, acats_h)], 1)
        check(f"{tag}.rows_bit_exact",
              got_rows.dtype == want_rows.dtype
              and np.array_equal(got_rows.view(np.uint16),
                                 want_rows.view(np.uint16)),
              f"{agree_rows} samples x {len(sizes)} tables")
        got = np.asarray(logit_eval(state, cats_d, put(anum_h)))
        want = np.asarray(plain(
            {"tables": [jnp.asarray(w) for w in weights],
             "dense": jax.device_get(state.dense_params)},
            jnp.asarray(anum_h), [jnp.asarray(c) for c in acats_h]))[:, 0]
        scale = max(1.0, float(np.abs(want).max()))
        err = float(np.abs(got - want).max())
        check(f"{tag}.logits_match_plain_model",
              np.isfinite(got).all() and got.shape == (agree_rows,)
              and err <= LOGIT_TOL_ULPS * BF16_ULP * scale,
              f"max |diff| {err:.3e} at logit scale {scale:.2f}, "
              f"tolerance {LOGIT_TOL_ULPS * BF16_ULP * scale:.3e}")

    def no_stray_writes(tag, w_before, w_after, steps_used):
        """Rows that changed must be rows some batch touched."""
        changed_total = 0
        for t, (a, b_) in enumerate(zip(w_before, w_after)):
            changed = np.flatnonzero(
                (a.view(np.uint16) != b_.view(np.uint16)).any(axis=1))
            touched = np.unique(cats_h[t][:steps_used])
            stray = np.setdiff1d(changed, touched)
            require(f"{tag}.no_stray_writes", not stray.size,
                    f"table {t}: rows {stray[:5]} changed untouched")
            changed_total += changed.size
        check(f"{tag}.no_stray_writes", changed_total > 0,
              f"{changed_total} rows changed, all of them touched ids")

    # ------------------------------------------------------ train, one chip
    print("== train, one chip", flush=True)
    de = DistributedEmbedding(cfg.embedding_configs(), world_size=1,
                              compute_dtype=jnp.bfloat16)
    state = init_hybrid_state(de, emb_opt, jax.device_put(dense0), tx,
                              jax.random.key(SEED + 1),
                              dtype=jnp.bfloat16)
    jax.block_until_ready(state)
    if not dry:
        ms = devices[0].memory_stats()
        note(f"memory_stats keys: {sorted(ms)}")
        note(f"after init: bytes_in_use {ms['bytes_in_use'] / 2**30:.2f} "
             f"GiB of bytes_limit {ms['bytes_limit'] / 2**30:.2f} GiB "
             f"(CHIP_SPECS hbm {spec.hbm_bytes / 2**30:.0f} GiB)")
    t0 = time.perf_counter()
    w0 = de.get_weights(state.emb_params)
    note(f"get_weights: {sum(w.nbytes for w in w0) / 2**30:.2f} GiB to "
         f"host in {time.perf_counter() - t0:.1f} s")
    if args.chips == 1:
        evals = (make_hybrid_eval_step(de, rows_fn),
                 make_hybrid_eval_step(de, logits_fn))
        agreement("agree.before", de, state, w0, evals, jnp.asarray)

    def staged(i):
        return ([jnp.asarray(c[i]) for c in cats_h],
                (jnp.asarray(num_h[i]), jnp.asarray(lab_h)))

    batches = jax.block_until_ready([staged(i) for i in range(n_steps)])
    step_fn = make_hybrid_train_step(de, loss_fn, tx, emb_opt,
                                     lr_schedule=LR, with_metrics=False)
    losses, step_ms = [], []
    n_timed = min(TIMED_STEPS, n_steps)
    for i in range(n_timed):
        if i == 1:
            built = compiles()
        t0 = time.perf_counter()
        loss, state = step_fn(state, *batches[i])
        jax.block_until_ready((loss, state))
        step_ms.append((time.perf_counter() - t0) * 1e3)
        losses.append(float(loss))
    steady = float(np.median(step_ms[1:]))
    check("train.no_compile_in_window", compiles() == built,
          f"{compiles() - built} program(s) built during steps 2..{n_timed}")
    note(f"first step call (compile + run) {step_ms[0] / 1e3:.1f} s; steady "
         f"step {steady:.1f} ms median of {n_timed - 1} "
         f"(min {min(step_ms[1:]):.1f}, max {max(step_ms[1:]):.1f})")
    taken = n_timed

    if args.chips == 1:
        # report only: does a real capture carry the detpu/ scopes?
        os.environ["DETPU_PROFILE_DIR"] = os.path.join(OUT_DIR, "profile")
        shutil.rmtree(os.environ["DETPU_PROFILE_DIR"], ignore_errors=True)
        with obs.profile_trace("steady_steps"):
            for i in range(taken, taken + PROFILED_STEPS):
                loss, state = step_fn(state, *batches[i])
                jax.block_until_ready((loss, state))
                losses.append(float(loss))
        taken += PROFILED_STEPS
        cap = os.path.join(os.environ["DETPU_PROFILE_DIR"], "steady_steps")
        xplanes = glob.glob(os.path.join(cap, "**", "*.xplane.pb"),
                            recursive=True)
        check("profile.capture_written", len(xplanes) >= 1, cap)
        # the TPU metadata path of utils/traceparse.py: device op events
        # carry their op_name (tf_op / long_name args), and with it the scope
        doc = traceparse.load_trace(traceparse.trace_files(cap)[0])
        meta = [e for e in doc["traceEvents"] if e.get("ph") == "M"]
        dev_pids = {e["pid"] for e in meta
                    if e.get("name") == "process_name"
                    and str(e["args"].get("name")).startswith("/device:TPU")}
        op_lines = {(e["pid"], e["tid"]) for e in meta
                    if e.get("name") == "thread_name"
                    and e["pid"] in dev_pids
                    and e["args"].get("name") == "XLA Ops"}
        ops = [e for e in traceparse.parse_events(doc)
               if (e.pid, e.tid) in op_lines]
        by_phase = {}
        for e in ops:
            by_phase[e.phase] = by_phase.get(e.phase, 0.0) + e.dur
        total_us = sum(by_phase.values())
        size = sum(os.path.getsize(p) for p in glob.glob(
            os.path.join(cap, "**", "*"), recursive=True)
            if os.path.isfile(p))
        note(f"profile: {size / 2**20:.1f} MiB in {cap}; device op events "
             f"{len(ops)}, of which {sum(1 for e in ops if e.phase)} carry "
             f"a detpu/ scope"
             + (f" covering {1 - by_phase.get('', 0.0) / total_us:.1%} of "
                f"{total_us / 1e3 / PROFILED_STEPS:.1f} ms/step of device "
                "op time" if ops else " (no device plane on this backend)"))
        for ph, us in sorted(by_phase.items(), key=lambda kv: -kv[1])[:5]:
            note(f"  {us / 1e3 / PROFILED_STEPS:8.2f} ms/step  "
                 f"{ph or '(no detpu scope)'}")

        loop_fn = make_hybrid_train_loop(de, loss_fn, tx, emb_opt,
                                         lr_schedule=LR, with_metrics=False)
        stack = jax.tree.map(lambda *xs: jnp.stack(xs),
                             *batches[taken:taken + LOOP_K])
        jax.block_until_ready(stack)
        t0 = time.perf_counter()
        loop_losses, state = loop_fn(state, *stack)
        jax.block_until_ready((loop_losses, state))
        note(f"train loop K={LOOP_K} (compile + run) "
             f"{time.perf_counter() - t0:.1f} s")
        losses += [float(x) for x in np.asarray(loop_losses)]
        taken += LOOP_K
        del stack

    check("train.losses_finite", bool(np.isfinite(losses).all()),
          " ".join(f"{x:.4f}" for x in losses))
    check("train.loss_decreased", losses[-1] < losses[0],
          f"{losses[0]:.4f} -> {losses[-1]:.4f}")
    check("train.step_counter", int(state.step) == taken,
          f"state.step {int(state.step)} after {taken} steps")

    if args.chips == 1:
        print("== agreement after training", flush=True)
        w1 = de.get_weights(state.emb_params)
        agreement("agree.after", de, state, w1, evals, jnp.asarray)
        no_stray_writes("agree.after", w0, w1, taken)
        del w1

        # ---------------------------------------------------------- serve
        print("== serve", flush=True)
        srng = np.random.default_rng(SEED + 2)
        reqs = [sv.synthetic_request(srng, sizes, int(srng.integers(1, 9)),
                                     numerical=13)
                for _ in range(SERVE_REQUESTS + 1)]
        # the direct answers, computed (and compiled) before the warm-up
        # marks the steady window: all rows in one padded batch
        logit_eval = make_hybrid_eval_step(de, logits_fn)
        total = sum(len(r.cats[0]) for r in reqs)
        pad = -total % 128

        def direct(st):
            cats_all = [jnp.asarray(np.concatenate(
                [r.cats[t] for r in reqs] + [np.zeros(pad, np.int32)]))
                for t in range(len(sizes))]
            num_all = jnp.asarray(np.concatenate(
                [r.batch for r in reqs] + [np.zeros((pad, 13), np.float32)]))
            out = np.asarray(logit_eval(st, cats_all, num_all))
            offs = np.cumsum([0] + [len(r.cats[0]) for r in reqs])
            return [out[a:b] for a, b in zip(offs[:-1], offs[1:])]

        want = direct(state)
        rt = ServingRuntime(de, logits_fn, state,
                            config=ServeConfig(max_batch=SERVE_MAX_BATCH))
        check("serve.ladder", len(rt.rungs) <= 4, f"rungs {rt.rungs}")
        pub = SnapshotPublisher(rt)
        pub.warm(state)
        t0 = time.perf_counter()
        n_warm = rt.warmup((reqs[0].cats, reqs[0].batch))
        note(f"ladder warm-up: {n_warm} programs in "
             f"{time.perf_counter() - t0:.1f} s")
        # serve from a published copy, as the online runtime does: the
        # train step below donates `state`, and on the chip a donated
        # buffer is really gone
        pub.publish(state, train_step=taken)

        def serve(batch_of_reqs):
            """Closed loop, flushed every fourth request so that the
            coalesced sizes land on several rungs."""
            out = []
            for i, r in enumerate(batch_of_reqs):
                require("serve.admitted", rt.submit(r) is None,
                        f"rid {r.rid}")
                out += rt.poll()
                if i % 4 == 3:
                    out += rt.flush()
            return out + rt.flush()

        results = serve(reqs[:SERVE_REQUESTS])

        def verify(results, want_by_rid, version):
            """Same program family as the direct eval at another padded
            batch size: the agreement's logit tolerance applies."""
            worst = 0.0
            for res in results:
                require("serve.all_served", isinstance(res, Served),
                        f"rid {res.rid}: {res!r}"[:300])
                require("serve.spans_sum_to_latency",
                        abs(sum(res.spans.values()) - res.latency_ms) < 1e-6,
                        f"rid {res.rid}: {res.spans} vs {res.latency_ms}")
                want_r = want_by_rid[res.rid]
                tol = (LOGIT_TOL_ULPS * BF16_ULP
                       * max(1.0, float(np.abs(want_r).max())))
                err = float(np.abs(np.asarray(res.predictions)
                                   - want_r).max())
                worst = max(worst, err)
                require("serve.predictions_match_direct_eval", err <= tol,
                        f"rid {res.rid} max |diff| {err:.2e}, tolerance "
                        f"{tol:.2e}")
                require("serve.version", res.version == version,
                        f"rid {res.rid} answered by v{res.version}")
            check(f"serve.v{version}", True,
                  f"{len(results)} Served, spans sum to latency_ms, logits "
                  f"within {worst:.2e} of a direct eval, all answered by "
                  f"v{version}")

        check("serve.conserved", sorted(r.rid for r in results)
              == [r.rid for r in reqs[:SERVE_REQUESTS]],
              f"{len(results)} results for {SERVE_REQUESTS} requests")
        verify(results, {r.rid: w for r, w in zip(reqs, want)}, 1)
        # train on (donating the state the runtime was built over), then
        # publish the newer view
        loss, state = step_fn(state, *batches[taken])
        jax.block_until_ready((loss, state))
        taken += 1
        want2 = direct(state)[-1]
        pub.publish(state, train_step=taken)
        last = serve(reqs[SERVE_REQUESTS:])
        require("serve.conserved_after_publish", len(last) == 1,
                f"{last!r}"[:200])
        verify(last, {reqs[-1].rid: want2}, 2)
        s = rt.stats()
        check("serve.zero_steady_recompiles",
              s["steady_state_recompiles"] == 0,
              f"{s['steady_state_recompiles']} after {s['served']} served "
              f"over rungs {s['rung_flushes']} and two publications")
        check("train.step_counter_final", int(state.step) == taken,
              f"state.step {int(state.step)}")
        note(f"served latency p50 {s['latency_p50_ms']:.2f} ms, p99 "
             f"{s['latency_p99_ms']:.2f} ms over {s['served']} requests "
             f"(closed loop, one at a time: an observation, not a metric)")
        del rt, pub

    # ---------------------------------------------------------- four chips
    if args.chips == 4:
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        print("== four chips", flush=True)
        del state
        mesh = Mesh(np.array(devices[:4]), ("data",))
        shard = NamedSharding(mesh, P("data"))

        def put4(x):
            return jax.device_put(x, shard)

        de4 = DistributedEmbedding(
            cfg.embedding_configs(), world_size=4,
            strategy="memory_balanced", dp_input=True,
            compute_dtype=jnp.bfloat16)
        used0 = ([d.memory_stats()["bytes_in_use"] for d in devices[:4]]
                 if not dry else None)
        state4 = init_hybrid_state(de4, emb_opt, jax.device_put(dense0), tx,
                                   jax.random.key(SEED + 1), mesh=mesh,
                                   dtype=jnp.bfloat16)
        jax.block_until_ready(state4)
        for k, v in state4.emb_params.items():
            check("four.slab_spans_four_devices",
                  len(v.sharding.device_set) == 4
                  and v.addressable_shards[0].data.shape[0] == 1,
                  f"{k} {v.shape} {v.sharding}")
        if dry:
            note("per-device bytes: skipped — the CPU backend has no "
                 "memory_stats")
        else:
            used = [d.memory_stats()["bytes_in_use"] - u0
                    for d, u0 in zip(devices[:4], used0)]
            quarter = sum(used) / 4
            note("bytes taken by the mesh init per device: "
                 + ", ".join(f"{u / 2**30:.3f} GiB" for u in used)
                 + f"; bytes_in_use now "
                 + ", ".join(f"{d.memory_stats()['bytes_in_use'] / 2**30:.3f}"
                             for d in devices[:4]))
            check("four.quarter_per_device",
                  all(abs(u - quarter) <= QUARTER_BAND * quarter
                      for u in used),
                  f"band +-{QUARTER_BAND:.0%} of {quarter / 2**30:.3f} GiB")
        # the same logical weights the one-chip run started from
        t0 = time.perf_counter()
        state4 = state4._replace(emb_params=de4.set_weights(
            w0, mesh=mesh, dtype=jnp.bfloat16, src_dtype=jnp.bfloat16))
        jax.block_until_ready(state4)
        note(f"set_weights onto the mesh in {time.perf_counter() - t0:.1f} s")
        evals4 = (make_hybrid_eval_step(de4, rows_fn, mesh=mesh),
                  make_hybrid_eval_step(de4, logits_fn, mesh=mesh))
        agreement("four.agree.before", de4, state4, w0, evals4, put4)

        batches4 = jax.block_until_ready(
            [([put4(c[i]) for c in cats_h], (put4(num_h[i]), put4(lab_h)))
             for i in range(FOUR_CHIP_STEPS)])
        step4 = make_hybrid_train_step(de4, loss_fn, tx, emb_opt, mesh=mesh,
                                       lr_schedule=LR, with_metrics=False)
        t0 = time.perf_counter()
        hlo = step4.lower(state4, *batches4[0]).compile().as_text()
        note(f"four-chip step lowered + compiled in "
             f"{time.perf_counter() - t0:.1f} s")
        ops = {}
        # an opcode follows whitespace and opens its operand list; the
        # async spelling is <op>-start / <op>-done
        for m in re.finditer(
                r"(?<=\s)(all-to-all|all-gather|all-reduce|reduce-scatter|"
                r"collective-permute)(?:-start)?\(", hlo):
            ops[m.group(1)] = ops.get(m.group(1), 0) + 1
        want_c = audit.expected_collectives(
            de4, nan_guard=obs.nanguard_enabled(),
            n_dense_leaves=len(jax.tree.leaves(dense0)))
        check("four.three_all_to_alls",
              ops.get("all-to-all", 0) == want_c["all_to_all"] == 3,
              f"compiled step collectives {ops}")
        check("four.no_all_gather", ops.get("all-gather", 0) == 0,
              f"compiled step collectives {ops}")
        del hlo

        losses4, ms4 = [], []
        for i in range(FOUR_CHIP_STEPS):
            if i == 1:
                built = compiles()
            t0 = time.perf_counter()
            loss, state4 = step4(state4, *batches4[i])
            jax.block_until_ready((loss, state4))
            ms4.append((time.perf_counter() - t0) * 1e3)
            losses4.append(float(loss))
        check("four.no_compile_in_window", compiles() == built,
              f"{compiles() - built} program(s) built during steps "
              f"2..{FOUR_CHIP_STEPS}")
        note(f"four-chip first step call {ms4[0] / 1e3:.1f} s; steady step "
             f"{np.median(ms4[1:]):.1f} ms median of {len(ms4) - 1}")
        rel = [abs(a - b_) / abs(b_)
               for a, b_ in zip(losses4, losses[:FOUR_CHIP_STEPS])]
        check("four.losses_match_one_chip",
              bool(np.isfinite(losses4).all())
              and max(rel) <= FOUR_CHIP_LOSS_RTOL,
              "four " + " ".join(f"{x:.5f}" for x in losses4) + " | one "
              + " ".join(f"{x:.5f}" for x in losses[:FOUR_CHIP_STEPS])
              + f" | max rel diff {max(rel):.2e}, tolerance "
              f"{FOUR_CHIP_LOSS_RTOL:.0e}")
        check("four.step_counter", int(state4.step) == FOUR_CHIP_STEPS,
              f"state.step {int(state4.step)}")
        w4 = de4.get_weights(state4.emb_params)
        agreement("four.agree.after", de4, state4, w4, evals4, put4)
        no_stray_writes("four.agree.after", w0, w4, FOUR_CHIP_STEPS)

    # -------------------------------------------------------------- experts
    print("== experts", flush=True)
    gap, tail, finite, stray = experts_share()
    check("experts.tail_rows_zero", finite and tail == 0.0,
          f"largest |value| past the held experts' rows {tail}, forward and "
          f"d lhs; all finite {finite}")
    check("experts.agrees_with_plain_products", gap <= 2.0 ** -7,
          f"worst gap {gap:.2e} of the largest value over out, d lhs, d rhs")
    note(f"lax.ragged_dot on this backend leaves up to {stray} in those rows "
         f"(0 on the CPU; it is not the chip's form)")

    c = obs.counters()
    print(f"programs built {c.get('recompiles', 0)}, of which loaded from the "
          f"persistent compile cache {c.get('persistent_cache_hits', 0)}",
          flush=True)
    if dry:
        print("dry run complete — nothing above was measured on a chip",
              flush=True)
        return 0
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
