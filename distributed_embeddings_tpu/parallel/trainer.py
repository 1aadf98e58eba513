"""Hybrid-parallel training step builder.

Composes the pieces the reference wires manually in its examples
(``examples/dlrm/main.py:201-210``: tape → ``DistributedGradientTape`` →
``optimizer.apply_gradients``) into one jitted SPMD step:

* dense (data-parallel) parameters: autodiff + ``lax.pmean`` + any optax
  transform;
* embedding (model-parallel) slabs: **no autodiff through the tables** — the
  dense model is differentiated w.r.t. the embedding *activations*, and those
  cotangents feed :meth:`DistributedEmbedding.sparse_apply_gradients`, which
  routes them through the reverse all-to-all and applies per-row scatter
  updates (the IndexedSlices path). The slab and its optimizer state are
  donated, so updates are in-place on device.
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..utils import obs
from . import apply as apply_mod
from . import schedule as schedule_mod
from .dist_embedding import DistributedEmbedding
from .grads import resolve_dp_gradient


#: The SINGLE ordering registry of jit-carried trailing aux arguments to
#: the step builders (``make_hybrid_train_step`` / ``_loop`` /
#: ``_eval_step``): ``(kind, parameter_name)`` in the order the aux
#: states trail the fixed ``(state, cat_inputs, batch)`` prefix. Jit
#: donation indices, shard_map in/out specs, checkpoint aux manifests
#: and the resilient driver's rewind all address these positionally, so
#: the order is LOAD-BEARING: a builder that threads them in any other
#: order (or adds an undeclared one) silently donates / rewinds the
#: wrong buffer. The detlint rule ``donated-aux`` reads this tuple by
#: AST and fails ``make lint`` on any step-builder signature whose
#: trailing params are undeclared here or out of this order — add the
#: kind HERE first (future schedule state included), then thread it.
AUX_ARG_REGISTRY = (
    ("telemetry", "telem"),
    ("streaming", "stream"),
)


def _metric_specs(axis_name: str, extra=()):
    """shard_map out_specs for the step-metrics dict: every ``[1]``
    per-device entry concatenates into a ``[world]`` per-rank vector.
    ``extra`` appends conditional key sets (the ``stream_*`` metrics of
    dynamic-table steps)."""
    return {k: P(axis_name) for k in obs.STEP_METRIC_KEYS + tuple(extra)}


def _sq_sum(tree) -> jax.Array:
    """Sum of squares over every leaf of a gradient pytree, in f32."""
    return jax.tree.reduce(
        lambda a, g: a + jnp.sum(jnp.square(g.astype(jnp.float32))),
        tree, jnp.float32(0.0))


def _table_sentinels(de, out_grads, lr):
    """Per-table numerical health sentinels, computed from this device's
    embedding cotangents (O(ids) — never a slab-wide pass): the three
    ``table_*`` entries of :data:`~..utils.obs.STEP_METRIC_KEYS`, each
    ``[1, n_tables]`` so ``out_specs=P(axis)`` stacks them to
    ``[world, n_tables]``. The cotangent is what the sparse backward
    scatters into the slab (times ``lr/world`` for the linear SGD path),
    so a non-finite or exploding entry here IS the row update that would
    have poisoned — or did poison — the named table. Inputs sharing a
    table (``input_table_map``) fold into that table's entry; the update
    bound uses the ``1/world`` pre-scale :meth:`~.dist_embedding.
    DistributedEmbedding.sparse_apply_gradients` defaults to."""
    n_tables = len(de.strategy.global_configs)
    tmap = de.strategy.input_table_map
    per_input = []
    for g in out_grads:
        g32 = g.astype(jnp.float32)
        per_input.append((jnp.sum(jnp.square(g32)),
                          jnp.max(jnp.abs(g32)),
                          jnp.sum(jnp.logical_not(jnp.isfinite(g32)),
                                  dtype=jnp.int32)))
    # a device-varying REAL zero (shard_map vma): tables with no input
    # still need entries, and ``x * 0.0`` would be NaN exactly when the
    # cotangent is — the case these sentinels exist to count
    zvar = de._vary(jnp.float32(0.0))
    sq, mx, nf = [], [], []
    for t in range(n_tables):
        mine = [per_input[i] for i, tt in enumerate(tmap) if tt == t]
        sq.append(sum((m[0] for m in mine), zvar))
        mx.append(jnp.maximum(zvar,
                              jnp.stack([m[1] for m in mine]).max())
                  if mine else zvar)
        nf.append(sum((m[2].astype(jnp.float32) for m in mine), zvar))
    scale = jnp.float32(lr) / de.world_size
    return {
        "table_grad_norm": jnp.sqrt(jnp.stack(sq)).reshape(1, n_tables),
        "table_update_maxabs": (jnp.abs(scale)
                                * jnp.stack(mx)).reshape(1, n_tables),
        "table_nonfinite": jnp.stack(nf).reshape(1, n_tables),
    }


def _microbatch_count(de) -> int:
    """The schedule-declared microbatch count the step builders split
    by (1 = the serialized program, traced through the exact pre-
    pipelining code path)."""
    return int(getattr(de.schedule, "microbatches", 1) or 1)


def _microbatch_inputs(cat_inputs, batch, K: int):
    """Split one per-device batch into K microbatch slices along the
    leading batch dimension: ``[(cat_inputs_k, batch_k), ...]``.

    Dense categorical inputs and every ``batch`` pytree leaf slice rows
    ``[k*b/K, (k+1)*b/K)``. A :class:`~...ops.embedding_lookup.Ragged`
    keeps its FULL static capacity per microbatch (the id count per row
    is dynamic, so a smaller static capacity could truncate a skewed
    microbatch): values gather from the CSR offset of the microbatch's
    first row, row_splits rebase to 0. A COO
    :class:`~...ops.embedding_lookup.SparseIds` converts to CSR first —
    the same conversion the forward's input normalization applies.
    ``b % K != 0`` raises at trace time (unequal microbatches would
    break the exact mean-of-means loss accumulation)."""
    from ..ops.embedding_lookup import Ragged, SparseIds, row_to_split

    def norm(x):
        if isinstance(x, SparseIds):
            return Ragged(values=x.values,
                          row_splits=row_to_split(x.indices,
                                                  x.dense_shape[0]),
                          weights=x.weights)
        return x

    cats = [norm(c) for c in cat_inputs]

    def rows_of(x):
        return x.nrows if isinstance(x, Ragged) else x.shape[0]

    if cats:
        b = rows_of(cats[0])
    else:
        b = jax.tree_util.tree_leaves(batch)[0].shape[0]
    if b % K:
        raise ValueError(
            f"pipelined step: per-device batch {b} does not divide into "
            f"{K} microbatches — pick K | batch (DETPU_MICROBATCH / the "
            "pipelined_schedule argument)")
    mbb = b // K

    def slice_cat(x, k):
        if isinstance(x, Ragged):
            splits = x.row_splits
            lo = splits[k * mbb]
            sub = lax.slice_in_dim(splits, k * mbb, (k + 1) * mbb + 1,
                                   axis=0) - lo
            cap = x.values.shape[0]
            idx = lo + jnp.arange(cap, dtype=splits.dtype)
            vals = jnp.take(x.values, idx, mode="clip")
            wts = (jnp.take(x.weights, idx, mode="clip")
                   if x.weights is not None else None)
            return Ragged(values=vals, row_splits=sub, weights=wts)
        return lax.slice_in_dim(x, k * mbb, (k + 1) * mbb, axis=0)

    out = []
    for k in range(K):
        cats_k = [slice_cat(c, k) for c in cats]
        batch_k = jax.tree.map(
            lambda a, k=k: lax.slice_in_dim(a, k * mbb, (k + 1) * mbb,
                                            axis=0), batch)
        out.append((cats_k, batch_k))
    return out


def _apply_dense_and_assemble(de, state, emb_local, emb_opt_local,
                              new_emb, new_emb_opt, dense_grads,
                              dense_tx, ok, nan_guard):
    """Shared step epilogue of the serialized and pipelined bodies: the
    dense optimizer update, the non-finite guard's small-leaf
    where-selects, and the new-state assembly — ONE body so the guard's
    skip semantics can never drift between the two step variants.

    Slab-shaped leaves are already protected by the sentinel-gated
    scatters; only the small leaves need an explicit select — the dense
    params/opt state (MBs) and non-slab embedding-optimizer aux (Adam's
    step count), never the GB-scale slabs."""
    with obs.scope("dense_update"):
        updates, dense_opt_state = dense_tx.update(
            dense_grads, state.dense_opt_state, state.dense_params)
        dense_params = optax.apply_updates(state.dense_params, updates)

    if nan_guard:
        slab_shapes = {tuple(v.shape) for v in emb_local.values()}

        def sel(new, old):
            return jax.tree.map(lambda n, o: jnp.where(ok, n, o), new, old)

        new_emb_opt = jax.tree.map(
            lambda n, o: (n if tuple(n.shape) in slab_shapes
                          else jnp.where(ok, n, o)),
            new_emb_opt, emb_opt_local)
        dense_params = sel(dense_params, state.dense_params)
        dense_opt_state = sel(dense_opt_state, state.dense_opt_state)

    return HybridTrainState(
        emb_params=de.stacked_view(new_emb),
        emb_opt_state=de.stacked_view(new_emb_opt),
        dense_params=dense_params, dense_opt_state=dense_opt_state,
        step=state.step + 1)


def _finish_metrics(de, metrics, out_grads, dense_grads, loss, ok, state,
                    sstats, lr):
    """Shared tail of the instrumented step's metrics dict (sentinels,
    norms, loss/step/skip counters, ``stream_*`` stats) — the pipelined
    step passes the exactly-reassembled full-batch cotangents so every
    entry keeps serialized semantics."""
    with obs.scope("health_sentinels"):
        # per-table numerical health, next to the nan-guard: names WHICH
        # table's cotangents went non-finite/exploded (the recovery log's
        # "table 3 went unhealthy at step k", not just "step k skipped")
        metrics.update(_table_sentinels(de, out_grads, lr))
    # out_grads are device-varying; the pmean'd loss / resolved dense
    # grads / replicated step are not — _vary marks them for P(axis) out
    metrics["emb_grad_norm"] = jnp.sqrt(_sq_sum(out_grads)).reshape(1)
    metrics["dense_grad_norm"] = de._vary(
        jnp.sqrt(_sq_sum(dense_grads)).reshape(1))
    metrics["loss"] = de._vary(loss.astype(jnp.float32).reshape(1))
    skipped = ((1 - ok.astype(jnp.int32)).reshape(1) if ok is not None
               else jnp.zeros((1,), jnp.int32))
    metrics["skipped_steps"] = de._vary(skipped)
    metrics["step"] = de._vary(state.step.astype(jnp.int32).reshape(1))
    if sstats is not None:
        # this step's (guard-gated) slot-map transition counts — derived
        # from the device-varying routed ids, so P(axis) stacks them per
        # rank like every other metric
        for k, v in sstats.items():
            metrics[f"stream_{k}"] = v
    return metrics


def _pipelined_local_step(de, loss_fn, dense_tx, emb_optimizer,
                          lr_schedule, state, cat_inputs, batch, K,
                          with_metrics=False, nan_guard=False,
                          telemetry_cfg=None, telem=None,
                          streaming_cfg=None, sstate=None):
    """The K-microbatch software-pipelined hybrid step (ROADMAP item 2;
    built when ``de.schedule`` is a :func:`~.schedule.pipelined_schedule`
    with K > 1 — K == 1 never reaches here, it traces the serialized
    program bitwise).

    The per-device batch splits into K microbatches; each runs its own
    id-exchange → lookup → out-exchange → dense fwd/bwd chain under
    ``_mb{k}``-suffixed phase scopes. The chains share NO data
    dependencies until the accumulation point — all microbatches read
    the same parameters, gradients accumulate, ONE dense update and ONE
    sparse apply per width slab run at the end — so XLA's scheduler is
    free to ship microbatch k+1's all-to-alls while microbatch k's
    dense compute runs (the overlap the schedule declares and
    ``make schedule-audit`` / ``make phase-profile`` certify).

    Numerics vs the serialized step: the accumulation leans on the step
    builders' documented ``loss_fn`` contract — a *plain (unweighted)
    mean* over the per-device batch shard. Under that contract each
    microbatch loss is a mean over b/K rows, so per-row cotangents are
    K× the full-batch ones and the 1/K accumulation scale restores them
    exactly for power-of-two K. A loss that is NOT an unweighted mean —
    a sum reduction, or a masked/weighted mean whose denominator varies
    per row subset — violates that contract and silently trains a
    different trajectory under K > 1 (mean-of-means ≠ overall mean);
    keep such losses on the serialized schedule or fold the weighting
    into per-row terms of an unweighted mean. Dense gradients average across microbatches (one pmean per leaf,
    after accumulation — the psum census is K-invariant), the sparse
    apply concatenates the per-microbatch update streams into the same
    single scatter per width slab, and streaming admission stages ONCE
    over the concatenated raw id streams (bitwise the serialized
    decisions — :meth:`~.dist_embedding.DistributedEmbedding
    .streaming_stage`). K > 1 trajectories are float-rounding-
    equivalent, not bitwise: the scatter-add accumulation order over
    duplicate ids differs (microbatch-major instead of batch-major).
    """
    world = de.world_size
    if not de.dp_input:
        raise NotImplementedError(
            "pipelined schedules need dp inputs: mp-input mode has no id "
            "exchange to hide (use dp_input=True or a serialized "
            "schedule)")
    emb_local = de.local_view(state.emb_params)
    emb_opt_local = de.local_view(state.emb_opt_state)
    mbs = _microbatch_inputs(cat_inputs, batch, K)

    losses = []
    dense_grads_list = []
    out_grads_list = []
    res_list = []
    serve_list = []
    for k, (cats_k, batch_k) in enumerate(mbs):
        tag = schedule_mod.microbatch_tag(k)
        with obs.scope(f"embedding_forward{tag}"):
            if streaming_cfg is not None:
                outs, res, serve = de.forward_with_residuals(
                    emb_local, cats_k,
                    streaming=(streaming_cfg, sstate, "serve"),
                    phase_tag=tag)
                serve_list.append(serve)
            else:
                outs, res = de.forward_with_residuals(emb_local, cats_k,
                                                      phase_tag=tag)
        with obs.scope(schedule_mod.PHASE_DENSE + tag):
            loss_k, (dgrads_k, ograds_k) = jax.value_and_grad(
                loss_fn, argnums=(0, 1))(state.dense_params, outs,
                                         batch_k)
        losses.append(loss_k)
        dense_grads_list.append(dgrads_k)
        out_grads_list.append(ograds_k)
        res_list.append(res)

    inv_k = 1.0 / K
    loss = sum(losses[1:], losses[0]) * inv_k
    dense_grads = jax.tree.map(
        lambda *gs: sum(gs[1:], gs[0]) * inv_k, *dense_grads_list)
    if world > 1:
        loss = lax.pmean(loss, de.axis_name)
        dense_grads = jax.tree.map(
            lambda g: resolve_dp_gradient(g, de.axis_name), dense_grads)

    new_telem = None
    if telemetry_cfg is not None:
        # ONE sketch fold + top-k merge over every microbatch's routed
        # ids — the serialized step's telemetry input, reassembled
        with obs.scope("telemetry"):
            new_telem = de.update_telemetry(telem, res_list,
                                            telemetry_cfg)

    # the serialized step's full-batch cotangents, reassembled exactly:
    # concatenate per input across microbatches and undo the K× mean
    # scaling (exact for power-of-two K) — feeds the guard probe, the
    # health sentinels, and the grad-norm metrics with serialized
    # semantics
    cat_grads = [
        jnp.concatenate([og[i] for og in out_grads_list], axis=0) * inv_k
        for i in range(len(out_grads_list[0]))]

    ok = None
    if nan_guard:
        with obs.scope("nanguard"):
            # same lockstep-verdict construction as the serialized step
            # (one pmean — the psum census is K-invariant)
            probe = jnp.float32(0.0) * _sq_sum(cat_grads)
            if world > 1:
                probe = lax.pmean(probe, de.axis_name)
            ok = (jnp.isfinite(loss.astype(jnp.float32))
                  & jnp.isfinite(_sq_sum(dense_grads))
                  & jnp.isfinite(probe))

    lr = lr_schedule(state.step) if callable(lr_schedule) else lr_schedule

    spending = None
    if streaming_cfg is not None:
        # ONE admission-staging pass over the concatenated raw streams:
        # bitwise the serialized step's transition decisions, and an
        # independent compute chain next to every out/grad exchange
        spending = de.streaming_stage(serve_list, streaming_cfg, sstate)

    # per-microbatch reverse exchanges + stream rebuilds (each under its
    # own phase, overlapping other microbatches' dense compute), merged
    # into ONE optimizer scatter per width slab — grad accumulation
    # without a second pass over the slabs
    per_width = {}
    fallback = next(iter(emb_local.values())).dtype
    for k in range(K):
        tag = schedule_mod.microbatch_tag(k)
        with obs.scope(f"sparse_bwd{tag}"):
            pw = apply_mod.cotangent_width_streams(
                de, res_list[k], out_grads_list[k],
                fallback_dtype=fallback, tag=tag)
        for key, tris in pw.items():
            per_width.setdefault(key, []).extend(tris)
    with obs.scope("sparse_apply"):
        new_emb, new_emb_opt = apply_mod.apply_width_streams(
            de, emb_local, emb_opt_local, per_width, emb_optimizer, lr,
            scale=1.0 / (world * K), enable=ok)

    new_sstate = None
    sstats = None
    if streaming_cfg is not None:
        from . import streaming as streaming_mod

        with obs.scope("streaming_commit"):
            new_emb, new_emb_opt, new_sstate, sstats = streaming_mod.commit(
                de, new_emb, spending, sstate, enable=ok,
                opt_state=new_emb_opt, optimizer=emb_optimizer)

    new_state = _apply_dense_and_assemble(
        de, state, emb_local, emb_opt_local, new_emb, new_emb_opt,
        dense_grads, dense_tx, ok, nan_guard)
    aux_out = ()
    if new_telem is not None:
        aux_out += (new_telem,)
    if new_sstate is not None:
        aux_out += (new_sstate,)
    if not with_metrics:
        return (loss, new_state) + aux_out
    metrics = None
    out_dtype = cat_grads[0].dtype if cat_grads else None
    for res in res_list:
        m = de.step_metrics(res, out_dtype=out_dtype)
        if metrics is None:
            metrics = m
        else:
            for mk in m:
                if mk == "out_pad_frac":
                    continue  # static plan property, equal per microbatch
                metrics[mk] = metrics[mk] + m[mk]
    metrics = _finish_metrics(de, metrics, cat_grads, dense_grads, loss,
                              ok, state, sstats, lr)
    return (loss, new_state, metrics) + aux_out


def _hybrid_local_step(de, loss_fn, dense_tx, emb_optimizer, lr_schedule,
                       state, cat_inputs, batch, with_metrics=False,
                       nan_guard=False, telemetry_cfg=None, telem=None,
                       streaming_cfg=None, sstate=None, has_aux=False):
    """One per-device hybrid step (shared by :func:`make_hybrid_train_step`
    and :func:`make_hybrid_train_loop`): forward, one backward producing dp
    gradients (pmean-averaged) and mp cotangents (manual sparse path), both
    optimizer updates, step counter bump.

    ``with_metrics=True`` (static, trace-time) additionally returns the
    :data:`~..utils.obs.STEP_METRIC_KEYS` dict — the embedding layer's
    exchange/overflow metrics plus loss, grad norms, and the step counter.

    ``nan_guard=True`` (static, trace-time; default follows
    ``DETPU_NANGUARD``, which defaults ON) checks the loss and both
    gradient energies for NaN/Inf *inside* the step and, on a non-finite
    verdict, skips the dense AND sparse updates so params and optimizer
    state come out bitwise-unchanged: the slab scatters route every row to
    the dropped sentinel (O(ids) masking, never a slab-wide select) and
    the small dense/aux leaves are ``where``-selected. The step counter
    still advances (the poisoned batch is skipped, not retried), the
    returned loss stays the true non-finite value so the host driver can
    count consecutive skips and escalate, and under ``with_metrics`` the
    per-device ``skipped_steps`` metric flags the skip.

    ``telemetry_cfg`` (static) + ``telem`` (this device's jit-carried
    access-telemetry state, :mod:`~..analysis.telemetry`): when given,
    the step folds the forward's routed ids into the hot-row sketches
    and load accumulators and RETURNS the updated telemetry state as its
    LAST element. Telemetry reads the same residual tensors the metrics
    do and touches nothing in the parameter/optimizer path — with it off
    the step is bit-for-bit the pre-telemetry program.

    ``streaming_cfg`` (static) + ``sstate`` (this device's jit-carried
    streaming-vocab state, :mod:`.streaming`): when given, the forward
    remaps every streaming table's external ids through the slot map
    (admitted ids read their slot, everything else its shared hash
    bucket) and STAGES the admission/eviction transitions; they COMMIT
    next to the nan-guard — a guard-skipped step leaves the slot map,
    sketch, and slabs bitwise-unchanged, exactly like the optimizer
    state, so the rollback/quarantine machinery sees one coherent
    trajectory. The updated streaming state returns as the step's LAST
    element (after the telemetry state when both ride).

    ``has_aux=True`` (static): ``loss_fn`` returns ``(loss, aux)``, ``aux``
    a dict of per-device arrays with a leading dimension (counts the model
    takes on the way, say). It leaves the step as its third element, merged
    into the metrics dict under ``with_metrics``; nothing of the update
    reads it. The serialized step only.

    A ``de.schedule`` with ``microbatches > 1`` (a
    :func:`~.schedule.pipelined_schedule`) routes to
    :func:`_pipelined_local_step` — the K-microbatch latency-hiding
    program with identical call/return signature. K == 1 (the default
    and every serialized schedule) traces THIS body unchanged, so the
    serialized program stays bitwise the pre-pipelining step.
    """
    K = _microbatch_count(de)
    if K > 1:
        if has_aux:
            raise NotImplementedError(
                "has_aux: the pipelined step has no way out for a loss's "
                "auxiliary outputs; use a serialized schedule")
        return _pipelined_local_step(
            de, loss_fn, dense_tx, emb_optimizer, lr_schedule, state,
            cat_inputs, batch, K, with_metrics=with_metrics,
            nan_guard=nan_guard, telemetry_cfg=telemetry_cfg, telem=telem,
            streaming_cfg=streaming_cfg, sstate=sstate)
    world = de.world_size
    # slabs are {width: [world, rows, w]} globally -> [rows, w] per device
    emb_local = de.local_view(state.emb_params)
    emb_opt_local = de.local_view(state.emb_opt_state)
    with obs.scope("embedding_forward"):
        if streaming_cfg is not None:
            outs, res, spending = de.forward_with_residuals(
                emb_local, cat_inputs, streaming=(streaming_cfg, sstate))
        else:
            outs, res = de.forward_with_residuals(emb_local, cat_inputs)
    new_telem = None
    if telemetry_cfg is not None:
        with obs.scope("telemetry"):
            new_telem = de.update_telemetry(telem, res, telemetry_cfg)

    with obs.scope("dense_forward_backward"):
        loss, (dense_grads, out_grads) = jax.value_and_grad(
            loss_fn, argnums=(0, 1), has_aux=has_aux)(
                state.dense_params, outs, batch)
    loss_aux = {}
    if has_aux:
        loss, loss_aux = loss
        # what the loss derived from its shard varies over the mesh already
        loss_aux = {k: v if world == 1 or de.axis_name in jax.typeof(v).vma
                    else de._vary(v) for k, v in loss_aux.items()}
    if world > 1:
        loss = lax.pmean(loss, de.axis_name)
        dense_grads = jax.tree.map(
            lambda g: resolve_dp_gradient(g, de.axis_name), dense_grads)

    ok = None
    if nan_guard:
        with obs.scope("nanguard"):
            # 0 * (local embedding-cotangent energy) is 0 when finite and
            # NaN otherwise; the pmean propagates one device's verdict to
            # every device so all ranks skip in LOCKSTEP — a half-applied
            # step would desync the replicated dense params from the
            # sharded slabs (the routed cotangent blocks carry the NaN to
            # every rank's scatter anyway)
            probe = jnp.float32(0.0) * _sq_sum(out_grads)
            if world > 1:
                probe = lax.pmean(probe, de.axis_name)
            ok = (jnp.isfinite(loss.astype(jnp.float32))
                  & jnp.isfinite(_sq_sum(dense_grads))
                  & jnp.isfinite(probe))

    lr = lr_schedule(state.step) if callable(lr_schedule) else lr_schedule
    with obs.scope("sparse_apply"):
        new_emb, new_emb_opt = de.sparse_apply_gradients(
            emb_local, emb_opt_local, res, out_grads, emb_optimizer, lr,
            enable=ok)

    new_sstate = None
    sstats = None
    if streaming_cfg is not None:
        from . import streaming as streaming_mod

        # commit AFTER the optimizer scatter and UNDER the guard verdict:
        # claimed rows zero post-apply (the evictee's last update is
        # dropped with its slot), slab-shaped optimizer moments reset to
        # the optimizer's fresh-row value in the same commit scatter (an
        # admitted id trains from a fresh-init row AND fresh-init
        # moments, not the evictee's leftovers), and a skipped step
        # leaves slot map, sketch, counters, slabs and moments
        # bitwise-unchanged
        with obs.scope("streaming_commit"):
            new_emb, new_emb_opt, new_sstate, sstats = streaming_mod.commit(
                de, new_emb, spending, sstate, enable=ok,
                opt_state=new_emb_opt, optimizer=emb_optimizer)

    new_state = _apply_dense_and_assemble(
        de, state, emb_local, emb_opt_local, new_emb, new_emb_opt,
        dense_grads, dense_tx, ok, nan_guard)
    aux_out = ()
    if new_telem is not None:
        aux_out += (new_telem,)
    if new_sstate is not None:
        aux_out += (new_sstate,)
    if not with_metrics:
        return (loss, new_state) + ((loss_aux,) if has_aux else ()) + aux_out
    metrics = de.step_metrics(
        res, out_dtype=out_grads[0].dtype if out_grads else None)
    metrics = _finish_metrics(de, metrics, out_grads, dense_grads, loss,
                              ok, state, sstats, lr)
    return (loss, new_state, dict(metrics, **loss_aux)) + aux_out


class HybridTrainState(NamedTuple):
    """All mutable training state. ``emb_params``/``emb_opt_state`` are the
    model-parallel slab dicts ``{width: [world, phys_rows, phys_width]}``
    (lane-packed for narrow widths, see ``ops/packed_slab.py``); the rest
    is replicated."""
    emb_params: Any
    emb_opt_state: Any
    dense_params: Any
    dense_opt_state: Any
    step: jax.Array


def _with_aux_signature(core, tel_on: bool, dyn_on: bool):
    """Give ``core(state, cat, batch, aux_tuple)`` the explicit
    positional signature its aux combination implies — jit donation and
    shard_map specs then address plain positional args (aux order:
    telemetry, then streaming)."""
    if tel_on and dyn_on:
        def step(state, cat_inputs, batch, telem, stream):
            return core(state, cat_inputs, batch, (telem, stream))
    elif tel_on:
        def step(state, cat_inputs, batch, telem):
            return core(state, cat_inputs, batch, (telem,))
    elif dyn_on:
        def step(state, cat_inputs, batch, stream):
            return core(state, cat_inputs, batch, (stream,))
    else:
        def step(state, cat_inputs, batch):
            return core(state, cat_inputs, batch, ())
    return step


def make_hybrid_train_step(de: DistributedEmbedding,
                           loss_fn: Callable,
                           dense_tx: optax.GradientTransformation,
                           emb_optimizer,
                           mesh=None,
                           lr_schedule=1.0,
                           with_metrics: Optional[bool] = None,
                           nan_guard: Optional[bool] = None,
                           telemetry=None,
                           dynamic=None,
                           has_aux: bool = False):
    """Build ``step(state, cat_inputs, batch) -> (loss, state)``.

    Args:
      de: the distributed embedding layer.
      loss_fn: ``loss_fn(dense_params, emb_outputs, batch) -> scalar`` local
        mean loss over the per-device batch shard; with ``has_aux``,
        ``(scalar, aux)``. The dense stack may be anything that takes the
        embedding activations: DLRM's MLPs, or a language model's layers over
        its token table's rows (:mod:`~..models.moe_lm`).
      dense_tx: optax transform for the dense (data-parallel) parameters.
      emb_optimizer: sparse slab optimizer (:class:`~.optimizers.SparseSGD`,
        :class:`~.optimizers.SparseAdagrad`,
        :class:`~.optimizers.SparseMomentum` or
        :class:`~.optimizers.SparseAdam`; the last two carry row-wise
        state, updated lazily for the rows a step touches).
      mesh: required when ``de.world_size > 1``.
      lr_schedule: embedding-optimizer learning rate — a constant or a
        ``step -> lr`` callable (the dense side can use optax schedules
        natively).
      with_metrics: instrument the step with on-device observability
        metrics — the step then returns ``(loss, state, metrics)`` where
        ``metrics`` is the :data:`~..utils.obs.STEP_METRIC_KEYS` dict of
        per-rank ``[world]`` vectors (exchange bytes, routed-id counts,
        ragged-overflow counters, grad norms). ``None`` (default) follows
        ``DETPU_OBS=1``, so an uninstrumented run keeps the 2-tuple
        signature and pays nothing.
      nan_guard: build the step with the on-device non-finite guard — a
        NaN/Inf loss or gradient energy skips BOTH optimizer updates with
        params and optimizer state bitwise-unchanged, advances the step
        counter, returns the true (non-finite) loss, and flags
        ``skipped_steps`` in the metrics. ``None`` (default) follows
        ``DETPU_NANGUARD``, which defaults ON (see
        :func:`~..utils.obs.nanguard_enabled`).
      telemetry: carry jit-threaded access telemetry
        (:mod:`~..analysis.telemetry`: per-table hot-row sketches +
        per-rank load accounting) through the step. EXPLICIT opt-in —
        off by default (``None``/``False``); ``True`` uses the
        ``DETPU_TELEMETRY_*`` sketch geometry; a
        :class:`~..analysis.telemetry.TelemetryConfig` pins it. (No env
        default: telemetry changes the step's CALL arity, so an env
        variable must never flip it under a 3-arg call site — the
        telemetry-aware entry points read ``DETPU_TELEMETRY``
        themselves.) When on,
        the step takes a fourth argument — the telemetry state from
        :func:`~..analysis.telemetry.init_telemetry` (donated, like the
        train state) — and returns the updated state as its LAST
        element: ``step(state, cat_inputs, batch, telem) -> (loss,
        state[, metrics], telem)``. The parameter/optimizer math is
        untouched: telemetry-off steps are bit-for-bit the pre-telemetry
        program, telemetry-on steps change only the extra output.

    ``dynamic`` opts the step into streaming-vocab mode
    (:mod:`.streaming`) with the same explicit-opt-in contract as
    ``telemetry`` (``None``/``False`` off, ``True`` env policy, a
    :class:`~.streaming.StreamingConfig` pins it): the step takes the
    jit-carried streaming state (:func:`~.streaming.init_streaming`,
    donated) as one more trailing argument — AFTER the telemetry state
    when both ride — and returns the updated state last. Under
    ``with_metrics`` the :data:`~..utils.obs.STREAMING_METRIC_KEYS`
    entries join the metrics dict.

    ``has_aux``: ``loss_fn`` returns ``(loss, aux)``, ``aux`` a dict of
    per-device arrays with a leading dimension (a model's own counts: the
    pairs an expert layer routed, say). The step then returns ``(loss,
    state, aux)``, or under ``with_metrics`` the metrics dict with ``aux``'s
    entries merged in; on a mesh every entry stacks per rank like a metric.
    The update reads nothing of it, and a step built without it is the
    program it was. Not with a pipelined schedule.

    The returned step takes data-parallel shards: each categorical input
    ``[local_batch, hotness]`` and ``batch`` any pytree of per-device arrays
    the loss consumes (already sharded by the caller).
    """
    from ..analysis import telemetry as tel
    from . import streaming as streaming_mod

    world = de.world_size
    if with_metrics is None:
        with_metrics = obs.metrics_enabled()
    if nan_guard is None:
        nan_guard = obs.nanguard_enabled()
    tel_cfg = tel.resolve_config(telemetry)
    dyn_cfg = streaming_mod.resolve_config(dynamic)
    n_aux = (tel_cfg is not None) + (dyn_cfg is not None)

    def core(state: HybridTrainState, cat_inputs, batch, aux):
        i = 0
        telem = sstate = None
        if tel_cfg is not None:
            telem = tel.local_state(aux[i])
            i += 1
        if dyn_cfg is not None:
            sstate = streaming_mod.local_state(aux[i])
        out = _hybrid_local_step(de, loss_fn, dense_tx, emb_optimizer,
                                 lr_schedule, state, cat_inputs, batch,
                                 with_metrics=with_metrics,
                                 nan_guard=nan_guard,
                                 telemetry_cfg=tel_cfg, telem=telem,
                                 streaming_cfg=dyn_cfg, sstate=sstate,
                                 has_aux=has_aux)
        if not n_aux:
            return out
        head, aux_out = out[:-n_aux], list(out[-n_aux:])
        stacked = []
        if tel_cfg is not None:
            stacked.append(tel.stacked_state(aux_out.pop(0)))
        if dyn_cfg is not None:
            stacked.append(streaming_mod.stacked_state(aux_out.pop(0)))
        return head + tuple(stacked)

    local_step = _with_aux_signature(core, tel_cfg is not None,
                                     dyn_cfg is not None)
    donate = (0,) + tuple(range(3, 3 + n_aux))
    if world == 1:
        return jax.jit(local_step, donate_argnums=donate)

    if mesh is None:
        raise ValueError("mesh is required for world_size > 1")
    ax = de.axis_name
    state_specs = HybridTrainState(
        emb_params=P(ax), emb_opt_state=P(ax),
        dense_params=P(), dense_opt_state=P(), step=P())
    mspecs = _metric_specs(
        ax, obs.STREAMING_METRIC_KEYS if dyn_cfg is not None else ())
    if has_aux:
        # the loss's own entries are not known before the trace: one spec
        # for the whole dict, every entry per rank as the metrics are
        out_specs = (P(), state_specs, P(ax))
    else:
        out_specs = ((P(), state_specs, mspecs) if with_metrics
                     else (P(), state_specs))
    in_specs = (state_specs, P(ax), P(ax)) + (P(ax),) * n_aux
    out_specs = out_specs + (P(ax),) * n_aux

    sm = jax.shard_map(
        local_step, mesh=mesh,
        in_specs=in_specs,
        out_specs=out_specs)
    return jax.jit(sm, donate_argnums=donate)


def make_hybrid_train_loop(de: DistributedEmbedding,
                           loss_fn: Callable,
                           dense_tx: optax.GradientTransformation,
                           emb_optimizer,
                           mesh=None,
                           lr_schedule=1.0,
                           unroll: int = 1,
                           with_metrics: Optional[bool] = None,
                           nan_guard: Optional[bool] = None,
                           telemetry=None,
                           dynamic=None):
    """Multi-step training driver: ``loop(state, cat_stacks, batch_stacks)
    -> (losses [K], state)`` running K steps inside ONE compiled program via
    ``lax.scan``.

    ``with_metrics`` (default: follow ``DETPU_OBS=1``) instruments every
    scanned step like :func:`make_hybrid_train_step`: the loop then
    returns ``(losses [K], state, metrics)`` with each metrics entry
    stacked ``[K, world]`` (one row per scanned step).

    Per-step host dispatch costs wall-clock (how much on the local chip
    has not been measured); production TPU input pipelines amortize it by
    driving several steps per dispatch. Inputs carry a leading scan axis K: each categorical
    input ``[K, local_batch, ...]`` (Ragged: values ``[K, cap]``, row_splits
    ``[K, b+1]``), ``batch`` any pytree with leading K.

    The per-step semantics (gradients, optimizer updates, step counter) are
    exactly :func:`make_hybrid_train_step`'s — same ``local_step`` body,
    non-finite guard included (``nan_guard``, default ``DETPU_NANGUARD``):
    a poisoned batch inside the scan skips its own updates and the
    remaining scanned steps proceed from the untouched state.

    ``telemetry`` (explicit opt-in, same contract as
    :func:`make_hybrid_train_step`) threads the access-telemetry state
    through the scan carry exactly like the single step: ``loop(state,
    cat_stacks, batch_stacks, telem) -> (losses, state[, metrics],
    telem)`` — every scanned step folds its ids in, ONE carried state
    for the whole dispatch.

    ``dynamic`` (explicit opt-in, same contract as the single step's)
    threads the streaming-vocab state through the scan carry the same
    way — slot-map admissions/evictions accumulate across the scanned
    steps inside one compiled program; the state rides AFTER the
    telemetry state when both are on.
    """
    from ..analysis import telemetry as tel
    from . import streaming as streaming_mod

    world = de.world_size
    if with_metrics is None:
        with_metrics = obs.metrics_enabled()
    if nan_guard is None:
        nan_guard = obs.nanguard_enabled()
    tel_cfg = tel.resolve_config(telemetry)
    dyn_cfg = streaming_mod.resolve_config(dynamic)
    n_aux = (tel_cfg is not None) + (dyn_cfg is not None)

    def body(carry, xs):
        cat_inputs, batch = xs
        state = carry[0] if n_aux else carry
        aux = carry[1:] if n_aux else ()
        i = 0
        telem = sstate = None
        if tel_cfg is not None:
            telem = aux[i]
            i += 1
        if dyn_cfg is not None:
            sstate = aux[i]
        out = _hybrid_local_step(
            de, loss_fn, dense_tx, emb_optimizer, lr_schedule, state,
            cat_inputs, batch, with_metrics=with_metrics,
            nan_guard=nan_guard, telemetry_cfg=tel_cfg, telem=telem,
            streaming_cfg=dyn_cfg, sstate=sstate)
        new_aux = out[len(out) - n_aux:] if n_aux else ()
        out = out[:len(out) - n_aux] if n_aux else out
        if with_metrics:
            loss, state, metrics = out
            ys = (loss, metrics)
        else:
            loss, state = out
            ys = loss
        return ((state,) + tuple(new_aux) if n_aux else state), ys

    def run_scan(carry, cat_stacks, batch_stacks):
        # shared by world == 1 and shard_map (_hybrid_local_step already
        # pmeans the loss and resolves dp gradients for world > 1)
        carry, ys = lax.scan(body, carry, (cat_stacks, batch_stacks),
                             unroll=unroll)
        if with_metrics:
            losses, metrics = ys  # metrics leaves stacked [K, 1]
            return carry, (losses, metrics)
        return carry, (ys, None)

    def core(state, cat_stacks, batch_stacks, aux):
        # local/stacked views once per dispatch, not per scanned step
        i = 0
        locals_ = []
        if tel_cfg is not None:
            locals_.append(tel.local_state(aux[i]))
            i += 1
        if dyn_cfg is not None:
            locals_.append(streaming_mod.local_state(aux[i]))
        carry = (state,) + tuple(locals_) if n_aux else state
        carry, (losses, metrics) = run_scan(carry, cat_stacks,
                                            batch_stacks)
        state = carry[0] if n_aux else carry
        stacked = []
        if n_aux:
            aux_out = list(carry[1:])
            if tel_cfg is not None:
                stacked.append(tel.stacked_state(aux_out.pop(0)))
            if dyn_cfg is not None:
                stacked.append(streaming_mod.stacked_state(aux_out.pop(0)))
        head = ((losses, state, metrics) if with_metrics
                else (losses, state))
        return head + tuple(stacked)

    local_loop = _with_aux_signature(core, tel_cfg is not None,
                                     dyn_cfg is not None)
    donate = (0,) + tuple(range(3, 3 + n_aux))
    if world == 1:
        return jax.jit(local_loop, donate_argnums=donate)

    if mesh is None:
        raise ValueError("mesh is required for world_size > 1")
    ax = de.axis_name
    state_specs = HybridTrainState(
        emb_params=P(ax), emb_opt_state=P(ax),
        dense_params=P(), dense_opt_state=P(), step=P())
    loop_keys = obs.STEP_METRIC_KEYS + (
        obs.STREAMING_METRIC_KEYS if dyn_cfg is not None else ())
    out_specs = ((P(), state_specs,
                  {k: P(None, ax) for k in loop_keys})
                 if with_metrics else (P(), state_specs))
    in_specs = (state_specs, P(None, ax), P(None, ax)) + (P(ax),) * n_aux
    out_specs = out_specs + (P(ax),) * n_aux

    sm = jax.shard_map(
        local_loop, mesh=mesh,
        in_specs=in_specs,
        out_specs=out_specs)
    return jax.jit(sm, donate_argnums=donate)


def make_hybrid_eval_step(de: DistributedEmbedding,
                          pred_fn: Callable,
                          mesh=None,
                          dynamic=None,
                          donate_inputs: bool = False,
                          unpack: Optional[Callable] = None):
    """Build ``eval_step(state, cat_inputs, batch) -> global predictions``.

    The inference analogue of :func:`make_hybrid_train_step` — the reference
    evaluates by running the forward under Horovod and allgathering per-rank
    predictions (``examples/dlrm/main.py:230-243`` there); here the shard_map
    output spec ``P(axis)`` reassembles the global prediction array directly.

    Args:
      de: the distributed embedding layer.
      pred_fn: ``pred_fn(dense_params, emb_outputs, batch) -> predictions``
        over the per-device batch shard.
      mesh: required when ``de.world_size > 1``.
      dynamic: streaming-vocab mode (same resolution as the train step's
        ``dynamic=``): the eval step then takes the carried streaming
        state as a fourth argument — ``eval_step(state, cat_inputs,
        batch, stream)`` — and serves ids through the slot map
        READ-ONLY: admitted ids read their slots, everything else its
        shared bucket; no admissions, no state mutation (the state is
        not donated), so interleaved eval never perturbs the training
        trajectory.
      donate_inputs: donate the ``cat_inputs`` / ``batch`` argument
        buffers to the compiled forward — the serving-runtime mode
        (:mod:`.serving`): each flush builds fresh padded input arrays,
        so their buffers are dead the moment the step consumes them and
        XLA may reuse them in place. The state (and any streaming
        state) is NEVER donated — it must survive every call. Leave off
        for interactive eval where callers re-feed the same arrays.
      unpack: the serving runtime's packed-input mode. The step is then
        ``eval_step(state, packed[, stream])``: ``packed`` stands where
        ``cat_inputs, batch`` stood (``[world x words]``, one row a
        device, split like them by the mesh axis), and the program's
        first stage, under the scope ``serve_unpack``, is ``cat_inputs,
        batch = unpack(packed)`` on each device's own row. One
        host-to-device transfer then feeds a call whatever the number
        of input leaves (:class:`.serving.PackLayout`).
    """
    from . import streaming as streaming_mod

    world = de.world_size
    dyn_cfg = streaming_mod.resolve_config(dynamic)

    # the train step's phase names, so a serve profile attributes its
    # device time the same way (dense_forward: there is no backward)
    def local_eval(state: HybridTrainState, *inputs):
        if unpack is not None:
            with obs.scope("serve_unpack"):
                inputs = (*unpack(inputs[0]), *inputs[1:])
        cat_inputs, batch, *stream = inputs
        with obs.scope("embedding_forward"):
            if dyn_cfg is None:
                outs = de(state.emb_params, cat_inputs)
            else:
                outs, _ = de.forward_with_residuals(
                    state.emb_params, cat_inputs,
                    streaming=(dyn_cfg,
                               streaming_mod.local_state(stream[0]), False))
        with obs.scope("dense_forward"):
            return pred_fn(state.dense_params, outs, batch)

    # inputs only: the state (and streaming state) must survive calls
    n_inputs = 1 if unpack is not None else 2
    donate = tuple(range(1, 1 + n_inputs)) if donate_inputs else ()
    if world == 1:
        return jax.jit(local_eval, donate_argnums=donate)
    if mesh is None:
        raise ValueError("mesh is required for world_size > 1")
    ax = de.axis_name
    state_specs = HybridTrainState(
        emb_params=P(ax), emb_opt_state=P(ax),
        dense_params=P(), dense_opt_state=P(), step=P())
    in_specs = (state_specs,) + (P(ax),) * (
        n_inputs + (dyn_cfg is not None))
    sm = jax.shard_map(
        local_eval, mesh=mesh,
        in_specs=in_specs,
        out_specs=P(ax))
    return jax.jit(sm, donate_argnums=donate)


def replicate_on_mesh(tree, mesh):
    """Place every leaf of a dense (data-parallel) pytree replicated over
    ``mesh`` — the placement the step's ``P()`` out_specs hand back.

    A jitted step's cache key includes each argument's sharding, so a
    state whose dense leaves start on one device and come back from the
    first step replicated over the mesh makes the SECOND step retrace
    and recompile the whole program (and a serving ladder warmed on the
    initial state recompile on the first published snapshot). Starting
    from the steady-state placement makes step 1 and step N one
    program."""
    if mesh is None:
        return tree
    return jax.device_put(tree, NamedSharding(mesh, P()))


def init_hybrid_state(de: DistributedEmbedding, emb_optimizer,
                      dense_params, dense_tx, key, mesh=None,
                      dtype=jnp.float32) -> HybridTrainState:
    """Initialize all state, laid out on the mesh as the train step
    returns it: slabs (and slab-shaped optimizer state) sharded over the
    mesh axis, dense leaves and the step counter replicated."""
    emb_params = de.init(key, dtype=dtype, mesh=mesh)
    emb_opt_state = emb_optimizer.init(emb_params)
    if mesh is not None:
        sharding = NamedSharding(mesh, P(de.axis_name))
        emb_opt_state = jax.tree.map(
            lambda a: jax.device_put(a, sharding), emb_opt_state)
    dense_params = replicate_on_mesh(dense_params, mesh)
    return HybridTrainState(
        emb_params=emb_params,
        emb_opt_state=emb_opt_state,
        dense_params=dense_params,
        dense_opt_state=replicate_on_mesh(dense_tx.init(dense_params),
                                          mesh),
        step=replicate_on_mesh(jnp.zeros((), jnp.int32), mesh))


@jax.jit
def _clone(tree):
    # a + 0 (same dtype) forces a REAL output buffer per leaf — an
    # identity would let the runtime hand the input buffer back
    return jax.tree.map(lambda a: a + jnp.zeros((), a.dtype), tree)


def clone_pytree(tree):
    """Donation-safe deep copy of a jit-carried pytree: fresh device
    buffers holding the source's values, with dtypes and shardings
    preserved (the copy is an elementwise jit, so GSPMD keeps each
    leaf's placement).

    The hybrid train step donates its state every step, so any view
    that must outlive the step — the online runtime's published serving
    snapshots (``parallel/online.py``) — has to be a real copy; and the
    copy must preserve placement so the serving ladder's jit cache keys
    match across published versions (the 0-steady-state-recompiles
    contract). One compile per distinct pytree structure/shape set,
    cache hits thereafter."""
    return _clone(tree)
