"""One run of one cell: set-up, window, comparison, metrics, result line."""

from __future__ import annotations

import contextlib
import gc
import os
import re
import sys
import time

import jax
import numpy as np

from benchmarks.families import system

from . import check, manifest, peaks, serve, tracered, train

COMPARE_REQUESTS = 300   # served requests whose answers are compared a run


class _Laps:
    """Where set-up's seconds go, for standard error."""

    def __init__(self, t_start: float):
        self.t, self.laps = t_start, []

    def lap(self, name: str) -> None:
        now = time.perf_counter()
        self.laps.append(f"{name} {now - self.t:.2f}")
        self.t = now

    def say(self) -> None:
        print("setup_s by part: " + ", ".join(self.laps), file=sys.stderr)


class _HostWatch:
    """What the host did over the window besides the cell's work, for
    standard error: this process's CPU seconds, and each pause of Python's
    collector. A run that reads far off says here whether the host stalled."""

    def __enter__(self):
        self.pauses, self._t = [], 0.0
        self.cpu0 = time.process_time()
        gc.callbacks.append(self._on_gc)
        return self

    def _on_gc(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.pauses.append((time.perf_counter() - self._t,
                                info["generation"]))

    def __exit__(self, *exc):
        gc.callbacks.remove(self._on_gc)
        self.cpu_s = time.process_time() - self.cpu0
        return False

    def __str__(self):
        by_gen = ", ".join(
            f"generation {g}: {len(p)} pauses, longest {max(p) * 1e3:.1f} ms"
            for g in (0, 1, 2)
            for p in [[d for d, gen in self.pauses if gen == g]] if p)
        return (f"host over the window: process CPU {self.cpu_s:.2f} s; "
                f"collector {by_gen or 'never ran'}")


def _memory_peak(devices) -> int:
    stats = [d.memory_stats() for d in devices]
    return max((s or {}).get("peak_bytes_in_use", 0) for s in stats)


def _window_counters(opened: dict) -> dict:
    """What every counter of the program rose by since the snapshot
    ``opened``: ``ctx["counters"]``, which a metric file reads by name
    (``{"reader": "value", "group": "counters", "key": ...}``). A counter
    that a family bumps in its own wrappers is among them."""
    return {k: v - opened.get(k, 0) for k, v in system.counters().items()}


def _metric_values(cell, trace: bool, e2e: dict, ctx: dict, rehearse: bool):
    out = {}
    if not trace:
        for m in cell.end_to_end():
            v = None if rehearse else e2e.get(m["name"])
            out[m["name"]] = {"value": v, "unit": m["unit"]}
        return out
    for m in cell.per_layer():
        v = manifest.read_metric(m["name"], ctx, cell.root)
        if v is None:
            continue  # a reader that finds nothing to read reports nothing
        if rehearse and m["source"] == "device_trace":
            continue
        out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def run(cell: manifest.Cell, seed: int, seconds: float, trace: bool,
        device: dict, rehearse: bool, t_start: float, hooks=None) -> dict:
    """``hooks`` is for the tests that break the timed path underneath:
    ``hooks["step"]`` wraps the compiled step, ``hooks["results"]`` alters the
    served results where they are produced."""
    hooks = hooks or {}
    system.install_compile_listener()
    if trace:
        seconds = min(seconds, float(cell.own["trace_seconds"]))
    capture = tracered.Capture(os.path.join(cell.root, ".bench_trace")) \
        if trace else None
    ctx = {"cell": cell.name, "config": cell.config, "traffic": cell.traffic,
           "chips": cell.chips, "family": cell.family,
           "peaks": None if rehearse else peaks.of(device["kind"])}
    kind = cell.traffic["kind"]
    if kind == "train":
        out = _train(cell, seed, seconds, capture, ctx, t_start, hooks)
    elif kind == "serve":
        out = _serve(cell, seed, seconds, capture, ctx, t_start, hooks)
    else:
        raise SystemExit(f"traffic kind {kind!r} has no driver")
    e2e, numbers, attempted, n_failed = out
    e2e["peak_hbm_gib"] = ctx["memory_peak_bytes"] / 2 ** 30
    correct, compared = check.verdict(numbers, cell.own["limits"])
    device = dict(device, memory_peak_bytes=ctx["memory_peak_bytes"])
    result = {"correct": bool(correct), "attempted": int(attempted),
              "failed": int(n_failed)}
    if trace:
        ctx["trace"] = capture.load()
        device["window_s"] = ctx["window_s"]
        device["busy_s"] = tracered.busy_seconds(ctx["trace"])
        result["breakdown"] = tracered.breakdown(ctx["trace"])
    result["metrics"] = _metric_values(cell, trace, e2e, ctx, rehearse)
    result["device"] = device
    result["compared"] = compared
    return result


def _train(cell, seed, seconds, capture, ctx, t_start, hooks):
    fam, cfg, tr = ctx["family"], cell.config, cell.traffic
    laps = _Laps(t_start)
    laps.lap("imports and backend")
    built = fam.build(cfg, tr, seed)
    jax.block_until_ready(built.state)
    laps.lap("weights")
    batches = fam.train_batches(cfg, tr, seed)
    staged = jax.block_until_ready([fam.stage(built, b) for b in batches])
    laps.lap("batches")
    step = fam.train_step(built, tr)
    if "step" in hooks:
        step = hooks["step"](step)
    prog, state = fam.first_steps(built, tr, step, staged, batches, seed)
    laps.lap("first three steps and their read-outs")
    laps.say()
    per_step = int(fam.samples_per_step(cfg, tr))
    opened = system.counters()
    e2e = {"setup_s": time.perf_counter() - t_start}
    with _HostWatch() as host, capture or contextlib.nullcontext():
        sps, n, dt, state, losses, stalls = train.window(
            step, state, staged, per_step, seconds, train.CHECK_STEPS,
            span=capture.span if capture else None)
    counters = _window_counters(opened)
    counters["compiles_in_window"] = counters.get("recompiles", 0)
    e2e["samples_per_s"] = sps
    ctx["memory_peak_bytes"] = _memory_peak(jax.devices()[:cell.chips])
    ctx.update(window_s=dt, steps=n, samples=n * per_step, counters=counters)
    if capture:
        ctx["work"] = fam.step_work(cfg, tr, batches)
    del state, staged
    ref = fam.reference_numbers(cfg, tr, batches, seed)
    bad = int(np.sum(~np.isfinite(losses)))
    print(f"window: {n} steps in {dt:.3f} s; the host's longest wait in a "
          + ", ".join(f"{k} {v * 1e3:.0f} ms" for k, v in stalls.items())
          + f"; {host}", file=sys.stderr)
    print(f"window losses: first {losses[0]:.6g} last {losses[-1]:.6g} "
          f"not finite {bad} of {n}; reference's first three "
          f"{ref['losses']}", file=sys.stderr)
    return e2e, fam.train_numbers(prog, ref), n, bad


def _say_serve_window(schedule, results, t_sub, t_last, lat, host) -> None:
    """Standard error's account of a serving window: the percentiles, the p95
    of each quarter, the longest waits and the slowest flushes by stage."""
    print(f"window: {len(schedule)} requests, last reply at {t_last:.3f} s; "
          "latency from due times p50 {:.2f} p75 {:.2f} p90 {:.2f} p95 {:.2f} "
          "p99 {:.2f} max {:.2f} ms; p95 by quarter of the window {}; {}".format(
              *np.percentile(lat, [50, 75, 90, 95, 99, 100]),
              [round(float(np.percentile(q, 95)), 2)
               for q in np.array_split(lat, 4)], host), file=sys.stderr)
    late = (t_sub - schedule.due_s) * 1e3
    waits = {i: r.spans["queue_wait_ms"] for i, r in results.items()
             if not serve.failed(r)}
    longest = max(waits, key=waits.get, default=0)
    print(f"longest wait before submit {late.max():.1f} ms (due at "
          f"{schedule.due_s[int(late.argmax())]:.2f} s), in the queue "
          f"{waits.get(longest, 0.0):.1f} ms (due at "
          f"{schedule.due_s[longest]:.2f} s)", file=sys.stderr)
    print("slowest flushes (due time s, rung, ms in each stage): " + "; ".join(
        f"{schedule.due_s[i]:.2f} {r.rung} " + " ".join(
            f"{k[:-3]} {v:.1f}" for k, v in r.spans.items()
            if k != "queue_wait_ms")
        for i, r in serve.slowest_flushes(results, 3)), file=sys.stderr)


def _serve(cell, seed, seconds, capture, ctx, t_start, hooks):
    fam, cfg, tr = ctx["family"], cell.config, cell.traffic
    laps = _Laps(t_start)
    laps.lap("imports and backend")
    built = fam.build(cfg, tr, seed)
    jax.block_until_ready(built.state)
    laps.lap("weights")
    rt = fam.serving_runtime(built, tr["serve"])
    schedule = fam.serve_schedule(cfg, tr, seed, seconds)
    requests = fam.requests_of(schedule)
    laps.lap("requests")
    rt.warmup(schedule.request(0))
    laps.lap("rungs")
    # Every request of the window exists already: some 400 k arrays that
    # Python's collector would walk in each full pass inside the window, 100 ms
    # and more a pass with the loop stopped. They are the load generator's, not
    # the runtime's: put them out of the collector's reach. What the window
    # allocates is collected as ever.
    gc.collect()
    gc.freeze()
    laps.lap("collector")
    laps.say()
    opened = system.counters()
    e2e = {"setup_s": time.perf_counter() - t_start}
    try:
        with _HostWatch() as host, capture or contextlib.nullcontext():
            results, t_sub, t_last = serve.open_loop(
                rt, schedule, requests,
                span=capture.span if capture else contextlib.nullcontext)
    finally:
        gc.unfreeze()
    counters = _window_counters(opened)
    counters["serve_recompiles"] = rt.steady_recompiles()
    if "results" in hooks:
        hooks["results"](results)
    lat = serve.latencies_ms(schedule.due_s, t_sub, results)
    _say_serve_window(schedule, results, t_sub, t_last, lat, host)
    sizes = np.diff(schedule.offsets)
    served = [i for i, r in results.items() if not serve.failed(r)]
    # serve_p<q>_ms for whichever percentiles BENCHMARK.json lists end to end
    for m in cell.end_to_end():
        q = re.fullmatch(r"serve_p(\d+)_ms", m["name"])
        if q:
            e2e[m["name"]] = float(np.percentile(lat, int(q.group(1))))
    e2e["served_samples_per_s"] = float(sizes[served].sum() / max(t_last, 1e-9))
    ctx["memory_peak_bytes"] = _memory_peak(jax.devices()[:cell.chips])
    stats = rt.stats()
    ctx.update(
        window_s=max(t_last, float(schedule.due_s[-1])),
        samples=int(sizes[served].sum()), flushes=int(stats["flushes"]),
        counters=counters,
        # every number of the runtime's own summary, under the runtime's name
        stats={k: v for k, v in stats.items()
               if isinstance(v, (int, float)) and not isinstance(v, bool)},
        ratios={"serve_pad_fraction": float(stats["pad_fraction"])},
        spans={
            "queue_wait_ms": [results[i].spans["queue_wait_ms"] for i in served],
            "coalesce_ms": [results[i].spans["coalesce_ms"] for i in served],
            "gen_late_ms": list((t_sub - schedule.due_s) * 1e3),
            "latency_ms": list(lat)})
    rt.state = None
    del rt
    built.state = None
    picked = serve.sample_to_compare(seed, results, sizes, COMPARE_REQUESTS)
    numbers = fam.serve_numbers(
        schedule, results, picked,
        fam.reference_answers(cfg, schedule, picked, seed))
    numbers["unanswered"] = float(len(schedule) - len(results))
    n_failed = len(schedule) - len(served)
    return e2e, numbers, len(schedule), n_failed
