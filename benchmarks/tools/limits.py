"""Read, on the chip and in one process, the numbers a cell's limits are set
from: the program against the reference on many seeds (the lower readings), and
on a few of them the control (the reference in float8 in the program's place)
and the planted faults (the upper readings).

    python benchmarks/tools/limits.py --workload <cell> --seeds 12 --controls 3

It is not part of a benchmark run. Prints one JSON object a line.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2_200_000_000)
    ap.add_argument("--seed-list", default="",
                    help="these seeds, comma-separated, ahead of the others")
    ap.add_argument("--seconds", type=float, default=4.0,
                    help="serving: the short window at the cell's own load")
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args(argv)

    from benchmarks.lib import manifest
    cell = manifest.Cell(args.workload)
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={cell.chips}")
    from benchmarks.lib import program
    program.ensure_compile_cache()
    from benchmarks.lib import check, runner, serve, traffic, train
    import numpy as np

    cfg, tr = cell.config, cell.traffic
    seeds = [int(x) for x in args.seed_list.split(",") if x] \
        + [args.first_seed + 7919 * k for k in range(args.seeds)]

    def say(**kw):
        print(json.dumps(kw), flush=True)

    def program_numbers(seed, batches):
        """The compiled step's first three steps, as a run's set-up reads
        them."""
        one_hot = tr["hotness"]["kind"] == "one"
        built = program.build(cfg, seed, combiner=None if one_hot else "sum",
                              dense_lr=float(tr["dense_lr"]))
        staged = [program.stage(built, b) for b in batches]
        step = program.train_step(built, float(tr["emb_lr"]),
                                  float(tr["dense_lr"]))
        return train.first_steps(built, tr, step, staged, batches, seed)[0]

    for k, seed in enumerate(seeds):
        t0 = time.perf_counter()
        if tr["kind"] == "train":
            batches = traffic.train_batches(
                dict(tr, distinct_batches=train.CHECK_STEPS),
                cfg["table_sizes"], int(cfg["num_numerical"]), seed)
            prog = program_numbers(seed, batches)
            ref = train.reference_numbers(cfg, tr, batches, seed)
            say(seed=seed, who="program", numbers=check.train_numbers(prog, ref),
                seconds=time.perf_counter() - t0)
            if k >= args.controls:
                continue
            if cell.chips > 1:
                with program.exchange_left_out():
                    bad = program_numbers(seed, batches)
                say(seed=seed, who="fault_no_exchange",
                    numbers=check.train_numbers(bad, ref))
            for who, kw in (("control_float8", {"precision": "float8"}),
                            ("fault_half_batch", {"fault": "half_batch"})):
                low = train.reference_numbers(cfg, tr, batches, seed, **kw)
                say(seed=seed, who=who, numbers=check.train_numbers(low, ref))
        else:
            built = program.build(cfg, seed)
            rt = program.serving_runtime(built, tr["serve"])
            schedule = traffic.serve_schedule(
                tr, cfg["table_sizes"], int(cfg["num_numerical"]), seed,
                args.seconds)
            rt.warmup(schedule.request(0))
            results, _, _ = serve.open_loop(rt, schedule,
                                            serve.requests_of(schedule))
            rt.state = None
            del rt, built
            picked = serve.sample_to_compare(
                seed, results, np.diff(schedule.offsets),
                runner.COMPARE_REQUESTS)
            want = serve.reference_logits(cfg, schedule, picked, seed)
            nums = serve.compare(schedule, results, picked, want)
            nums["failed"] = float(sum(serve.failed(r)
                                       for r in results.values()))
            say(seed=seed, who="program", numbers=nums,
                seconds=time.perf_counter() - t0)
            if k < args.controls:
                low = serve.reference_logits(cfg, schedule, picked, seed,
                                             precision="float8")
                say(seed=seed, who="control_float8", numbers={
                    "logit_gap": float(np.abs(low - want).max())})
    return 0


if __name__ == "__main__":
    sys.exit(main())
