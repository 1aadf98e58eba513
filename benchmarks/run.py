"""The benchmark's one command.

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, on the machine it is started on. Without an accelerator, or with
fewer chips than the cell asks for, it exits non-zero and prints no result;
``--rehearse-cpu`` alone runs the same code on the CPU, says so in ``device``
and reports no device metric (it is for the tests).
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="run on the CPU; proves nothing about the chip")
    args = ap.parse_args(argv)

    from benchmarks.lib import manifest
    cell = manifest.Cell(args.workload)
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={cell.chips}")
    try:
        from benchmarks.families import system
    except ImportError as e:
        print(f"the system under test is not in this checkout: {e}",
              file=sys.stderr)
        return 3
    system.ensure_compile_cache()
    import jax
    if args.rehearse_cpu:
        jax.config.update("jax_platforms", "cpu")
    devices = jax.devices()
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    if not args.rehearse_cpu and device["platform"] != "tpu":
        print(f"no accelerator: JAX reports {device}", file=sys.stderr)
        return 2
    if len(devices) < cell.chips:
        print(f"{cell.name} needs {cell.chips} chips, JAX reports "
              f"{len(devices)}", file=sys.stderr)
        return 2

    from benchmarks.lib import runner
    result = runner.run(cell, args.seed, args.seconds, bool(args.trace),
                        device, rehearse=args.rehearse_cpu, t_start=T_START)
    from benchmarks.lib import check
    check.print_compared(result["compared"], result["correct"])
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
