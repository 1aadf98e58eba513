"""A kernel's share of its roofline: the least time the chip could take for
the work its scopes did in a step, over the device time they took."""
from benchmarks.lib import tracered, work


def read(ctx, spec):
    trace, steps, w = ctx.get("trace"), ctx.get(spec["per"]), ctx.get("work")
    if trace is None or not trace.devices or not steps or not w \
            or not ctx.get("peaks"):
        return None
    seconds = tracered.scope_seconds(trace, spec["scopes"],
                                     spec.get("exclude", ())) / steps
    if seconds <= 0.0:
        return None
    cfg, chips = ctx["config"], ctx["chips"]
    flops = nbytes = 0.0
    if spec["work"] == "lookup":
        nbytes = work.lookup_bytes(cfg, w["ids_per_step"],
                                   w["outputs_per_step"])
    elif spec["work"] == "apply":
        nbytes = work.apply_bytes(cfg, w["ids_per_step"],
                                  w["distinct_rows_per_step"])
    elif spec["work"] == "dense_train":
        flops = work.dense_train_flops_per_sample(cfg) \
            * ctx["samples"] / ctx["steps"]
    else:
        raise SystemExit(f"no work function {spec['work']!r}")
    # the step's work is spread over the chips; the time is a device's own
    return work.roofline_share(flops / chips, nbytes / chips, seconds,
                               ctx["peaks"])
