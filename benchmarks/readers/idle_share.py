"""The share (%) of the traced window in which no operation ran on a device."""
from benchmarks.lib import tracered


def read(ctx, spec):
    trace = ctx.get("trace")
    if trace is None or not trace.devices or not ctx.get("window_s"):
        return None
    return 100.0 * (1.0 - tracered.busy_seconds(trace) / ctx["window_s"])
