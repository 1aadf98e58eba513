"""A percentile of one kind of span's durations (ms) over the window."""
import numpy as np


def read(ctx, spec):
    xs = ctx.get("spans", {}).get(spec["span"])
    if xs is None or len(xs) == 0:
        return None
    return float(np.percentile(np.asarray(xs, np.float64), spec["quantile"]))
