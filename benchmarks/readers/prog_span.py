"""One of the program's own spans over the flushes of the traced window: for
each ``serve/flush`` span, the summed duration (ms) or the count of the spans
of that name inside it on its thread (the flush's own duration where the name
is the flush's), then a percentile, the maximum or the mean over the flushes."""
import numpy as np

from benchmarks.lib import progspans


def read(ctx, spec):
    spans = progspans.of_ctx(ctx)
    groups = progspans.flushes(spans) if spans else []
    name = spec["span"]
    picked = [[f] if name == progspans.FLUSH
              else [s for s in inside if s.name == name]
              for f, inside in groups]
    if not any(picked):
        return None
    per_flush = np.asarray(
        [len(p) if spec["take"] == "count" else 1e3 * sum(s.dur for s in p)
         for p in picked], np.float64)
    how = spec["over"]
    if how == "mean":
        return float(per_flush.mean())
    return float(np.percentile(per_flush, {"max": 100}.get(how, how)))
