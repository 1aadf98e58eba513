"""The share (%) of device 0's idle time (the gaps between its operations, as
in ``tracered.breakdown``) that falls inside the program's spans of one name,
or, with ``outside``, inside none of them. ``within_flush`` takes only the
spans that lie inside a ``serve/flush`` span of their thread."""
from benchmarks.lib import progspans, tracered


def read(ctx, spec):
    trace, spans = ctx.get("trace"), progspans.of_ctx(ctx)
    if trace is None or not trace.devices or not spans:
        return None
    if spec.get("within_flush"):
        spans = [s for _, inside in progspans.flushes(spans) for s in inside]
    named = tracered.union((s.start, s.end) for s in spans
                           if s.name == spec["span"])
    dev0 = tracered.union((o.start, o.start + o.dur) for o in trace.ops
                          if o.device == 0)
    gaps = [(a[1], b[0]) for a, b in zip(dev0, dev0[1:]) if b[0] > a[1]]
    if not named or not gaps:
        return None
    inside, j = 0.0, 0
    for gs, ge in gaps:
        while j < len(named) and named[j][1] <= gs:
            j += 1
        k = j
        while k < len(named) and named[k][0] < ge:
            inside += min(ge, named[k][1]) - max(gs, named[k][0])
            k += 1
    share = inside / tracered.total(gaps)
    return 100.0 * (1.0 - share if spec.get("outside") else share)
