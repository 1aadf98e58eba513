"""One counter's rise over the window divided by another's, times a count
that the configuration states where the metric's file names its key
(``times_config``): pairs a step, or the fullest expert's pairs over an
expert's even share of them."""


def read(ctx, spec):
    c = ctx.get("counters") or {}
    top, under = c.get(spec["key"]), c.get(spec["over"])
    if top is None or not under:
        return None
    times = ctx["config"][spec["times_config"]] if "times_config" in spec \
        else 1
    return float(top) * float(times) / float(under)
