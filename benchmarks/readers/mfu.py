"""The whole step's share (%) of the chips' peak FLOP/s: the model's FLOP a
sample (nothing recomputed), counted by the cell's family
(``FLOPS[<the metric file's "flops">]``), times the samples a second of the
traced window."""


def read(ctx, spec):
    if not ctx.get("peaks") or not ctx.get("samples") or not ctx.get("window_s") \
            or ctx.get("trace") is None or not ctx["trace"].devices:
        return None
    count = ctx["family"].FLOPS.get(spec["flops"])
    if count is None:
        raise SystemExit(f"no FLOP count {spec['flops']!r}")
    per_sample = count(ctx["config"])
    rate = ctx["samples"] / ctx["window_s"]
    return 100.0 * per_sample * rate / (ctx["chips"]
                                        * ctx["peaks"]["flops_per_s"])
