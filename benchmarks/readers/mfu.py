"""The whole step's share (%) of the chips' peak FLOP/s: the model's FLOP a
sample (nothing recomputed) times the samples a second of the traced window."""
from benchmarks.lib import work


def read(ctx, spec):
    if not ctx.get("peaks") or not ctx.get("samples") or not ctx.get("window_s") \
            or ctx.get("trace") is None or not ctx["trace"].devices:
        return None
    per_sample = {"dense_train": work.dense_train_flops_per_sample,
                  "dense_forward": work.dense_forward_flops_per_sample,
                  }[spec["flops"]](ctx["config"])
    rate = ctx["samples"] / ctx["window_s"]
    return 100.0 * per_sample * rate / (ctx["chips"]
                                        * ctx["peaks"]["flops_per_s"])
