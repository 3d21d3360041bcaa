"""Whole-step model FLOP utilization, in percent: the forward FLOPs a batch
needs (``work.forward_flops``) times the batches completed in the traced
window, over the window's length, over the chip's peak FLOP/s."""

UNIT = "%"


def read(ctx):
    t = ctx.get("trace")
    if not t or not ctx.get("batches") or not t["window_s"]:
        return None
    rate = ctx["flops_per_batch"] * ctx["batches"] / t["window_s"]
    return 100.0 * rate / ctx["peaks"]["flops_per_s"]
