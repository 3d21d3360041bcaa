"""Host time of the missed rows' ``jax.device_put`` per batch, in ms: the
time the program's ``prefetch:put`` spans cover inside the window.  The
put returns before its copy ends, so this is the host's part only."""

import trace_reduce

UNIT = "ms"


def read(ctx):
    spans = ctx.get("spans")
    if not spans or not ctx.get("batches"):
        return None
    intervals = trace_reduce.span_intervals(spans, {"prefetch:put"})
    if not intervals:
        return None
    return trace_reduce.covered_ns(ctx["window"], intervals) / ctx["batches"] / 1e6
