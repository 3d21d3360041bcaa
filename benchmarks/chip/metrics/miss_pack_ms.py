"""Host time of the miss-row pack per batch, in ms: the time the program's
``prefetch:pack`` spans cover inside the window (on the ``dci-miss-pack``
worker; on the window's thread for a batch whose every row missed)."""

import trace_reduce

UNIT = "ms"


def read(ctx):
    spans = ctx.get("spans")
    if not spans or not ctx.get("batches"):
        return None
    intervals = trace_reduce.span_intervals(spans, {"prefetch:pack"})
    if not intervals:
        return None
    return trace_reduce.covered_ns(ctx["window"], intervals) / ctx["batches"] / 1e6
