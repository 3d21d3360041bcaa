"""Host time in the per-batch device-to-host pulls per batch, in ms: the
time the program's ``sync:num_unique``, ``sync:stats``, ``sync:telemetry``,
``sync:logits`` and ``prefetch:pull`` spans cover inside the window.  Each
waits for the device work queued before it, so this is waiting, not
copying alone."""

import trace_reduce

UNIT = "ms"
SPANS = {"sync:num_unique", "sync:stats", "sync:telemetry", "sync:logits", "prefetch:pull"}


def read(ctx):
    spans = ctx.get("spans")
    if not spans or not ctx.get("batches"):
        return None
    intervals = trace_reduce.span_intervals(spans, SPANS)
    if not intervals:
        return None
    return trace_reduce.covered_ns(ctx["window"], intervals) / ctx["batches"] / 1e6
