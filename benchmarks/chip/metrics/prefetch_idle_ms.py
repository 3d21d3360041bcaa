"""Device idle inside the prefetch stage per batch, in ms: the time the
window's thread spends in ``prefetch`` and its children ``prefetch:pull``,
``prefetch:scan``, ``prefetch:index`` and ``prefetch:join`` (the program's
spans, on the window's line of the trace) in which no operation ran on the
device."""

import trace_reduce

UNIT = "ms"
SPANS = {"prefetch", "prefetch:pull", "prefetch:scan", "prefetch:index", "prefetch:join"}


def read(ctx):
    spans = ctx.get("spans")
    if not spans or not ctx.get("batches"):
        return None
    line = trace_reduce.window_line(spans)
    intervals = trace_reduce.span_intervals(spans, SPANS, line)
    if line is None or not intervals:
        return None
    idle = trace_reduce.idle_within(ctx["busy"], ctx["window"], intervals)
    return idle / ctx["batches"] / 1e6
