"""Share of the HBM peak that the feature path reaches, in percent: the
least bytes its gathers must move (``work.gather_bytes`` of the window's
gathered and prefetched rows) over its device time, over the chip's HBM
bandwidth.  The bytes are the same whatever implements the gather."""

import work

UNIT = "%"


def read(ctx):
    t = ctx.get("trace")
    c = ctx.get("counters")
    if not t or not c:
        return None
    s = t["layer_s"].get("feature")
    if not s:
        return None
    moved = work.gather_bytes(c["gathered_rows"], c["prefetched_rows"], ctx["row_bytes"])
    return 100.0 * moved / s / ctx["peaks"]["hbm_bytes_per_s"]
