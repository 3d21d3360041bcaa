"""Share of the rows the prefetch stage moved to the device that were
missed rows, in percent: 100 x ``prefetched_rows`` / ``staged_rows`` over
the window (the rest is the packs' pow2 padding)."""

UNIT = "%"
COUNTERS = ("prefetched_rows", "staged_rows")


def read(ctx):
    c = ctx.get("counters")
    if not c or not c.get("staged_rows"):
        return None
    return 100.0 * c["prefetched_rows"] / c["staged_rows"]
