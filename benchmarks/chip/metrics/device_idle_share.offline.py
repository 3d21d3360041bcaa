"""Share of the traced window in which no operation ran on the device, in
percent (offline cells): 100 x (1 - busy / window), busy being the union of
the device's op intervals."""

UNIT = "%"


def read(ctx):
    t = ctx.get("trace")
    if not t or not t["window_s"] or t["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
