"""Device time of the feature path per batch, in the traced window.

The feature path has no program of its own yet: it is every device program
that is neither sampling nor the forward (the gather, the prefetch scatter,
the hit-mask expansion and the small reductions beside them)."""

UNIT = "ms"


def read(ctx):
    t = ctx.get("trace")
    if not t or not ctx.get("batches"):
        return None
    s = t["layer_s"].get("feature")
    return None if not s else s / ctx["batches"] * 1e3
