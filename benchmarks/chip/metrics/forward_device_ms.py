"""Device time of the GNN forward programs (``jit_forward*``) per batch, in
the traced window."""

UNIT = "ms"


def read(ctx):
    t = ctx.get("trace")
    if not t or not ctx.get("batches"):
        return None
    s = t["layer_s"].get("forward")
    return None if not s else s / ctx["batches"] * 1e3
