"""The GAT forward's share of its roofline, in percent: the least time the
chip could take for a batch's forward, ``max(flops / peak FLOP/s, bytes /
peak HBM bytes/s)``, over the forward layer's device time per batch.

``flops`` is the model plug-in's ``forward_flops`` (``ctx["flops_per_batch"]``)
and ``bytes`` its ``forward_bytes``: each distinct input row of a batch read
once (the window's ``unique_rows`` over its batches), each layer's output
written and read once, the weights read once.  Neither counts what an
implementation materialises, so no faster program can read above 100%.

Returns None where the forward layer has no device time, where the
window's ``attn_edges`` (edge scores the program computed, heads counted)
is not the plug-in's per-batch count times the batches, or where the
program reported no unique rows (dedup off).
"""

UNIT = "%"
COUNTERS = ("attn_edges", "unique_rows")


def read(ctx):
    t, c, n = ctx.get("trace"), ctx.get("counters"), ctx.get("batches")
    if not t or not c or not n:
        return None
    s = t["layer_s"].get("forward")
    if not s or not c.get("unique_rows"):
        return None
    model, cfg = ctx["model"], ctx["config"]
    shape = (ctx["dims"], cfg["fanouts"], ctx["batch"], cfg["model"])
    if c.get("attn_edges") != n * model.attn_edges(*shape):
        return None
    flops = ctx["flops_per_batch"]
    moved = model.forward_bytes(*shape, unique_rows=c["unique_rows"] / n)
    peaks = ctx["peaks"]
    least = max(flops / peaks["flops_per_s"], moved / peaks["hbm_bytes_per_s"])
    return 100.0 * least / (s / n)
