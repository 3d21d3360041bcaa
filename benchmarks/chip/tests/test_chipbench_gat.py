"""The GAT plug-in, its roofline reader and its cell (CPU): the numpy GAT
against the program's forward at a tiny size, the plug-in's counts at the
cell's shapes, the reader's arithmetic, and a traced run of the cell."""

import json
import pathlib
import sys

import numpy as np
import pytest

CHIP = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(CHIP))
sys.path.insert(0, str(CHIP.parents[1] / "src"))

import harness  # noqa: E402
import reference  # noqa: E402

FANOUTS = (4, 3, 2)
BATCH = 16
# Both sides compute in float32 on the CPU: the same products summed in a
# different order differ by a few units of the last place (2^-24 relative
# each) over sums of at most a few hundred terms, so a few 1e-6 of the
# logit scale.  A wrong row, neighbour block, head or layer moves logits by
# the order of the scale itself.
TOL = 1e-5
# 2 heads at the test's widths, so that the hidden layers concatenate
# (2 x 16 = 32) and the last averages (2 x 5).
TINY_GAT = {"heads": 2}
GAT_PRODUCTS = {"name": "gat", "num_layers": 3, "hidden": 128, "heads": 4}
PRODUCTS = {"feat_dim": 100, "num_classes": 47}
CELL_FANOUTS = [15, 10, 5]
CELL_DIMS = [100, 512, 512, 47]


def gat():
    return harness.load_module(CHIP / "models" / "gat.py", "model")


def reader():
    return harness.load_module(CHIP / "metrics" / "gat_forward_roofline.py", "metric")


def test_reference_matches_the_program_forward():
    import jax.numpy as jnp

    from repro.models.gnn import forward

    rng = np.random.default_rng(7)
    table = rng.standard_normal((300, 24)).astype(np.float32)
    params = gat().make_weights([24, 32, 32, 5], 3, TINY_GAT)
    params_np = [{k: np.asarray(v) for k, v in p.items()} for p in params]
    size = reference.frontier_sizes(BATCH, FANOUTS)[-1]
    ids = rng.integers(0, table.shape[0], size).astype(np.int32)  # with duplicates
    ref = reference.forward(gat(), params_np, table, ids, BATCH, FANOUTS, block=5)
    got = np.asarray(forward(params, jnp.asarray(table[ids]), model="gat", fanouts=FANOUTS))
    assert got.shape == ref.shape == (BATCH, 5)
    assert np.abs(got - ref).max() <= TOL * np.abs(ref).max()


def test_plugin_widths_and_engine_args():
    cfg = {"name": "gat", "num_layers": 3, "hidden": 128, **TINY_GAT}
    dataset = {"feat_dim": 602, "num_classes": 41}
    # the hidden layers concatenate their heads; the last averages them
    assert gat().widths(cfg, dataset) == [602, 256, 256, 41]
    assert gat().engine_args(cfg) == {"model": "gat"}


def test_plugin_counts_at_the_cell_shapes():
    """gat-products' widths and its per-batch counts at fan-outs 15,10,5 and
    batch 1024 (destination frontiers 67,584 / 6,144 / 1,024 from a
    1,081,344-row input frontier), from shapes alone."""
    plug = gat()
    dims = plug.widths(GAT_PRODUCTS, PRODUCTS)
    assert dims == CELL_DIMS
    assert plug.forward_flops(dims, CELL_FANOUTS, 1024, GAT_PRODUCTS) == 160_154_190_848
    assert plug.attn_edges(dims, CELL_FANOUTS, 1024, GAT_PRODUCTS) == 4 * (
        67_584 * 16 + 6_144 * 11 + 1_024 * 6
    )
    weights = 4 * sum((k * hc + 2 * hc + m + k * m + m)
                      for k, hc, m in ((100, 512, 512), (512, 512, 512), (512, 188, 47)))
    outputs = 4 * 2 * (67_584 * 512 + 6_144 * 512 + 1_024 * 47)
    got = plug.forward_bytes(dims, CELL_FANOUTS, 1024, GAT_PRODUCTS, unique_rows=1000)
    assert got == 1000 * 100 * 4 + outputs + weights


def test_plugin_draws_every_tensor_and_refuses_odd_head_widths():
    params = gat().make_weights([24, 32, 32, 5], 3, TINY_GAT)
    assert [sorted(p) for p in params] == [["att_dst", "att_src", "b", "b_skip", "w", "w_skip"]] * 3
    assert params[0]["w"].shape == (24, 32) and params[0]["att_src"].shape == (2, 16)
    assert params[2]["w"].shape == (32, 10) and params[2]["b"].shape == (5,)
    for p in params:  # the biases are drawn, so that a dropped one shows
        assert np.abs(np.asarray(p["b"])).min() > 0 and np.abs(np.asarray(p["b_skip"])).min() > 0
    with pytest.raises(ValueError, match="not 3 heads wide"):
        gat().make_weights([24, 32, 32, 5], 3, {"heads": 3})


CELL_CFG = {"fanouts": CELL_FANOUTS, "model": GAT_PRODUCTS}


def roofline_ctx(forward_s, batches=4, unique_rows=2_000_000, attn_edges=None):
    m = gat()
    per_batch_edges = m.attn_edges(CELL_DIMS, CELL_FANOUTS, 1024, GAT_PRODUCTS)
    edges = batches * per_batch_edges if attn_edges is None else attn_edges
    return {
        "trace": {"busy_s": 1.0, "window_s": 1.0, "layer_s": {"forward": forward_s}},
        "batches": batches,
        "counters": {"attn_edges": edges, "unique_rows": unique_rows},
        "flops_per_batch": m.forward_flops(CELL_DIMS, CELL_FANOUTS, 1024, GAT_PRODUCTS),
        "peaks": {"flops_per_s": 200e12, "hbm_bytes_per_s": 800e9},
        "model": m, "config": CELL_CFG, "dims": CELL_DIMS, "batch": 1024,
    }


def test_forward_roofline_from_known_counts():
    r = reader()
    assert set(r.COUNTERS) == {"attn_edges", "unique_rows"}
    flops = 160_154_190_848  # models/gat.py at the cell's shapes
    ctx = roofline_ctx(forward_s=4 * 0.02)  # 20 ms a batch
    # compute-bound: 160 GFLOP over 200 TFLOP/s is 0.80 ms; the least bytes,
    # 500,000 unique rows x 400 B plus 0.28 GB of layer outputs, 0.35 ms
    moved = gat().forward_bytes(CELL_DIMS, CELL_FANOUTS, 1024, GAT_PRODUCTS, unique_rows=500_000)
    assert moved / 800e9 < flops / 200e12
    assert r.read(ctx) == pytest.approx(100 * (flops / 200e12) / 0.02)
    # bandwidth-bound once the rows outweigh the arithmetic
    ctx = roofline_ctx(forward_s=4 * 0.02, unique_rows=4 * 1_000_000_000)
    moved = gat().forward_bytes(CELL_DIMS, CELL_FANOUTS, 1024, GAT_PRODUCTS,
                                unique_rows=1_000_000_000)
    assert r.read(ctx) == pytest.approx(100 * (moved / 800e9) / 0.02)


@pytest.mark.parametrize("why,ctx", [
    ("no forward time", roofline_ctx(forward_s=0.0)),
    ("the program computed another edge count", roofline_ctx(0.08, attn_edges=1)),
    ("no attention counted", roofline_ctx(0.08, attn_edges=0)),
    ("no unique rows (dedup off)", roofline_ctx(0.08, unique_rows=0)),
])
def test_forward_roofline_reads_nothing_it_cannot_trust(why, ctx):
    assert reader().read(ctx) is None, why


def _with_device_ops(load_xplane):
    """The CPU backend's trace has no device plane: give each layer of
    ``layers.json`` one op inside the window, and keep the rest as traced."""
    def load(trace_dir):
        prof = load_xplane(trace_dir)
        w0, w1 = prof["window"]
        step = (w1 - w0) / 4
        prof["ops"] = [["/device:TPU:0", prog, "fusion", w0 + i * step, step / 2]
                       for i, prog in enumerate(("jit_sample_blocks", "jit_gather", "jit_forward"))]
        return prof
    return load


def test_the_cell_runs_traced_on_the_cpu(monkeypatch):
    """``gat-products.offline``'s own plug-in, reader and model settings (4
    heads x 128) over the tiny stand-in: a traced run on the CPU reads
    ``correct`` true, its forward matching the numpy GAT within the cell's
    own logit limit, and the roofline reader finds its counter and counts."""
    import trace_reduce

    cell = harness.resolve_cell("gat-products.offline")
    assert "gat_forward_roofline" in cell.readers and "attn_edges" in cell.counters
    tiny = json.loads((CHIP / "tests" / "data" / "tiny-sage.json").read_text())
    tiny.update(name="tiny-gat", model=cell.config["model"], check=cell.config["check"])
    cell.config = tiny
    monkeypatch.setattr(trace_reduce, "load_xplane", _with_device_ops(trace_reduce.load_xplane))
    peaks = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}
    res = harness.measure(cell, 2147483911, 0.5, True, peaks=peaks)
    assert res["correct"], res["checks"]
    assert res["metrics"]["gat_forward_roofline"]["value"] > 0
