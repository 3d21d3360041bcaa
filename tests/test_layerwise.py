"""Layer-wise full-graph inference (runtime/layerwise.py).

The load-bearing guarantees:

  * numerical equivalence — an L-layer layer-wise pass equals (a) a dense
    numpy reference on arbitrary small graphs and (b) a FULL-NEIGHBORHOOD
    sampled forward on regular graphs (degree == fanout, where the
    deterministic enumeration takes every neighbor exactly once), within
    fp tolerance (summation order differs: segment_sum vs reshape-reduce);
  * knob invariance — prefetch / kernel route / pipeline depth / chunk
    size never change the outputs, only where bytes move;
  * exact access counts — the layer-wise pattern is ``1 + out_degree``
    per node per layer, read straight off the CSC;
  * engine dispatch — ``EngineConfig(mode="layerwise")`` routes
    ``GNNInferenceEngine.run`` to the chunked executor and the report
    echoes the resolved config.
"""

import dataclasses

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core.allocation import allocate_layerwise_capacity
from repro.core.config import EngineConfig
from repro.graph.csc import CSCGraph
from repro.graph.datasets import DatasetSpec, SyntheticGraphDataset
from repro.graph.sampling import sample_blocks
from repro.models import gnn as gnn_models
from repro.runtime.gnn_engine import GNNInferenceEngine
from repro.runtime.layerwise import (
    LayerwiseReport,
    layerwise_access_counts,
    plan_chunks,
)

TOL = dict(rtol=2e-4, atol=2e-5)


def _dataset_from_graph(graph: CSCGraph, feat_dim: int = 8, num_classes: int = 4, seed: int = 0):
    n = graph.num_nodes
    rng = np.random.default_rng(seed)
    idx = rng.permutation(n)
    spec = DatasetSpec("custom", n, graph.num_edges / max(n, 1), feat_dim, num_classes, (0.5, 0.2, 0.3))
    return SyntheticGraphDataset(
        spec=spec,
        graph=graph,
        features=rng.standard_normal((n, feat_dim)).astype(np.float32),
        labels=rng.integers(0, num_classes, n).astype(np.int32),
        train_idx=idx[: n // 2],
        val_idx=idx[n // 2 : (7 * n) // 10],
        test_idx=idx[(7 * n) // 10 :],
    )


def _regular_graph(n: int, d: int) -> CSCGraph:
    """Every node's in-neighbors are the next ``d`` nodes (mod n) — degree
    exactly ``d`` everywhere, so fanout ``d`` full-neighborhood sampling
    enumerates each in-edge exactly once."""
    col_ptr = np.arange(n + 1, dtype=np.int64) * d
    row_index = np.empty(n * d, np.int32)
    for v in range(n):
        row_index[v * d : (v + 1) * d] = [(v + k + 1) % n for k in range(d)]
    return CSCGraph(col_ptr=col_ptr, row_index=row_index)


def _ragged_graph() -> CSCGraph:
    """Small arbitrary graph with a zero-degree node and a multi-edge."""
    nbrs = [[1, 2], [0, 3, 4, 4], [], [2], [0, 1, 2, 3, 5], [4], [0]]
    col_ptr = np.cumsum([0] + [len(x) for x in nbrs]).astype(np.int64)
    row_index = np.concatenate([np.asarray(x, np.int32) for x in nbrs if x])
    return CSCGraph(col_ptr=col_ptr, row_index=row_index)


def _dense_gat_layer(g, h, p) -> np.ndarray:
    """One GAT layer, a per-node loop over {self} ∪ in-neighbors (multi-edges
    counted once each): per-head softmax of LeakyReLU_0.2 scores, heads
    concatenated (or averaged where ``b`` is one head wide), plus the skip."""
    p = {k: np.asarray(v, np.float64) for k, v in p.items()}
    heads, ch = p["att_src"].shape
    z = (h @ p["w"]).reshape(-1, heads, ch)
    out = np.zeros((g.num_nodes, heads, ch))
    for v in range(g.num_nodes):
        e0, e1 = int(g.col_ptr[v]), int(g.col_ptr[v + 1])
        srcs = np.concatenate([[v], np.asarray(g.row_index[e0:e1])]).astype(np.int64)
        e = (z[srcs] * p["att_src"]).sum(-1) + (z[v] * p["att_dst"]).sum(-1)
        e = np.where(e > 0, e, 0.2 * e)
        a = np.exp(e - e.max(axis=0))
        a /= a.sum(axis=0)
        out[v] = (a[..., None] * z[srcs]).sum(axis=0)
    merged = out.reshape(g.num_nodes, -1) if p["b"].shape[0] == heads * ch else out.mean(axis=1)
    return merged + p["b"] + h @ p["w_skip"] + p["b_skip"]


def _dense_reference(dataset, params, model: str) -> np.ndarray:
    """Straight numpy layer chain over full in-neighborhoods (agg = 0 for
    zero-degree nodes, matching forward_layer's segment_sum semantics; a
    GAT node without in-edges attends to itself alone)."""
    g = dataset.graph
    n = g.num_nodes
    deg = np.diff(g.col_ptr).astype(np.float64)
    h = dataset.features.astype(np.float64)
    for li, p in enumerate(params):
        if model == "gat":
            out = _dense_gat_layer(g, h, p)
            h = np.where(out > 0, out, np.expm1(out)) if li < len(params) - 1 else out
            continue
        agg = np.zeros_like(h)
        for v in range(n):
            e0, e1 = int(g.col_ptr[v]), int(g.col_ptr[v + 1])
            if e1 > e0:
                agg[v] = h[np.asarray(g.row_index[e0:e1])].sum(axis=0)
        if model == "graphsage":
            out = h @ np.asarray(p["w_self"], np.float64)
            out += agg @ np.asarray(p["w_nbr"], np.float64)
            out += np.asarray(p["b"], np.float64)
        else:
            out = ((h + agg) / (deg[:, None] + 1.0)) @ np.asarray(p["w_self"], np.float64)
            out += np.asarray(p["b"], np.float64)
        h = np.maximum(out, 0.0) if li < len(params) - 1 else out
    return h


def _params(dataset, model, n_layers, seed=0, hidden=6):
    """Seeded parameters; GAT at 2 heads (hidden layers concatenate, the
    last averages), with drawn biases so that a dropped one shows."""
    import jax

    params = gnn_models.init_params(
        jax.random.PRNGKey(seed),
        model,
        dataset.spec.feat_dim,
        dataset.spec.num_classes,
        hidden=hidden,
        n_layers=n_layers,
        heads=2 if model == "gat" else None,
    )
    if model == "gat":
        rng = np.random.default_rng(seed)
        for p in params:
            for k in ("b", "b_skip"):
                p[k] = jnp.asarray(rng.standard_normal(p[k].shape).astype(np.float32) * 0.1)
    return params


def _layerwise_engine(dataset, *, model="graphsage", fanouts=(3, 3), cache_bytes=4096, seed=0):
    # Layer count must match the fanout depth: the sampled forward runs
    # len(fanouts) layers, the layer-wise executor len(params).
    eng = GNNInferenceEngine(
        dataset,
        model=model,
        fanouts=fanouts,
        batch_size=8,
        seed=seed,
        params=_params(dataset, model, len(fanouts), seed=seed),
    )
    eng.prepare("dci", total_cache_bytes=cache_bytes, n_presample=2)
    return eng


# ------------------------------------------------------------ access pattern


def test_access_counts_exact():
    g = _ragged_graph()
    counts = layerwise_access_counts(g)
    # 1 (chunk member) + out-degree (appearances as an in-edge source).
    out_deg = np.bincount(np.asarray(g.row_index), minlength=g.num_nodes)
    np.testing.assert_array_equal(counts, 1 + out_deg)
    assert counts.min() >= 1


@pytest.mark.parametrize("chunk_size", [3, 4, 7, 16])
def test_plan_chunks_geometry(chunk_size):
    g = _ragged_graph()
    plan = plan_chunks(g, chunk_size)
    assert sum(c.cnt for c in plan.chunks) == g.num_nodes
    assert sum(c.n_edges for c in plan.chunks) == g.num_edges
    for c in plan.chunks:
        bucket = c.base_ids.shape[0] - chunk_size
        assert bucket >= c.n_edges and bucket & (bucket - 1) == 0  # pow2
        # Live self block is the node range; live neighbor block is the
        # CSC slice; seg ids map each live edge into [0, cnt).
        np.testing.assert_array_equal(
            c.base_ids[: c.cnt], np.arange(c.lo, c.lo + c.cnt, dtype=np.int32)
        )
        np.testing.assert_array_equal(
            c.base_ids[chunk_size : chunk_size + c.n_edges],
            np.asarray(g.row_index[g.col_ptr[c.lo] : g.col_ptr[c.lo] + c.n_edges]),
        )
        seg = np.asarray(c.seg_ids)
        assert seg[: c.n_edges].max(initial=0) < c.cnt
        assert (seg[c.n_edges :] == chunk_size).all()  # pads → dropped segment
        assert int(np.asarray(c.live).sum()) == c.cnt + c.n_edges


# ------------------------------------------------------------- equivalence


@pytest.mark.parametrize("model", ["graphsage", "gcn", "gat"])
def test_matches_dense_reference(model):
    ds = _dataset_from_graph(_ragged_graph())
    eng = _layerwise_engine(ds, model=model, fanouts=(2, 2), cache_bytes=2048)
    rep = eng.run(config=EngineConfig(mode="layerwise", chunk_size=3))
    assert isinstance(rep, LayerwiseReport)
    ref = _dense_reference(ds, eng.params, model)
    np.testing.assert_allclose(rep.outputs, ref, **TOL)


@pytest.mark.parametrize("model", ["graphsage", "gcn", "gat"])
def test_matches_full_neighborhood_sampled_forward(model):
    """On a d-regular graph with fanout == d, the deterministic
    full-neighborhood enumeration takes every in-edge exactly once, so the
    sampled L-layer forward IS the full-graph computation — the layer-wise
    outputs must match it within summation-order tolerance."""
    d = 3
    ds = _dataset_from_graph(_regular_graph(24, d))
    fanouts = (d, d)
    eng = _layerwise_engine(ds, model=model, fanouts=fanouts, cache_bytes=4096)
    rep = eng.run(config=EngineConfig(mode="layerwise", chunk_size=8))

    dgraph = eng.pipeline.caches.dgraph
    store = eng.pipeline.caches.store
    import jax

    for lo in range(0, ds.num_nodes, 8):
        seeds = jnp.arange(lo, min(lo + 8, ds.num_nodes), dtype=jnp.int32)
        block = sample_blocks(
            jax.random.PRNGKey(0), dgraph, seeds, fanouts, full_neighborhood=True
        )
        feats, _ = store.gather(block.input_nodes)
        logits = gnn_models.forward(eng.params, feats, model=model, fanouts=fanouts)
        np.testing.assert_allclose(rep.outputs[np.asarray(seeds)], np.asarray(logits), **TOL)


def test_knob_and_chunk_invariance():
    """Prefetch staging, the kernel route, a deeper window, and a
    different chunk size never change the scores — only byte movement."""
    ds = _dataset_from_graph(_regular_graph(30, 4), feat_dim=8)
    eng = _layerwise_engine(ds, fanouts=(4, 4), cache_bytes=4096)
    base = eng.run(config=EngineConfig(mode="layerwise", chunk_size=8, pipeline_depth=1))
    for knobs in (
        dict(prefetch=True),
        dict(use_kernel=True),
        dict(prefetch=True, use_kernel=True),
        dict(pipeline_depth=3),
    ):
        rep = eng.run(config=EngineConfig(mode="layerwise", chunk_size=8, **knobs))
        np.testing.assert_array_equal(rep.outputs, base.outputs)
        assert (rep.feat_hits, rep.feat_lookups) == (base.feat_hits, base.feat_lookups)
        assert (rep.embed_hits, rep.embed_lookups) == (base.embed_hits, base.embed_lookups)
    other = eng.run(config=EngineConfig(mode="layerwise", chunk_size=13))
    np.testing.assert_allclose(other.outputs, base.outputs, **TOL)
    # Lookup totals are chunking-invariant: N + E per layer, exactly.
    assert other.feat_lookups == base.feat_lookups
    assert other.embed_lookups == base.embed_lookups


def test_cacheless_budget_still_runs():
    ds = _dataset_from_graph(_ragged_graph())
    eng = GNNInferenceEngine(
        ds, fanouts=(2, 2), batch_size=8, params=_params(ds, "graphsage", 2)
    )
    eng.prepare("dgl")  # no cache budget at all
    rep = eng.run(config=EngineConfig(mode="layerwise", chunk_size=4))
    assert rep.outputs.shape == (ds.num_nodes, ds.spec.num_classes)
    assert rep.allocation is None
    ref = _dense_reference(ds, eng.params, "graphsage")
    np.testing.assert_allclose(rep.outputs, ref, **TOL)


# ---------------------------------------------------------- engine surface


def test_engine_dispatch_and_report():
    ds = _dataset_from_graph(_regular_graph(24, 3))
    eng = _layerwise_engine(ds, fanouts=(3, 3))
    rep = eng.run(config=EngineConfig(mode="layerwise", chunk_size=8, pipeline_depth=2))
    assert isinstance(rep, LayerwiseReport)
    assert eng.last_outputs[0] is rep.outputs
    s = rep.summary()
    assert s["mode"] == "layerwise"
    assert s["chunks"] == rep.num_chunks == -(-ds.num_nodes // 8)
    assert s["pipeline_depth"] == 2
    # The echoed config is RESOLVED: every knob concrete.
    cfg = s["config"]
    assert cfg["mode"] == "layerwise" and cfg["chunk_size"] == 8
    assert all(cfg[k] is not None for k in ("prefetch", "use_kernel", "gather_buffers"))
    # Lookups are the exact access pattern: N + E per layer.
    n, e = ds.num_nodes, ds.graph.num_edges
    assert rep.feat_lookups == n + e
    assert rep.embed_lookups == (rep.num_layers - 1) * (n + e)
    assert rep.modeled_transfer_seconds() > 0


def test_layerwise_allocation_mapping():
    # Feature gathers measured 3x slower than embedding gathers → Eq. 1
    # gives the feature cache 75% of the budget.
    alloc = allocate_layerwise_capacity([0.03], [0.01], 1000)
    assert alloc.feat_bytes == 750 and alloc.embed_bytes == 250
    assert alloc.feat_fraction == pytest.approx(0.75)
    # Saturation spill: a feature share beyond its need flows to embeds.
    alloc = allocate_layerwise_capacity([0.03], [0.01], 1000, feat_need_bytes=500)
    assert alloc.feat_bytes == 500 and alloc.embed_bytes == 500
    assert dataclasses.asdict(alloc)  # frozen dataclass stays introspectable
