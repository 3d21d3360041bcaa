"""End-to-end behaviour tests for the DCI system (paper pipeline + LM side)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import miss_bytes

from repro.data.tokens import TokenStream, batches
from repro.launch.steps import make_train_step
from repro.models.lm.model import init_params
from repro.optim.adamw import init_adamw
from repro.runtime.gnn_engine import GNNInferenceEngine
from repro.runtime.lm_cache import build_serving_caches


def test_dci_end_to_end_moves_fewer_miss_bytes_than_dgl(small_dataset):
    reports = {}
    for policy in ("dgl", "dci"):
        eng = GNNInferenceEngine(small_dataset, fanouts=(4, 3, 2), batch_size=128)
        eng.prepare(policy, total_cache_bytes=1_000_000)
        reports[policy] = eng.run(max_batches=4)
    dgl, dci = reports["dgl"], reports["dci"]
    # Same dataset, budget and batches: the same lookups, of which DGL
    # misses every one and DCI's caches take a share.
    assert (dci.feat_lookups, dci.adj_lookups) == (dgl.feat_lookups, dgl.adj_lookups)
    assert dgl.feat_row_bytes == dci.feat_row_bytes == small_dataset.feature_nbytes_per_row()
    assert 0 < miss_bytes(dci) < miss_bytes(dgl)
    assert dci.adj_hit_rate > 0 and dci.feat_hit_rate > 0
    assert dgl.feat_hit_rate == 0
    # stage decomposition is complete and sane
    assert dci.total_seconds > 0
    assert dci.feat_hits <= dci.feat_lookups
    assert dci.adj_hits <= dci.adj_lookups


def test_dci_allocation_reacts_to_workload(small_dataset):
    """Fat fan-outs make sampling relatively more expensive -> Eq.1 gives
    the adjacency cache a non-trivial share."""
    eng = GNNInferenceEngine(small_dataset, fanouts=(15, 10, 5), batch_size=128)
    pipe = eng.prepare("dci", total_cache_bytes=1_000_000)
    a = pipe.caches.allocation
    assert 0 < a.sample_fraction < 1
    assert a.adj_bytes > 0 and a.feat_bytes > 0


def test_lm_training_loss_decreases():
    import dataclasses

    from repro.configs import get_smoke

    cfg = dataclasses.replace(get_smoke("yi-6b"), vocab=512)
    params = init_params(jax.random.PRNGKey(0), cfg)
    opt = init_adamw(params)
    step = jax.jit(make_train_step(cfg, base_lr=3e-3))
    stream = TokenStream(vocab=cfg.vocab, seed=0)
    losses = []
    for b in batches(stream, batch=4, seq=32, steps=30):
        params, opt, loss = step(params, opt, {k: jnp.asarray(v) for k, v in b.items()})
        losses.append(float(loss))
    assert all(np.isfinite(losses))
    assert np.mean(losses[-5:]) < np.mean(losses[:5])


def test_serving_dual_cache_hits_on_zipfian_requests():
    from repro.configs import get_smoke

    cfg = get_smoke("phi3.5-moe-42b-a6.6b")
    params = init_params(jax.random.PRNGKey(0), cfg)
    stream = TokenStream(vocab=cfg.vocab, seed=1)
    rng = np.random.default_rng(0)
    sample = stream.sample(rng, 8, 32)
    caches = build_serving_caches(cfg, params, sample, total_cache_bytes=100_000)
    a = caches.allocation
    assert a.adj_bytes + a.feat_bytes == 100_000
    live = stream.sample(rng, 4, 32)
    assert 0.0 <= caches.embed_hit_rate(live) <= 1.0
    # zipfian reuse: the hot-row cache must catch a meaningful share
    assert caches.embed_hit_rate(live) > 0.3


@pytest.mark.parametrize("zipf_a", [1.05, 1.3])
def test_serving_embed_hit_rate_monotone_in_budget(zipf_a):
    """The embedding hit rate on live traffic never falls as the budget
    grows, at either request skew."""
    from repro.configs import get_smoke

    cfg = get_smoke("phi3.5-moe-42b-a6.6b")
    params = init_params(jax.random.PRNGKey(0), cfg)
    stream = TokenStream(vocab=cfg.vocab, seed=1, zipf_a=zipf_a)
    rng = np.random.default_rng(0)
    sample = stream.sample(rng, 8, 48)
    live = stream.sample(rng, 8, 48)
    hits = [
        build_serving_caches(cfg, params, sample, total_cache_bytes=b).embed_hit_rate(live)
        for b in (25_000, 100_000, 400_000)
    ]
    assert hits == sorted(hits)
    assert hits[0] < hits[-1]


def test_gnn_inference_deterministic_given_pipeline(small_dataset):
    """Eq.1's split depends on measured wall time (by design), so determinism
    holds *given a prepared pipeline*: same caches + seed => same hits."""
    eng = GNNInferenceEngine(small_dataset, fanouts=(3, 2), batch_size=64, seed=7)
    eng.prepare("dci", total_cache_bytes=500_000)
    r1 = eng.run(max_batches=2)
    r2 = eng.run(max_batches=2)
    assert (r1.adj_hits, r1.feat_hits) == (r2.adj_hits, r2.feat_hits)


def test_full_budget_gives_full_hit_rates(small_dataset):
    """With a budget covering the whole dataset, both caches hit ~100%
    (paper: 'performance of both strategies is identical' past that point)."""
    ds = small_dataset
    budget = ds.features.nbytes + ds.graph.num_edges * 4 + 1024
    eng = GNNInferenceEngine(ds, fanouts=(4, 3, 2), batch_size=128)
    eng.prepare("dci", total_cache_bytes=budget)
    rep = eng.run(max_batches=4)
    assert rep.feat_hit_rate == 1.0
    assert rep.adj_hit_rate == 1.0


def test_sampler_is_uniform_over_neighbors(small_dataset):
    """Chi-square-style check: slots are drawn uniformly over each node's
    neighbor list (the property Eq.1's workload statistics rely on)."""
    import jax

    from repro.graph.sampling import device_graph, sample_neighbors

    ds = small_dataset
    deg = np.diff(ds.graph.col_ptr)
    v = int(np.argmax((deg >= 5) & (deg <= 20)))  # a mid-degree node
    d = int(deg[v])
    g = device_graph(ds.graph)
    seeds = jnp.full((256,), v, jnp.int32)
    counts = np.zeros(d, np.int64)
    for i in range(20):
        _, _, slots = sample_neighbors(jax.random.PRNGKey(i), g, seeds, 4)
        local = np.asarray(slots).reshape(-1) - int(ds.graph.col_ptr[v])
        np.add.at(counts, local, 1)
    n = counts.sum()
    expect = n / d
    chi2 = float(((counts - expect) ** 2 / expect).sum())
    # dof = d-1 <= 19; chi2 far below a catastrophic threshold
    assert chi2 < 4 * d, (chi2, d, counts)
