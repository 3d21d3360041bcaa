"""Sampler invariants: validity, cache-hit equivalence, visit counting."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core.allocation import CacheAllocation
from repro.core.cache import DualCache
from repro.graph.csc import CSCGraph, build_adj_cache, two_level_sort
from repro.graph.sampling import (
    count_visits,
    dedup_frontier,
    device_graph,
    sample_blocks,
    sample_neighbors,
)


def neighbors_of(ds, v):
    lo, hi = ds.graph.col_ptr[v], ds.graph.col_ptr[v + 1]
    return set(ds.graph.row_index[lo:hi].tolist()) or {v}


def test_sampled_neighbors_are_real(small_dataset):
    ds = small_dataset
    g = device_graph(ds.graph)
    seeds = jnp.asarray(ds.test_idx[:32])
    nbr, hit, _ = sample_neighbors(jax.random.PRNGKey(0), g, seeds, 5)
    nbr = np.asarray(nbr)
    for i, v in enumerate(np.asarray(seeds)):
        allowed = neighbors_of(ds, int(v))
        assert set(nbr[i].tolist()) <= allowed


def test_cached_sampler_returns_real_neighbors(small_dataset):
    """With the adjacency cache active, samples must still be true neighbors."""
    ds = small_dataset
    seeds = jnp.asarray(ds.test_idx[:64])
    # visit counts from a real pre-sampling pass over the same seeds, so the
    # cache holds the edges these seeds actually touch
    plain = device_graph(ds.graph)
    _, _, slots = sample_neighbors(jax.random.PRNGKey(7), plain, seeds, 4)
    counts = np.zeros(ds.graph.num_edges, np.int64)
    np.add.at(counts, np.asarray(slots).reshape(-1), 1)
    sorted_row, totals = two_level_sort(ds.graph, counts)
    cache = build_adj_cache(ds.graph, sorted_row, totals, capacity_bytes=4 * 2000)
    g = device_graph(ds.graph, sorted_row_index=sorted_row, adj_cache=cache)
    nbr, hit, _ = sample_neighbors(jax.random.PRNGKey(1), g, seeds, 4)
    nbr, hit = np.asarray(nbr), np.asarray(hit)
    assert hit.any()  # cache actually used
    for i, v in enumerate(np.asarray(seeds)):
        assert set(nbr[i].tolist()) <= neighbors_of(ds, int(v))


def test_zero_degree_self_loop():
    import numpy as np

    from repro.graph.csc import CSCGraph

    g = CSCGraph(col_ptr=np.array([0, 0, 1]), row_index=np.array([0], np.int32))
    dg = device_graph(g)
    nbr, hit, _ = sample_neighbors(jax.random.PRNGKey(0), dg, jnp.array([0], jnp.int32), 3)
    assert (np.asarray(nbr) == 0).all()
    assert np.asarray(hit).all()  # self-loops need no host access


def test_block_frontier_sizes(small_dataset):
    g = device_graph(small_dataset.graph)
    seeds = jnp.asarray(small_dataset.test_idx[:16])
    b = sample_blocks(jax.random.PRNGKey(0), g, seeds, (4, 3, 2))
    sizes = [16]
    for f in (2, 3, 4):  # expansion uses reversed fanouts
        sizes.append(sizes[-1] * (1 + f))
    assert [fr.shape[0] for fr in b.frontiers] == sizes


def test_count_visits_totals(small_dataset):
    g = device_graph(small_dataset.graph)
    seeds = jnp.asarray(small_dataset.test_idx[:16])
    b = sample_blocks(jax.random.PRNGKey(0), g, seeds, (3, 2))
    node_counts, edge_counts = count_visits(
        small_dataset.num_nodes, small_dataset.graph.num_edges, [b]
    )
    assert node_counts.sum() == b.input_nodes.shape[0]
    # every edge count came from a sampled slot of a non-isolated seed
    assert edge_counts.sum() <= sum(s.size for s in b.edge_slots)


@settings(max_examples=10, deadline=None)
@given(fanout=st.integers(1, 6), n_seeds=st.integers(1, 32), seed=st.integers(0, 99))
def test_hit_rate_in_unit_interval(small_dataset, fanout, n_seeds, seed):
    ds = small_dataset
    counts = np.random.default_rng(seed).integers(0, 5, ds.graph.num_edges).astype(np.int64)
    sorted_row, totals = two_level_sort(ds.graph, counts)
    cache = build_adj_cache(ds.graph, sorted_row, totals, capacity_bytes=4 * 200)
    g = device_graph(ds.graph, sorted_row_index=sorted_row, adj_cache=cache)
    seeds = jnp.asarray(ds.test_idx[:n_seeds])
    _, hit, _ = sample_neighbors(jax.random.PRNGKey(seed), g, seeds, fanout)
    rate = float(jnp.mean(hit))
    assert 0.0 <= rate <= 1.0


def test_dual_cache_build(small_dataset):
    ds = small_dataset
    rng = np.random.default_rng(0)
    alloc = CacheAllocation(
        total_bytes=100_000, adj_bytes=50_000, feat_bytes=50_000, sample_fraction=0.5
    )
    dc = DualCache.build(
        ds,
        node_counts=rng.integers(0, 9, ds.num_nodes),
        edge_counts=rng.integers(0, 9, ds.graph.num_edges),
        allocation=alloc,
    )
    assert dc.adj_cached_elements * 4 <= alloc.adj_bytes
    assert dc.feat_cached_rows * ds.feature_nbytes_per_row() <= alloc.feat_bytes


# ------------------------------------------------ two-gather reference sampler


def reference_graph(graph, sorted_row_index=None, adj_cache=None) -> dict:
    """The arrays the two-gather sampler read: the CSC plus a device copy
    of the adjacency cache, built from a host ``AdjCache`` with a 1-element
    dummy ``cache_row_index`` where nothing is cached."""
    n = graph.num_nodes
    if adj_cache is not None:
        row = sorted_row_index
        cache_ptr = adj_cache.cache_ptr.astype(np.int32)
        cache_row = adj_cache.cache_row_index
        cached_len = adj_cache.cached_len
    else:
        row = graph.row_index if sorted_row_index is None else sorted_row_index
        cache_ptr = np.zeros(n + 1, np.int32)
        cache_row = np.empty(0, np.int32)
        cached_len = np.zeros(n, np.int32)
    if cache_row.shape[0] == 0:
        cache_row = np.zeros(1, np.int32)
    arrays = dict(
        col_ptr=graph.col_ptr, row_index=row, cache_ptr=cache_ptr,
        cache_row_index=cache_row, cached_len=cached_len,
    )
    return {k: jnp.asarray(v, jnp.int32) for k, v in arrays.items()}


def reference_sample_neighbors(key, g, seeds, fanout, *, full_neighborhood=False):
    """Reads every draw from both the CSC and the cache, and keeps the
    cache's id on a hit."""
    seeds = seeds.astype(jnp.int32)
    start = g["col_ptr"][seeds]
    deg = g["col_ptr"][seeds + 1] - start
    safe_deg = jnp.maximum(deg, 1)
    if full_neighborhood:
        r = jnp.arange(fanout, dtype=jnp.int32)[None, :] % safe_deg[:, None]
    else:
        r = jax.random.randint(key, (seeds.shape[0], fanout), 0, safe_deg[:, None])
    edge_slots = start[:, None] + r
    host_nbr = g["row_index"][edge_slots]
    clen = g["cached_len"][seeds]
    hit = r < clen[:, None]
    cache_idx = g["cache_ptr"][seeds][:, None] + jnp.minimum(
        r, jnp.maximum(clen - 1, 0)[:, None]
    )
    cache_row = g["cache_row_index"]
    cache_nbr = cache_row[jnp.minimum(cache_idx, cache_row.shape[0] - 1)]
    nbr = jnp.where(hit, cache_nbr, host_nbr)
    isolated = (deg == 0)[:, None]
    nbr = jnp.where(isolated, seeds[:, None], nbr)
    hit = jnp.where(isolated, True, hit)
    return nbr, hit, edge_slots


@functools.partial(jax.jit, static_argnames=("fanouts", "dedup", "full_neighborhood"))
def reference_sample_blocks(key, g, seeds, fanouts, dedup=False, full_neighborhood=False):
    frontier = seeds.astype(jnp.int32)
    frontiers, hits, slots = [frontier], [], []
    for fanout in reversed(fanouts):
        key, sub = jax.random.split(key)
        nbr, hit, slot = reference_sample_neighbors(
            sub, g, frontier, fanout, full_neighborhood=full_neighborhood
        )
        frontier = jnp.concatenate([frontier, nbr.reshape(-1)])
        frontiers.append(frontier)
        hits.append(hit)
        slots.append(slot)
    return frontiers, hits, slots, dedup_frontier(frontier) if dedup else None


def assert_blocks_match_reference(key, dgraph, ref_graph, seeds, fanouts, *, dedup,
                                  full_neighborhood=False):
    block = sample_blocks(
        key, dgraph, seeds, fanouts, dedup=dedup, full_neighborhood=full_neighborhood
    )
    frontiers, hits, slots, ref_dedup = reference_sample_blocks(
        key, ref_graph, seeds, fanouts, dedup=dedup, full_neighborhood=full_neighborhood
    )
    for got, want in zip(
        (block.frontiers, block.neighbor_hits, block.edge_slots), (frontiers, hits, slots),
        strict=True,
    ):
        assert len(got) == len(want)
        for a, b in zip(got, want, strict=True):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    if dedup:
        for a, b in zip(
            jax.tree.leaves(block.dedup), jax.tree.leaves(ref_dedup), strict=True
        ):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    else:
        assert block.dedup is None and ref_dedup is None
    return block


def _skewed_graph(seed: int = 3, n: int = 300) -> CSCGraph:
    """Degrees 0..40 with zero-degree nodes in the middle and at the end
    (a zero-degree node's slot then points one past the last edge)."""
    rng = np.random.default_rng(seed)
    deg = rng.integers(0, 41, n)
    deg[rng.choice(n - 1, 20, replace=False)] = 0
    deg[-1] = 0
    col_ptr = np.concatenate([[0], np.cumsum(deg)]).astype(np.int64)
    return CSCGraph(col_ptr=col_ptr, row_index=rng.integers(0, n, int(col_ptr[-1])).astype(np.int32))


@pytest.mark.parametrize("full_neighborhood", [False, True])
@pytest.mark.parametrize("dedup", [False, True])
@pytest.mark.parametrize("cache", ["partial", "empty", "none"])
def test_blocks_bit_identical_to_two_gather_reference(cache, dedup, full_neighborhood):
    """Sampling from the resident CSC alone returns the frontiers, hit
    flags, edge slots and unique set the two-gather sampler returned."""
    graph = _skewed_graph()
    deg = graph.degrees()
    counts = np.random.default_rng(4).integers(0, 9, graph.num_edges).astype(np.int64)
    sorted_row, totals = two_level_sort(graph, counts)
    if cache == "none":
        dgraph, ref = device_graph(graph), reference_graph(graph)
        seeds = np.arange(graph.num_nodes)
    else:
        # Fill whole nodes up to a third of the fill order, then cut the
        # next node (degree >= 3) after one element: Alg. 1's partial node.
        order = np.argsort(-totals, kind="stable")
        k = graph.num_nodes // 3 + int(np.argmax(deg[order][graph.num_nodes // 3:] >= 3))
        capacity = 4 * (int(deg[order[:k]].sum()) + 1) if cache == "partial" else 0
        adj = build_adj_cache(graph, sorted_row, totals, capacity)
        partial = np.nonzero((adj.cached_len > 0) & (adj.cached_len < deg))[0]
        assert partial.tolist() == ([order[k]] if cache == "partial" else [])
        dgraph = device_graph(graph, sorted_row_index=sorted_row, adj_cache=adj)
        ref = reference_graph(graph, sorted_row, adj)
        seeds = np.concatenate([[order[k]] * 4, np.arange(graph.num_nodes)])
    assert (deg[seeds] == 0).sum() >= 20
    block = assert_blocks_match_reference(
        jax.random.PRNGKey(11), dgraph, ref, jnp.asarray(seeds, jnp.int32), (4, 3),
        dedup=dedup, full_neighborhood=full_neighborhood,
    )
    hits = np.asarray(block.neighbor_hits[0])
    if cache == "partial":
        assert hits[deg[seeds] > 0].any() and not hits[deg[seeds] > 0].all()
    else:
        assert hits.sum() == 3 * (deg[seeds] == 0).sum()  # self-loops only
