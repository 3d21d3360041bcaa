"""Vectorized neighbor sampling (paper §II-B) with adjacency-cache accounting.

The sampler is pure JAX and jittable: for every seed node it draws
``fanout`` uniform slots ``r ~ U[0, deg)`` and reads the neighbor at that
slot from ``row_index``.  With DCI's adjacency cache active, ``row_index``
is the two-level-sorted CSC and the hit test is the paper's single compare
``r < cached_len[v]`` (Fig. 6c).  On the paper's GPU a hit reads the
compact cache and a miss the host CSC behind UVA.  Here the full sorted
CSC is resident in HBM, and the cached prefix of a node is, slot for slot,
the same ids as the start of its run there, so a hit is pure accounting:
every draw reads ``row_index`` once.  A separate cache read pays only once
``row_index`` no longer lives on the device (ROADMAP R2).

Zero-degree nodes self-loop (counted as hits: no host access is needed).
Sampling is with replacement; see DESIGN.md §3 for why this does not
change the cache algorithms.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.graph.csc import AdjCache, CSCGraph

__all__ = [
    "DeviceGraph",
    "LayerSample",
    "BlockSample",
    "DedupFrontier",
    "dedup_frontier",
    "device_graph",
    "pow2_bucket",
    "sample_neighbors",
    "sample_blocks",
]


@dataclasses.dataclass(frozen=True)
class DeviceGraph:
    """Graph structure as device arrays, with the adjacency cache's lengths.

    Without a cache, ``row_index`` is the original CSC order and
    ``cached_len`` is all zeros.  With a cache, ``row_index`` MUST be the
    two-level-sorted copy: node ``v``'s cached prefix is then its first
    ``cached_len[v]`` slots there, so the whole sorted CSC being resident
    makes ``cached_len`` all the device needs of the cache.
    """

    col_ptr: jax.Array  # int32[N+1]
    row_index: jax.Array  # int32[E]  resident in HBM
    cached_len: jax.Array  # int32[N]

    @property
    def num_nodes(self) -> int:
        return self.col_ptr.shape[0] - 1

    def tree_flatten(self):
        return (self.col_ptr, self.row_index, self.cached_len), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        del aux
        return cls(*children)


jax.tree_util.register_pytree_node(
    DeviceGraph, DeviceGraph.tree_flatten, DeviceGraph.tree_unflatten
)


def device_graph(
    graph: CSCGraph,
    *,
    sorted_row_index: np.ndarray | None = None,
    adj_cache: AdjCache | None = None,
) -> DeviceGraph:
    """Stage a CSC graph, and the hit lengths of its adjacency cache, on device.

    The full (sorted) CSC goes to HBM whatever the cache holds, so of the
    host :class:`AdjCache` only ``cached_len`` is uploaded: its cached ids
    are a copy of what ``row_index`` already holds at the same slots.
    """
    if adj_cache is not None:
        if sorted_row_index is None:
            raise ValueError("adjacency cache requires the two-level-sorted row_index")
        cached_len = adj_cache.cached_len
    else:
        cached_len = np.zeros(graph.num_nodes, np.int32)
    row = graph.row_index if sorted_row_index is None else sorted_row_index
    return DeviceGraph(
        col_ptr=jnp.asarray(graph.col_ptr, jnp.int32),
        row_index=jnp.asarray(row, jnp.int32),
        cached_len=jnp.asarray(cached_len, jnp.int32),
    )


class LayerSample(dict):
    pass


@dataclasses.dataclass(frozen=True)
class DedupFrontier:
    """Sorted-unique view of one frontier, with jit-stable shapes.

    ``unique_ids[:num_unique]`` are the frontier's distinct node ids in
    ascending order; positions at and beyond ``num_unique`` repeat a pad
    id (a valid node, so padded gathers stay well-defined and are simply
    never referenced).  The pad id is the caller-supplied ``pad_id`` — a
    known-CACHED node, so pad slots resolve as cache hits and can never
    stage a duplicate miss row through
    ``FeatureStore.prefetch_misses`` — falling back to the frontier's
    largest id when no pad is given (or none is cached, signalled by
    ``pad_id < 0``).  ``inverse`` maps every frontier position to
    its slot in ``unique_ids`` — ``unique_ids[inverse]`` reconstructs the
    frontier bit-for-bit, which is the identity the whole dedup feature
    path rests on (gathering unique rows then expanding through
    ``inverse`` equals gathering every duplicate directly).  ``num_unique``
    stays a device scalar so the computation is one fused jit program; the
    runtime pulls it host-side once per batch to pick the pow2 gather
    bucket (:func:`pow2_bucket`).
    """

    unique_ids: jax.Array  # int32[S] sorted; tail padded with a cached (or max) id
    inverse: jax.Array  # int32[S] frontier position -> slot in unique_ids
    num_unique: jax.Array  # int32[] distinct-id count (duplication = S / this)

    def tree_flatten(self):
        return ((self.unique_ids, self.inverse, self.num_unique), None)

    @classmethod
    def tree_unflatten(cls, aux, children):
        del aux
        return cls(*children)


jax.tree_util.register_pytree_node(
    DedupFrontier, DedupFrontier.tree_flatten, DedupFrontier.tree_unflatten
)


@jax.jit
def dedup_frontier(frontier: jax.Array, pad_id: jax.Array | int | None = None) -> DedupFrontier:
    """Sort-and-unique one frontier on device with static output shapes.

    One argsort + one cumsum + two scatters — no host round trip, no
    data-dependent shapes: the unique set lives in a full-frontier-sized
    array and ``num_unique`` marks the live prefix.  Duplicate positions
    scatter the same value to the same slot, so the result is
    deterministic regardless of scatter order.

    ``pad_id`` fills the tail beyond the live prefix.  Pass a known-CACHED
    node id (``FeatureStore.pad_node_id``) so pad slots are feature-cache
    hits: a tail padded with an UNcached id (the old max-id behavior)
    would look like extra copies of a miss row to any consumer that scans
    the whole bucket — e.g. a warmup-path ``prefetch_misses`` call without
    ``num_live`` — staging duplicate miss rows.  ``pad_id`` is a traced
    operand (no recompile per value); ``None`` or a negative value falls
    back to the frontier's largest id, which keeps cache-less policies
    (every row a miss anyway) on the original behavior.
    """
    ids = frontier.astype(jnp.int32)
    order = jnp.argsort(ids)
    sorted_ids = ids[order]
    is_new = jnp.concatenate(
        [jnp.ones((1,), bool), sorted_ids[1:] != sorted_ids[:-1]]
    )
    rank = (jnp.cumsum(is_new) - 1).astype(jnp.int32)
    fill = sorted_ids[-1]
    if pad_id is not None:
        pad = jnp.asarray(pad_id, jnp.int32)
        fill = jnp.where(pad >= 0, pad, fill)
    unique = jnp.full(ids.shape, fill, jnp.int32).at[rank].set(sorted_ids)
    inverse = jnp.zeros(ids.shape, jnp.int32).at[order].set(rank)
    return DedupFrontier(unique_ids=unique, inverse=inverse, num_unique=rank[-1] + 1)


def pow2_bucket(n: int, cap: int | None = None) -> int:
    """Smallest power of two >= ``max(n, 1)``, optionally capped at ``cap``.

    The one pow2 padding discipline shared by every dynamic-count device
    structure: the deduped frontier's gather bucket, the miss-path
    prefetch pack (:meth:`repro.graph.features.FeatureStore.prefetch_misses`),
    and the cache-refresh delta scatters — so each compiles O(log S)
    programs across batches with varying counts, not one per count.
    """
    bucket = 1 << max(int(n) - 1, 0).bit_length()
    return bucket if cap is None else min(bucket, int(cap))


def sample_neighbors(
    key: jax.Array, g: DeviceGraph, seeds: jax.Array, fanout: int,
    *, full_neighborhood: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Sample ``fanout`` in-neighbors per seed (with replacement).

    Returns ``(neighbors[S, fanout], hits[S, fanout], edge_slots[S, fanout])``
    where ``edge_slots`` are global positions ``col_ptr[v] + r`` used for
    visit counting during pre-sampling.

    Each neighbor is read once, ``row_index[edge_slots]``; ``hits`` is the
    adjacency cache's test ``r < cached_len[v]`` (Fig. 6c).  With the
    sorted CSC resident in HBM a hit and a miss read the same id from the
    same array, so the cache counts hits without a second gather.

    ``full_neighborhood=True`` (static) replaces the random draw with the
    deterministic enumeration ``r = arange(fanout) % deg``: when a seed's
    degree equals ``fanout`` every neighbor is taken exactly once, making
    the sampled aggregate EXACTLY the full-neighborhood sum — the bridge
    the layer-wise mode's equivalence tests rest on (higher degrees
    truncate to the first ``fanout`` CSC slots, lower ones wrap).  The key
    is ignored in this mode but kept in the signature so call sites and
    RNG bookkeeping are mode-invariant.
    """
    seeds = seeds.astype(jnp.int32)
    start = g.col_ptr[seeds]  # [S]
    deg = g.col_ptr[seeds + 1] - start  # [S]
    safe_deg = jnp.maximum(deg, 1)
    if full_neighborhood:
        r = jnp.arange(fanout, dtype=jnp.int32)[None, :] % safe_deg[:, None]
    else:
        r = jax.random.randint(key, (seeds.shape[0], fanout), 0, safe_deg[:, None])
    edge_slots = start[:, None] + r
    nbr = g.row_index[edge_slots]
    hit = r < g.cached_len[seeds][:, None]

    isolated = (deg == 0)[:, None]
    nbr = jnp.where(isolated, seeds[:, None], nbr)
    hit = jnp.where(isolated, True, hit)
    return nbr, hit, edge_slots


@dataclasses.dataclass(frozen=True)
class BlockSample:
    """Layered mini-batch (GraphSAGE-style blocks).

    ``frontiers[0]`` are the batch seeds; ``frontiers[l+1]`` has layout
    ``[frontiers[l] | neighbors_l.reshape(-1)]`` so the model can split a
    feature matrix over frontier ``l+1`` into (self, neighbors) parts by a
    static reshape.  ``input_nodes`` is the deepest frontier — these are the
    rows the feature loader must fetch.
    """

    frontiers: tuple[jax.Array, ...]
    neighbor_hits: tuple[jax.Array, ...]  # per layer, [S_l, fanout_l]
    edge_slots: tuple[jax.Array, ...]
    fanouts: tuple[int, ...]
    # Sorted-unique view of the deepest frontier (``sample_blocks``'s
    # dedup=True mode); None on the default path.  Only the input frontier
    # is deduped: it is the one the feature loader gathers, and every
    # shallower frontier is a prefix of it, so one unique set covers the
    # whole block.
    dedup: DedupFrontier | None = None

    @property
    def input_nodes(self) -> jax.Array:
        return self.frontiers[-1]

    def adj_hit_stats(self) -> tuple[jax.Array, jax.Array]:
        hits = sum(jnp.sum(h) for h in self.neighbor_hits)
        total = sum(h.size for h in self.neighbor_hits)
        return hits, jnp.asarray(total)


@functools.partial(jax.jit, static_argnames=("fanouts", "dedup", "full_neighborhood"))
def sample_blocks(
    key: jax.Array,
    g: DeviceGraph,
    seeds: jax.Array,
    fanouts: tuple[int, ...],
    dedup: bool = False,
    dedup_pad_id: jax.Array | int | None = None,
    full_neighborhood: bool = False,
) -> BlockSample:
    """Multi-layer fan-out sampling producing GraphSAGE blocks.

    ``fanouts`` is listed outermost-layer-first (the paper's '15,10,5'
    convention); layer 0 of the expansion uses the *last* element, matching
    DGL's semantics where fan-outs map to model layers from input to output.

    ``dedup=True`` additionally sorts-and-uniques the deepest frontier on
    device (:func:`dedup_frontier`) inside the same jit program, so the
    feature path can gather each distinct row once and expand through the
    inverse map; sampling itself — frontiers, hits, edge slots, RNG
    consumption — is bit-identical with the flag on or off.
    ``dedup_pad_id`` is the (traced) known-cached pad id forwarded to
    :func:`dedup_frontier` — a plain int or scalar, never static, so a
    refresh-epoch pad change does not recompile the sampler.
    ``full_neighborhood=True`` (static) enumerates neighbor slots
    deterministically per layer instead of drawing them (see
    :func:`sample_neighbors`); the per-layer key splits still happen so
    frontier layouts and shapes are mode-invariant.
    """
    frontiers = [seeds.astype(jnp.int32)]
    hits_all = []
    slots_all = []
    frontier = frontiers[0]
    for i, fanout in enumerate(reversed(fanouts)):
        key, sub = jax.random.split(key)
        nbr, hit, slots = sample_neighbors(
            sub, g, frontier, fanout, full_neighborhood=full_neighborhood
        )
        frontier = jnp.concatenate([frontier, nbr.reshape(-1)])
        frontiers.append(frontier)
        hits_all.append(hit)
        slots_all.append(slots)
    return BlockSample(
        frontiers=tuple(frontiers),
        neighbor_hits=tuple(hits_all),
        edge_slots=tuple(slots_all),
        fanouts=tuple(fanouts),
        dedup=dedup_frontier(frontier, dedup_pad_id) if dedup else None,
    )


jax.tree_util.register_pytree_node(
    BlockSample,
    lambda b: ((b.frontiers, b.neighbor_hits, b.edge_slots, b.dedup), b.fanouts),
    lambda aux, ch: BlockSample(
        frontiers=ch[0], neighbor_hits=ch[1], edge_slots=ch[2], dedup=ch[3], fanouts=aux
    ),
)


def count_visits(
    num_nodes: int, num_edges: int, blocks: Sequence[BlockSample]
) -> tuple[np.ndarray, np.ndarray]:
    """Pre-sampling visit counters (paper §IV-B).

    Node counts = how often each node's *feature* row is loaded (membership
    in input frontiers); edge counts = how often each adjacency element is
    touched by sampling.  Both are one scatter-add per block.
    """
    node_counts = jnp.zeros(num_nodes, jnp.int32)
    edge_counts = jnp.zeros(num_edges, jnp.int32)
    for b in blocks:
        node_counts = node_counts.at[b.input_nodes].add(1)
        for slots in b.edge_slots:
            edge_counts = edge_counts.at[slots.reshape(-1)].add(1)
    return np.asarray(node_counts), np.asarray(edge_counts)
