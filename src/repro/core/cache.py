"""DualCache — the runtime bundle of DCI's two caches, versioned by epoch.

``DualCache`` owns the adjacency cache (the host ``AdjCache``, whose hit
lengths ride in ``DeviceGraph.cached_len``) and the feature cache (inside
``FeatureStore``) plus the allocation that produced them.  It is what the
inference engine actually runs against; policies (core/policies.py) are
factories for it.

Since the online refresh subsystem (runtime/cache_refresh.py) it is a
*versioned, mutable-by-delta* runtime object rather than a frozen value:
``refresh()`` swaps in a new allocation's worth of cache contents as an
incremental delta (only changed feature rows / adjacency segments move,
never the O(N)/O(E) host structures) and bumps ``epoch``.  Consumers read
``caches.dgraph`` / ``caches.store`` at stage-dispatch time, so every
stream picks up the new epoch at its next batch without coordination.
Refreshes never change sampled blocks, gathered rows, or logits — the
two-level sort order and the host feature table are frozen at build time —
only hit accounting and byte movement (tests/test_cache_refresh.py).
Without refresh enabled nothing mutates and the object behaves exactly
like the former frozen dataclass.
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np

from repro.core.allocation import CacheAllocation
from repro.graph.csc import (
    AdjCache,
    AdjRefreshStats,
    build_adj_cache,
    node_visit_totals,
    refresh_adj_cache,
    two_level_sort,
)
from repro.graph.datasets import SyntheticGraphDataset
from repro.graph.features import (
    FeatureRefreshStats,
    FeatureStore,
    build_feature_cache,
    plain_feature_store,
    refresh_feature_cache,
)
from repro.graph.sampling import DeviceGraph, device_graph

__all__ = ["DualCache", "CacheRefreshDelta"]


@dataclasses.dataclass(frozen=True)
class CacheRefreshDelta:
    """One epoch transition: what moved, and what it cost."""

    epoch: int  # the epoch this delta produced
    allocation: CacheAllocation
    feat: FeatureRefreshStats
    adj: AdjRefreshStats

    @property
    def changed(self) -> bool:
        return self.feat.changed or self.adj.changed


@dataclasses.dataclass
class DualCache:
    dgraph: DeviceGraph
    store: FeatureStore
    allocation: CacheAllocation | None
    epoch: int = 0
    # Frozen refresh context, captured by ``build``: the host CSC, the
    # two-level-sorted row order, and the host-side adjacency cache the
    # delta re-fill copies unchanged segments from.  ``None`` for cacheless
    # builds (``none()``), which have nothing to refresh.
    _graph: object | None = dataclasses.field(default=None, repr=False)
    _sorted_row: np.ndarray | None = dataclasses.field(default=None, repr=False)
    _adj_cache: AdjCache | None = dataclasses.field(default=None, repr=False)

    @property
    def adj_cached_elements(self) -> int:
        return int(np.asarray(self.dgraph.cached_len).sum())

    @property
    def feat_cached_rows(self) -> int:
        return self.store.num_cached

    @property
    def refreshable(self) -> bool:
        return self._graph is not None and self._sorted_row is not None

    @classmethod
    def build(
        cls,
        dataset: SyntheticGraphDataset,
        *,
        node_counts: np.ndarray,
        edge_counts: np.ndarray,
        allocation: CacheAllocation,
    ) -> "DualCache":
        """Fill both caches per §IV-B with the given capacity split."""
        sorted_row, node_totals = two_level_sort(dataset.graph, edge_counts)
        adj_cache = build_adj_cache(dataset.graph, sorted_row, node_totals, allocation.adj_bytes)
        dgraph = device_graph(dataset.graph, sorted_row_index=sorted_row, adj_cache=adj_cache)
        store = build_feature_cache(dataset.features, node_counts, allocation.feat_bytes)
        return cls(
            dgraph=dgraph,
            store=store,
            allocation=allocation,
            _graph=dataset.graph,
            _sorted_row=sorted_row,
            _adj_cache=adj_cache,
        )

    @classmethod
    def none(cls, dataset: SyntheticGraphDataset) -> "DualCache":
        """The DGL baseline: no caches at all."""
        return cls(
            dgraph=device_graph(dataset.graph),
            store=plain_feature_store(dataset.features),
            allocation=None,
        )

    # ------------------------------------------------------------- refresh
    def refresh(
        self,
        *,
        allocation: CacheAllocation,
        node_counts: np.ndarray,
        edge_counts: np.ndarray,
        injector=None,
    ) -> CacheRefreshDelta:
        """Swap both caches to a new allocation/ranking as a delta re-fill.

        No full ``build``: the two-level sort is never re-run, unchanged
        feature rows stay device-resident in their slots, unchanged
        adjacency segments are copied from the previous cache, and the
        O(E) device arrays are untouched.  In-flight batches that already
        dispatched against the previous epoch's arrays keep them alive
        (JAX arrays are immutable) and retire normally — the swap is a
        pointer flip on this object, visible to the next stage dispatch.

        The swap is TRANSACTIONAL: exactly five attributes mutate
        (``dgraph``, ``store``, ``allocation``, ``_adj_cache``, ``epoch``),
        and any failure mid-apply — including an injected ``refresh_fill``
        fault (core/faults.py), charged deliberately *between* the
        attribute writes to model a re-fill dying half-applied — restores
        all five from a snapshot before re-raising.  The old epoch's
        arrays are immutable and still referenced by the snapshot, so
        rollback is a pointer flip back: membership, epoch, and every
        byte of cache state are exactly the pre-refresh values
        (property-tested in tests/test_faults.py), and the caller keeps
        serving the stale epoch.
        """
        if not self.refreshable:
            raise ValueError("this DualCache was built without refresh context (none())")
        snapshot = (self.dgraph, self.store, self.allocation, self._adj_cache, self.epoch)
        try:
            node_totals = node_visit_totals(self._graph, edge_counts)
            new_adj, adj_stats = refresh_adj_cache(
                self._graph, self._sorted_row, self._adj_cache, node_totals, allocation.adj_bytes
            )
            new_store, feat_stats = refresh_feature_cache(
                self.store, node_counts, allocation.feat_bytes
            )
            # Only the hit lengths go to the device: the sampler reads every
            # neighbor from the resident sorted ``row_index``, and
            # ``cached_len`` keeps its int32[N] shape, so no epoch recompiles.
            self.dgraph = dataclasses.replace(
                self.dgraph, cached_len=jnp.asarray(new_adj.cached_len, jnp.int32)
            )
            self.store = new_store
            if injector is not None:
                # Mid-apply on purpose: dgraph/store already swapped, the
                # rest not — the worst-case partial state rollback must
                # cleanly undo.
                injector.check("refresh_fill")
            self.allocation = allocation
            self._adj_cache = new_adj
            self.epoch += 1
        except BaseException:
            (self.dgraph, self.store, self.allocation, self._adj_cache, self.epoch) = snapshot
            raise
        return CacheRefreshDelta(
            epoch=self.epoch, allocation=allocation, feat=feat_stats, adj=adj_stats
        )
