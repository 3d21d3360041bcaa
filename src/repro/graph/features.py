"""Node-feature storage: host ("UVA") table + DCI hot-feature cache.

The paper locates cached rows "through a hash table" inside the GPU; on
TPU a dense ``position_map: int32[N]`` (−1 = miss) is the idiomatic
equivalent — one vectorized gather instead of pointer chasing (DESIGN.md
§3).  ``gather`` reads hits from the compact hot table and misses from the
full host table, returning the hit mask so the engine can account for
bytes moved over the slow path.
"""

from __future__ import annotations

import collections
import concurrent.futures
import dataclasses
import itertools
import typing

import jax
import jax.numpy as jnp
import numpy as np

from repro.graph.sampling import pow2_bucket

__all__ = [
    "FeatureStore",
    "FeatureRefreshStats",
    "PrefetchedMisses",
    "build_embedding_cache",
    "build_feature_cache",
    "refresh_feature_cache",
]

# One shared worker for the host-side miss-row pack: the numpy row copy is
# the heavy part of prefetch staging, and a single worker keeps the packs
# ordered (packs are consumed in submission order by the batch that
# requested them) while the submitting thread builds the index arrays and
# issues their device transfers concurrently.
PACK_LANE = "dci-miss-pack"  # the worker's thread-name prefix and trace lane
_PACK_POOL = concurrent.futures.ThreadPoolExecutor(max_workers=1, thread_name_prefix=PACK_LANE)


class PrefetchedMisses(typing.NamedTuple):
    """Missed host rows staged onto the device ahead of their gather.

    ``rows`` is the ``device_put`` buffer: the full ``[S, F]`` row set when
    every row missed (``idx is None``), else a ``[P, F]`` power-of-two
    padded pack of just the miss rows.  ``idx`` holds each packed row's
    position in the batch (pad entries point one past the end and are
    dropped by the consuming scatter); ``pack_pos`` is the inverse map —
    each batch row's slot in the pack (0 for hit rows, whose miss source
    is never read) — so the kernel route can address the pack directly
    instead of rebuilding a dense miss source.  ``num_miss`` is the
    unpadded miss count — the staging accounting, so callers need not
    re-derive the miss mask.  ``pack_buffer_allocs`` counts the host pack
    buffers this call allocated (0 once the store's pack ring is warm for
    the bucket)."""

    rows: jax.Array
    idx: jax.Array | None
    pack_pos: jax.Array | None
    num_miss: int
    pack_buffer_allocs: int = 0

    @property
    def staged_rows(self) -> int:
        """Rows ``device_put`` moved: ``num_miss`` plus the pack's pow2
        padding (the whole row set when every row missed)."""
        return int(self.rows.shape[0])


def _span_args(tracer, args: dict | None, **extra) -> dict | None:
    """A staging span's args: the caller's, plus ``extra`` (built only when
    tracing)."""
    return {**(args or {}), **extra} if tracer.enabled else None


def _aliases(rows: jax.Array, buf: np.ndarray) -> bool:
    """Whether ``device_put`` handed back ``buf``'s own memory.

    The CPU backend puts an aligned numpy buffer zero-copy, whatever
    ``may_alias`` says (JAX 0.9); a device with its own memory always
    copies, so only a CPU array's pointer is read (on an accelerator the
    read could wait on the transfer)."""
    dev = next(iter(rows.devices()))
    return dev.platform == "cpu" and rows.unsafe_buffer_pointer() == buf.ctypes.data


class _PackSlot:
    """One reused host buffer of the miss-row pack.

    ``live`` rows lead the buffer; every row past them is zero, as a fresh
    ``np.zeros`` pack would have it.  ``last`` is the array last
    ``device_put`` from the buffer while its host→device copy may still be
    reading the buffer: :meth:`pack` waits for it first."""

    __slots__ = ("buf", "live", "last")

    def __init__(self, bucket: int, feat_dim: int, dtype):
        self.buf = np.empty((bucket, feat_dim), dtype)
        self.buf.fill(0)  # touches every page now, not in the first packs
        self.live = 0
        self.last = None

    def landed(self) -> bool:
        """Whether no copy out of the buffer can still be in flight."""
        return self.last is None or self.last.is_deleted() or self.last.is_ready()

    def pack(self, table: np.ndarray, ids: np.ndarray) -> None:
        """Rows ``table[ids]`` first, zeros after: bit-for-bit the fresh
        pack.  Only the rows the last pack held past ``len(ids)`` are
        re-zeroed.  ``mode="wrap"`` indexes exactly as ``table[ids]`` does
        for every id the position-map scan accepted (an out-of-range id
        raised there), and unlike ``"raise"`` writes ``out`` unbuffered."""
        if not self.landed():
            self.last.block_until_ready()
        self.last = None
        m = ids.shape[0]
        if m < self.live:
            self.buf[m : self.live] = 0
        self.live = m
        np.take(table, ids, axis=0, out=self.buf[:m], mode="wrap")

    def device_put(self, device) -> jax.Array:
        """Put the buffer on ``device``.  Where the backend would alias the
        buffer (see :func:`_aliases`), put a copy instead: the next pack
        into this slot must not rewrite a live array."""
        rows = jax.device_put(self.buf, device)
        if _aliases(rows, self.buf):
            rows = jax.device_put(self.buf.copy(), device)
        self.last = rows
        return rows


class _PackRing:
    """The store's reused miss-row pack buffers: :attr:`SLOTS` per pow2
    bucket, all allocated on the bucket's first pack (in warm-up), then
    taken in turn.  ``allocs`` counts the buffers allocated."""

    SLOTS = 3  # a pipeline of depth 2 holds two packs; the third is slack

    def __init__(self, feat_dim: int, dtype):
        self.feat_dim, self.dtype = feat_dim, dtype
        self.slots: dict[int, collections.deque] = {}
        self.allocs = 0

    def take(self, bucket: int) -> tuple[_PackSlot, int]:
        """The next slot for ``bucket``, and how many buffers this call
        allocated."""
        ring, new = self.slots.get(bucket), 0
        if ring is None:
            ring = self.slots[bucket] = collections.deque(
                _PackSlot(bucket, self.feat_dim, self.dtype) for _ in range(self.SLOTS)
            )
            new = self.SLOTS
            self.allocs += new
        ring.rotate(-1)
        return ring[-1], new

    def pack(self, slot: _PackSlot, table: np.ndarray, ids: np.ndarray) -> None:
        """:meth:`_PackSlot.pack`, after letting go of every array whose
        copy has landed, so the ring keeps no device memory alive."""
        for other in itertools.chain.from_iterable(tuple(self.slots.values())):
            if other.landed():
                other.last = None
        slot.pack(table, ids)


@dataclasses.dataclass(frozen=True)
class FeatureStore:
    host_table: jax.Array  # f32[N, F] — the UVA/HBM-resident full table
    hot_table: jax.Array  # f32[H, F] — device cache (H >= 1; row 0 unused if empty)
    position_map: jax.Array  # int32[N] — slot in hot_table or -1

    @property
    def num_nodes(self) -> int:
        return self.host_table.shape[0]

    @property
    def feat_dim(self) -> int:
        return self.host_table.shape[1]

    @property
    def num_cached(self) -> int:
        return int((self.position_map >= 0).sum())

    def host_np(self) -> np.ndarray:
        """Host-memory mirror of the full feature table (cached lazily).

        The paper's miss path reads host/UVA memory; this is the array the
        prefetch stage copies missed rows *from* with ``jax.device_put``.
        Same float bits as ``host_table``, so a prefetched row is
        bit-identical to a direct device-side miss gather.  Row-major: a
        TPU hands the table back column-major, where each row is strided
        across the whole table and ``np.take`` would copy all of it per
        call."""
        cached = getattr(self, "_host_np", None)
        if cached is None:
            cached = np.ascontiguousarray(np.asarray(self.host_table))
            object.__setattr__(self, "_host_np", cached)
        return cached

    def pack_ring(self) -> _PackRing:
        """The reused host buffers of the miss-row pack (created lazily;
        carried to a refreshed store, which shares the host table)."""
        ring = getattr(self, "_pack_ring", None)
        if ring is None:
            ring = _PackRing(self.feat_dim, self.host_np().dtype)
            object.__setattr__(self, "_pack_ring", ring)
        return ring

    def kernel_tables(self) -> tuple[jax.Array, jax.Array]:
        """``(hot, host)`` in the gather kernel's lane layout (built lazily,
        once per store; the host copy is shared with derived stores).

        The kernel reads tables padded to whole 128-lane groups
        (:func:`~repro.kernels.cached_gather.kernel.lane_layout`); building
        them here keeps that pad off every gather call."""
        from repro.kernels.cached_gather.kernel import lane_layout

        host = getattr(self, "_host_lanes", None)
        if host is None:
            host = lane_layout(self.host_table)
            object.__setattr__(self, "_host_lanes", host)
        hot = getattr(self, "_hot_lanes", None)
        if hot is None:
            hot = lane_layout(self.hot_table)
            object.__setattr__(self, "_hot_lanes", hot)
        return hot, host

    def position_np(self) -> np.ndarray:
        """Host-memory mirror of ``position_map`` (cached lazily) — lets
        the prefetch stage find the missed rows without a device round
        trip."""
        cached = getattr(self, "_position_np", None)
        if cached is None:
            cached = np.asarray(self.position_map)
            object.__setattr__(self, "_position_np", cached)
        return cached

    def pad_node_id(self) -> int:
        """A known-CACHED node id for padding device index buffers, or −1
        when nothing is cached.

        The deduped frontier's pow2 bucket tail is filled with this id
        (``dedup_frontier(pad_id=...)``): pad slots then resolve as cache
        hits, so a bucket-wide scan — e.g. a warmup-path
        :meth:`prefetch_misses` without ``num_live`` — can never mistake
        padding for duplicate miss rows.  Computed lazily from the host
        position-map mirror (largest cached id; any cached id would do)."""
        cached = getattr(self, "_pad_node_id", None)
        if cached is None:
            hot = np.nonzero(self.position_np() >= 0)[0]
            cached = int(hot[-1]) if hot.size else -1
            object.__setattr__(self, "_pad_node_id", cached)
        return cached

    def prefetch_misses(
        self,
        nodes: np.ndarray,
        *,
        pack_in_thread: bool = True,
        num_live: int | None = None,
        device=None,
        injector=None,
        tracer=None,
        lane: str = "main",
        args: dict | None = None,
    ) -> PrefetchedMisses:
        """Stage the missed host rows for a batch onto the device.

        ``jax.device_put`` issues the host→device copy of exactly the
        rows the gather would otherwise pull across the slow link; under
        async dispatch it overlaps whatever the device is running (the
        previous batch's forward, in the pipelined executor).  The miss
        count varies batch to batch, so the pack is padded to a
        power-of-two bucket — the consuming scatter then compiles
        O(log S) programs instead of one per distinct count.

        ``num_live`` marks a live prefix: positions at and beyond it are
        padding (the deduped frontier's pow2 bucket tail) whose gathered
        values are never read, so their misses are not staged — the pack
        holds exactly the DISTINCT missed rows.  The consuming gather
        still covers all of ``nodes``; pad miss rows read pack slot 0,
        which only ever lands in unread pad output rows.

        The pack is written into a reused host buffer, not a fresh one:
        the store's :meth:`pack_ring` keeps three buffers per bucket,
        allocated (and their pages touched) on the bucket's first pack, so
        in warm-up, and taken in turn on the calling thread.  Before a
        buffer is rewritten, the array last put from it is waited on
        (``block_until_ready``: its host→device copy may still read the
        buffer; with a pipeline of depth 2 it finished batches ago).  Each
        buffer remembers how many rows it held last; when fewer are live
        now, only the rows between are re-zeroed, so the pad rows are zero
        and the pack is bit-for-bit a fresh ``np.zeros`` one.  A backend
        that would put the buffer zero-copy (the CPU's, for an aligned
        buffer) is given a copy.  ``pack_buffer_allocs`` on the result
        counts the buffers the call allocated: 0 once the bucket is warm.
        The all-miss path keeps its own fresh row set.

        ``pack_in_thread`` (default on) runs the heavy part of the pack —
        the row copy and its ``device_put`` — on a worker thread while the
        calling thread builds the ``idx``/``pack_pos`` index arrays and
        issues THEIR device transfers; the call joins before returning, so
        the result (and everything downstream) is bit-identical either
        way.

        ``device`` commits the staged buffers to a specific device — the
        sharded path stages each shard's misses onto that shard's device
        so the consuming per-shard gather never mixes committed devices.
        ``None`` (default) keeps the single-device placement.

        ``injector`` (core/faults.py, optional) charges one ``prefetch``
        fault-site call before any staging work — the check precedes every
        state mutation and the staging itself is pure, so a faulted call
        is safely retryable.

        ``tracer`` (core/trace.py, optional) records the staging steps as
        spans carrying ``args`` (the caller's batch link): on ``lane``,
        ``prefetch:scan`` (the position-map lookup), ``prefetch:index``
        (the index arrays and their transfer) and ``prefetch:join`` (the
        wait on the worker); on the worker's :data:`PACK_LANE` (on
        ``lane`` when the calling thread packs: ``pack_in_thread`` off, or
        every row missed), ``prefetch:pack`` (the wait on the buffer's last
        transfer, the pad re-zeroing and the row copy; args add
        ``pack_buffer_allocs``) and ``prefetch:put`` (its ``device_put``,
        with the rows it moves).  The spans only read the clock: they add
        no device sync."""
        from repro.core.trace import resolve_tracer  # repro.core imports this module

        tracer = resolve_tracer(tracer)
        if injector is not None:
            injector.check("prefetch")
        nodes = np.asarray(nodes)
        with tracer.span("prefetch:scan", lane=lane, args=args):
            live = nodes if num_live is None else nodes[:num_live]
            miss = np.nonzero(self.position_np()[live] < 0)[0].astype(np.int32)
        if miss.size == nodes.size:
            # Every row missed (e.g. no cache): the staged buffer IS the
            # whole row set — no pack, no pad, nothing to overlap.
            with tracer.span("prefetch:pack", lane=lane, args=args):
                rows = self.host_np()[nodes]
            put_args = _span_args(tracer, args, rows=nodes.size)
            with tracer.span("prefetch:put", lane=lane, args=put_args):
                rows = jax.device_put(rows, device)
            return PrefetchedMisses(rows=rows, idx=None, pack_pos=None, num_miss=int(miss.size))
        bucket = pow2_bucket(miss.size, nodes.size)
        pack_lane = PACK_LANE if pack_in_thread else lane
        ring = self.pack_ring()
        slot, allocs = ring.take(bucket)

        def pack_rows():
            pack_args = _span_args(tracer, args, pack_buffer_allocs=allocs)
            with tracer.span("prefetch:pack", lane=pack_lane, args=pack_args):
                ring.pack(slot, self.host_np(), nodes[miss])
            put_args = _span_args(tracer, args, rows=bucket)
            with tracer.span("prefetch:put", lane=pack_lane, args=put_args):
                return slot.device_put(device)

        rows_future = _PACK_POOL.submit(pack_rows) if pack_in_thread else None
        with tracer.span("prefetch:index", lane=lane, args=args):
            idx = np.full(bucket, nodes.size, np.int32)  # pad → one past the end (dropped)
            idx[: miss.size] = miss
            pack_pos = np.zeros(nodes.size, np.int32)  # hit rows point at slot 0 (never read)
            pack_pos[miss] = np.arange(miss.size, dtype=np.int32)
            if device is not None:
                idx, pack_pos = jax.device_put(idx, device), jax.device_put(pack_pos, device)
            else:
                idx, pack_pos = jnp.asarray(idx), jnp.asarray(pack_pos)
        if rows_future is None:
            rows = pack_rows()
        else:
            with tracer.span("prefetch:join", lane=lane, args=args):
                rows = rows_future.result()
        return PrefetchedMisses(
            rows=rows, idx=idx, pack_pos=pack_pos, num_miss=int(miss.size),
            pack_buffer_allocs=allocs,
        )

    def gather(
        self,
        indices: jax.Array,
        *,
        use_kernel: bool = False,
        gather_buffers: int = 2,
        prefetched: PrefetchedMisses | None = None,
        row_block: int | None = None,
        injector=None,
    ) -> tuple[jax.Array, jax.Array]:
        """Two-source gather. Returns ``(features[S, F], hit[S])``.

        ``use_kernel=True`` routes through the Pallas ``cached_gather``
        kernel (compiled on TPU, interpret mode on the CPU) over
        :meth:`kernel_tables`, with ``gather_buffers`` row copies in flight.

        ``prefetched`` (from :meth:`prefetch_misses`) replaces the host
        table as the miss source: miss rows come from the already-staged
        pack — scattered over the hot-table gather — instead of
        re-crossing the slow link inside this stage.  The hit mask — and
        therefore all hit/miss accounting — is computed from
        ``position_map`` exactly as in the non-prefetched path, and the
        output is bit-identical (the staged rows are copies of the same
        host rows).

        ``row_block`` (with ``use_kernel``) selects the row-block kernel
        variant: sorted-run index sets (deduped frontiers) collapse to one
        DMA descriptor per ``row_block`` consecutive source rows instead
        of one per row.  Correct for any index order — broken runs fall
        back to per-row copies inside the kernel — so the output stays
        bit-identical to every other route.

        ``injector`` (core/faults.py, optional) charges a ``host_fetch``
        fault-site call (the miss path's host read) and, on the kernel
        route, a ``kernel_gather`` call — both before any device dispatch,
        so a faulted gather is safely retryable.
        """
        if injector is not None:
            injector.check("host_fetch")
            if use_kernel:
                injector.check("kernel_gather")
        indices = indices.astype(jnp.int32)
        pos = self.position_map[indices]
        hit = pos >= 0
        s = indices.shape[0]
        if use_kernel:
            from repro.kernels.cached_gather.kernel import (
                cached_gather,
                cached_gather_blocks,
                lane_layout,
            )

            hot_src, host_src = self.kernel_tables()
            if prefetched is None:
                host_idx = indices
            elif prefetched.idx is None:  # all-miss: the pack is row-aligned
                host_src = lane_layout(prefetched.rows)
                host_idx = jnp.arange(s, dtype=jnp.int32)
            else:
                # Address the staged pack directly through its inverse map
                # — no dense [S, F] miss-source rebuild on the gather
                # stage.  Hit rows point at pack slot 0, which the DMA
                # kernel never reads (the hit branch copies the hot row).
                host_src, host_idx = lane_layout(prefetched.rows), prefetched.pack_pos
            kw = dict(feat_dim=self.feat_dim, gather_buffers=gather_buffers)
            if row_block is not None and row_block > 1:
                out = cached_gather_blocks(
                    hot_src, host_src, host_idx, pos, row_block=row_block, **kw
                )
            else:
                out = cached_gather(hot_src, host_src, host_idx, pos, **kw)
            return out, hit
        safe_pos = jnp.maximum(pos, 0)
        cached = self.hot_table[jnp.minimum(safe_pos, self.hot_table.shape[0] - 1)]
        if prefetched is None:
            return jnp.where(hit[:, None], cached, self.host_table[indices]), hit
        if prefetched.idx is None:  # all rows missed: straight select
            return jnp.where(hit[:, None], cached, prefetched.rows), hit
        # Misses overwrite their rows of the hot gather — S·F + M·F work
        # instead of the two full gathers + select of the table path.
        return cached.at[prefetched.idx].set(prefetched.rows, mode="drop"), hit

    def gather_cache_only(self, indices: jax.Array) -> tuple[jax.Array, jax.Array]:
        """Degraded-mode gather: hit rows from the device cache, miss rows
        ZERO-FILLED — never touches the host table.

        The fallback the serving layer uses when the miss path is down
        (core/faults.py ``host_fetch``): hit rows are bit-identical to
        :meth:`gather`'s, misses are explicitly wrong (zeros) and the
        request is marked ``degraded`` — availability over fidelity.  The
        hit mask is the usual ``position_map`` lookup, so hit accounting
        stays comparable with the healthy path."""
        indices = indices.astype(jnp.int32)
        pos = self.position_map[indices]
        hit = pos >= 0
        safe_pos = jnp.maximum(pos, 0)
        cached = self.hot_table[jnp.minimum(safe_pos, self.hot_table.shape[0] - 1)]
        return jnp.where(hit[:, None], cached, jnp.zeros_like(cached)), hit


jax.tree_util.register_pytree_node(
    FeatureStore,
    lambda s: ((s.host_table, s.hot_table, s.position_map), None),
    lambda aux, ch: FeatureStore(*ch),
)


def select_hot_rows(node_counts: np.ndarray, budget_rows: int) -> np.ndarray:
    """DCI's sort-free hot-row selection (paper §IV-B).

    Select nodes with ``visits > mean`` directly (no global argsort); if
    capacity remains, top up with below-mean *visited* nodes, then with
    anything else.  O(N) passes; only the (small, under power-law
    workloads) above-mean subset is ever sorted.  Shared by the build-time
    fill and the serve-time delta refresh, so both rank rows identically.
    """
    n = node_counts.shape[0]
    budget_rows = min(max(int(budget_rows), 0), n)
    counts = node_counts.astype(np.float64)
    mean = counts.mean() if n else 0.0
    hot = np.nonzero(counts > mean)[0]
    if hot.shape[0] > budget_rows:
        # More above-mean nodes than capacity: keep the hottest among them.
        hot = hot[np.argsort(-counts[hot], kind="stable")[:budget_rows]]
    elif hot.shape[0] < budget_rows:
        rest = np.nonzero(counts <= mean)[0]
        visited = rest[counts[rest] > 0]
        cold = rest[counts[rest] == 0]
        top_up = np.concatenate([visited, cold])[: budget_rows - hot.shape[0]]
        hot = np.concatenate([hot, top_up])
    return hot


def build_feature_cache(
    features: np.ndarray,
    node_counts: np.ndarray,
    capacity_bytes: int,
) -> FeatureStore:
    """DCI's sort-free feature-cache fill (paper §IV-B)."""
    n, f = features.shape
    row_bytes = f * features.dtype.itemsize
    budget_rows = min(max(int(capacity_bytes) // row_bytes, 0), n)
    # Slots are assigned in ascending NODE-ID order (selection — which
    # rows get cached — is unchanged): consecutive hot node ids land in
    # consecutive hot-table slots, so a sorted deduped frontier's hit
    # positions form the contiguous runs the row-block gather kernel
    # collapses to one DMA each.  Outputs and hit accounting are invariant
    # to slot order — gathers always go through ``position_map``.
    hot = np.sort(select_hot_rows(node_counts, budget_rows))

    position_map = np.full(n, -1, np.int32)
    position_map[hot] = np.arange(hot.shape[0], dtype=np.int32)
    hot_table = features[hot] if hot.shape[0] else np.zeros((1, f), features.dtype)
    return FeatureStore(
        host_table=jnp.asarray(features),
        hot_table=jnp.asarray(hot_table),
        position_map=jnp.asarray(position_map),
    )


@dataclasses.dataclass(frozen=True)
class FeatureRefreshStats:
    """What a delta re-fill actually moved (the bounded-pause accounting)."""

    rows_kept: int  # hot rows that stayed in their slots — zero bytes moved
    rows_inserted: int  # new hot rows scattered into freed slots
    rows_evicted: int  # old hot rows whose slots were reused / invalidated
    physical_rows: int  # device hot-table rows after the refresh
    budget_rows: int  # logical capacity the new allocation pays for

    @property
    def changed(self) -> bool:
        return bool(self.rows_inserted or self.rows_evicted)


def refresh_feature_cache(
    store: FeatureStore,
    node_counts: np.ndarray,
    capacity_bytes: int,
) -> tuple[FeatureStore, FeatureRefreshStats]:
    """Incremental re-fill: move only the rows whose hotness changed.

    Re-runs the sort-free selection on the UPDATED ``node_counts`` (merged
    presample + runtime telemetry), then applies the difference against
    the live store as a delta:

      * rows in both the old and new hot set KEEP their slots — no copy,
        no position_map write, no recompile;
      * evicted rows get ``position_map[v] = -1`` (their slots are freed;
        stale table rows are never read again);
      * inserted rows are packed once host-side and applied as ONE device
        scatter into the freed slots.

    The device hot table only grows (and only when the new budget exceeds
    its physical rows); shrinking budgets reuse the existing array with a
    smaller logical occupancy, so repeated refreshes at a stable split
    compile nothing new.  ``host_table`` is shared with the old store, so
    gathered feature rows stay bit-identical across epochs — a refresh
    changes hit accounting and byte movement, never outputs.
    """
    features = store.host_np()
    n, f = features.shape
    row_bytes = f * features.dtype.itemsize
    budget_rows = min(max(int(capacity_bytes) // row_bytes, 0), n)

    old_pos = store.position_np()
    new_hot = select_hot_rows(node_counts, budget_rows)
    in_new = np.zeros(n, bool)
    in_new[new_hot] = True
    old_nodes = np.nonzero(old_pos >= 0)[0]
    kept_mask = in_new[old_nodes]
    kept_nodes = old_nodes[kept_mask]
    evicted_nodes = old_nodes[~kept_mask]
    in_old = np.zeros(n, bool)
    in_old[old_nodes] = True
    # Ascending insert order mirrors the build-time id-ordered slot
    # assignment: freed slots are filled lowest-id-first, preserving what
    # run contiguity the surviving layout still allows (kept rows pin
    # their slots, so contiguity degrades gracefully across epochs rather
    # than resetting).
    inserted_nodes = np.sort(new_hot[~in_old[new_hot]])

    physical = store.hot_table.shape[0]
    needed = kept_nodes.shape[0] + inserted_nodes.shape[0]
    hot_table = store.hot_table
    if needed > physical:
        # Grow by appending zero rows; kept rows stay device-resident —
        # the host never re-uploads them.  Growth doubles (capped at the
        # node count) so a sequence of refreshes compiles O(log N) gather
        # programs, not one per epoch; shrinking budgets reuse the array
        # with lower logical occupancy and compile nothing.
        grow_to = min(max(needed, 2 * physical), max(n, needed))
        hot_table = jnp.concatenate(
            [hot_table, jnp.zeros((grow_to - physical, f), hot_table.dtype)]
        )
        physical = grow_to

    # Free slots = every physical slot not held by a kept row; inserts fill
    # them in ascending order (deterministic given the same inputs).
    occupied = np.zeros(physical, bool)
    occupied[old_pos[kept_nodes]] = True
    free_slots = np.nonzero(~occupied)[0][: inserted_nodes.shape[0]].astype(np.int32)

    new_pos_np = old_pos.copy()
    new_pos_np[evicted_nodes] = -1
    new_pos_np[inserted_nodes] = free_slots

    def pow2_pad(idx: np.ndarray, fill: int) -> jnp.ndarray:
        # The delta scatters compile per index-array shape; padding the
        # delta to a power-of-two bucket (pad entries point out of range
        # and are dropped) keeps repeated refreshes to O(log N) compiled
        # programs instead of one per distinct delta size.
        out = np.full(pow2_bucket(idx.size), fill, np.int32)
        out[: idx.size] = idx
        return jnp.asarray(out)

    position_map = store.position_map
    if evicted_nodes.size:
        position_map = position_map.at[pow2_pad(evicted_nodes, n)].set(-1, mode="drop")
    if inserted_nodes.size:
        ins = pow2_pad(inserted_nodes, n)
        slots = pow2_pad(free_slots, physical)
        position_map = position_map.at[ins].set(slots, mode="drop")
        rows = np.zeros((slots.shape[0], f), features.dtype)
        rows[: inserted_nodes.size] = features[inserted_nodes]
        hot_table = hot_table.at[slots].set(jnp.asarray(rows), mode="drop")
    new_store = FeatureStore(
        host_table=store.host_table, hot_table=hot_table, position_map=position_map
    )
    # Carry the host mirrors forward: host rows are unchanged, and the new
    # position map is already known host-side — no device round trip.
    object.__setattr__(new_store, "_host_np", features)
    object.__setattr__(new_store, "_position_np", new_pos_np)
    if hasattr(store, "_host_lanes"):
        object.__setattr__(new_store, "_host_lanes", store._host_lanes)
    # The miss-row pack buffers too: the row width and dtype are the host
    # table's, so a refresh allocates none.
    object.__setattr__(new_store, "_pack_ring", store.pack_ring())
    return new_store, FeatureRefreshStats(
        rows_kept=int(kept_nodes.shape[0]),
        rows_inserted=int(inserted_nodes.shape[0]),
        rows_evicted=int(evicted_nodes.shape[0]),
        physical_rows=int(physical),
        budget_rows=int(budget_rows),
    )


def build_embedding_cache(
    table: np.ndarray,
    access_counts: np.ndarray,
    capacity_bytes: int,
) -> FeatureStore:
    """DCI's sort-free fill applied to layer-*k* output EMBEDDINGS.

    The layer-wise executor (runtime/layerwise.py) spills each layer's
    outputs to a host-side table and re-reads them as the next layer's
    inputs; this builds the device cache those re-reads hit — the same
    :class:`FeatureStore` machinery (``position_map`` lookup, two-source
    ``gather``, row-block kernel route) as the input-feature cache, filled
    by :func:`select_hot_rows` over the chunk access pattern.  Unlike the
    presample-estimated feature counts, ``access_counts`` here is EXACT:
    a node's embedding is read once as a chunk member plus once per
    out-edge (``1 + bincount(row_index)``), known from the CSC alone.

    Slots are id-ordered like :func:`build_feature_cache`, so the chunk
    gathers' ascending-id runs hit contiguous hot-table rows — what the
    row-block ``cached_gather`` kernel collapses to one DMA per run.  The
    host mirrors are seeded from ``table`` directly (it already lives on
    the host), so building a per-layer cache never re-downloads the spill
    buffer.  A zero budget degrades to the cache-less store.
    """
    table = np.ascontiguousarray(table)
    n, f = table.shape
    row_bytes = f * table.dtype.itemsize
    budget_rows = min(max(int(capacity_bytes) // row_bytes, 0), n)
    hot = np.sort(select_hot_rows(access_counts, budget_rows))
    position_map = np.full(n, -1, np.int32)
    position_map[hot] = np.arange(hot.shape[0], dtype=np.int32)
    hot_table = table[hot] if hot.shape[0] else np.zeros((1, f), table.dtype)
    store = FeatureStore(
        host_table=jnp.asarray(table),
        hot_table=jnp.asarray(hot_table),
        position_map=jnp.asarray(position_map),
    )
    object.__setattr__(store, "_host_np", table)
    object.__setattr__(store, "_position_np", position_map)
    return store


def plain_feature_store(features: np.ndarray) -> FeatureStore:
    """No cache: everything is a miss except nothing — position map all −1."""
    n, f = features.shape
    return FeatureStore(
        host_table=jnp.asarray(features),
        hot_table=jnp.zeros((1, f), features.dtype),
        position_map=jnp.full((n,), -1, jnp.int32),
    )
