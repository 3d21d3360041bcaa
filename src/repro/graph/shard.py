"""Node-id-range sharding of the feature table + feature cache.

The sharded serving path (runtime/sharded_serve.py) partitions DCI's
feature side across a ``jax.sharding`` mesh by contiguous node-id range:
each shard holds its range's slice of the host table, a *local* hot table
re-slotted from the global feature cache (same rows, local slot ids), and
a local position map.  The adjacency cache is replicated per shard, so
only feature rows ever cross shards.

The exchange protocol is the all-to-all the dedup path set up in PR 5:
the device-side **sorted** unique ids partition into contiguous per-shard
segments with one ``searchsorted`` (:meth:`ShardedFeatureStore.partition`
— a stable shard-sort that degenerates to the identity for sorted input,
so unsorted/duplicate-carrying frontiers ride the same code path), each
shard gathers only its resident rows from its own hot/host tables, and
the results are copied back to the assembling device, concatenated, and
inverse-permuted — the caller's existing inverse map then reconstructs
the per-visit layout exactly as in the single-device path.  Every route
is a permutation of the same row copies, so outputs and the hit mask are
bit-for-bit identical to ``FeatureStore.gather`` over the same ids
(property-tested in tests/test_shard.py).

Per-shard pow2 buckets follow the one padding discipline
(:func:`~repro.graph.sampling.pow2_bucket`) and pad with a *shard-local*
known-cached id (:meth:`~repro.graph.features.FeatureStore.pad_node_id`
of the local store): pad slots are local-cache hits, never cross-shard
rows, so no shard ever stages a guaranteed-miss row for padding
(regression-tested in tests/test_dedup.py).
"""

from __future__ import annotations

import dataclasses
import typing

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.faults import InjectedFault
from repro.core.trace import resolve_tracer
from repro.graph.features import FeatureStore, PrefetchedMisses
from repro.graph.sampling import pow2_bucket

__all__ = [
    "ShardPlan",
    "ShardPartition",
    "ShardedPrefetch",
    "ShardedFeatureStore",
    "make_shard_plan",
    "partition_feature_store",
]


@dataclasses.dataclass(frozen=True)
class ShardPlan:
    """Contiguous node-id-range partition: shard ``s`` owns
    ``[row_starts[s], row_starts[s+1])``."""

    num_nodes: int
    row_starts: np.ndarray  # int64[num_shards + 1], 0 .. num_nodes

    @property
    def num_shards(self) -> int:
        return len(self.row_starts) - 1

    def bounds(self, s: int) -> tuple[int, int]:
        return int(self.row_starts[s]), int(self.row_starts[s + 1])

    def shard_of(self, ids: np.ndarray) -> np.ndarray:
        """Owning shard of each id.  ``side='right'`` maps an id on a
        boundary to the shard whose range *starts* there, so empty shards
        (equal consecutive starts) never receive ids."""
        return np.searchsorted(self.row_starts, np.asarray(ids), side="right") - 1

    def shard_sizes(self) -> np.ndarray:
        return np.diff(self.row_starts)


def make_shard_plan(num_nodes: int, num_shards: int) -> ShardPlan:
    """Balanced contiguous ranges; the first ``num_nodes % num_shards``
    shards get one extra row."""
    if num_shards < 1:
        raise ValueError(f"num_shards must be >= 1, got {num_shards}")
    base, rem = divmod(num_nodes, num_shards)
    sizes = np.full(num_shards, base, np.int64)
    sizes[:rem] += 1
    starts = np.zeros(num_shards + 1, np.int64)
    np.cumsum(sizes, out=starts[1:])
    return ShardPlan(num_nodes=num_nodes, row_starts=starts)


def partition_feature_store(
    store: FeatureStore, plan: ShardPlan, devices=None
) -> list[FeatureStore]:
    """Slice ``store`` into one local :class:`FeatureStore` per shard.

    Each shard's hot table holds exactly the globally-cached rows in its
    id range, re-slotted in ascending-id order — the same slot discipline
    :func:`~repro.graph.features.build_feature_cache` uses globally, so
    sorted segments keep their contiguous runs for the row-block kernel.
    Hot rows are copied from the host mirror (cached rows are always
    bit-identical copies of host rows, across refreshes too), so every
    sharded gather returns the same float bits as the global one.

    ``devices`` (optional, one jax device per shard — entries may repeat)
    commits each shard's arrays to its device; ``None`` leaves them on
    the default device (the co-resident layout the 1-device CI uses).
    """
    host = store.host_np()
    pos = store.position_np()
    shards: list[FeatureStore] = []
    for s in range(plan.num_shards):
        lo, hi = plan.bounds(s)
        local_pos = np.full(hi - lo, -1, np.int32)
        cached = np.nonzero(pos[lo:hi] >= 0)[0]  # ascending local ids
        local_pos[cached] = np.arange(cached.size, dtype=np.int32)
        hot = np.zeros((max(cached.size, 1), store.feat_dim), host.dtype)
        hot[: cached.size] = host[lo + cached]
        host_slice = host[lo:hi]
        dev = devices[s % len(devices)] if devices else None
        put = (lambda x, d=dev: jax.device_put(x, d)) if dev is not None else jnp.asarray
        fs = FeatureStore(
            host_table=put(host_slice),
            hot_table=put(hot),
            position_map=put(local_pos),
        )
        # Seed the host mirrors so per-batch partitioning never round-trips
        # the device (the global store does the same lazily).
        object.__setattr__(fs, "_host_np", host_slice)
        object.__setattr__(fs, "_position_np", local_pos)
        shards.append(fs)
    return shards


class ShardPartition(typing.NamedTuple):
    """One frontier's shard decomposition — shared by the prefetch stage
    and the gather that consumes it, so both see identical per-shard
    buckets.

    ``seg_ids[s]`` is shard ``s``'s pow2-padded **local** id bucket (None
    for shards with no positions); ``seg_len[s]`` of those are real
    frontier positions and ``seg_live[s]`` of those are live (original
    index < ``num_live`` — the dedup bucket's live prefix).  ``order`` is
    the stable shard-sort permutation over the original positions
    (identity for sorted-unique input); ``inv`` undoes it at reassembly
    (None when the identity)."""

    ids: np.ndarray
    asgn: np.ndarray
    order: np.ndarray
    inv: np.ndarray | None
    seg_ids: list
    seg_len: list
    seg_live: list

    @property
    def num_positions(self) -> int:
        return int(self.ids.size)


class ShardedPrefetch(typing.NamedTuple):
    """Per-shard staged miss packs (parallel to the shard list; None for
    empty segments).  ``num_miss`` sums the per-shard live miss counts —
    equal to the single-device staging count for the same frontier."""

    parts: list
    num_miss: int

    @property
    def staged_rows(self) -> int:
        """Rows the per-shard ``device_put`` calls moved, padding included."""
        return sum(p.staged_rows for p in self.parts if p is not None)

    @property
    def pack_buffer_allocs(self) -> int:
        """Host pack buffers the per-shard stagings allocated."""
        return sum(p.pack_buffer_allocs for p in self.parts if p is not None)


@dataclasses.dataclass
class ShardedFeatureStore:
    """The feature side of the dual cache, range-partitioned over shards.

    ``devices`` is the per-shard device list (None → all shards
    co-resident on the default device: partitioning, exchange, and
    accounting all still run — the layout the 1-device regression gate
    exercises).  ``assemble_device`` is where exchanged rows land (the
    device the forward runs on)."""

    plan: ShardPlan
    shards: list
    devices: list | None = None
    assemble_device: object | None = None

    @classmethod
    def partition_store(cls, store: FeatureStore, plan: ShardPlan, devices=None):
        shards = partition_feature_store(store, plan, devices)
        assemble = jax.devices()[0] if devices else None
        return cls(plan=plan, shards=shards, devices=devices, assemble_device=assemble)

    @property
    def num_shards(self) -> int:
        return self.plan.num_shards

    def shard_cached_rows(self) -> list[int]:
        return [int((s.position_np() >= 0).sum()) for s in self.shards]

    # ---------------------------------------------------------- partition
    def partition(self, ids: np.ndarray, *, num_live: int | None = None) -> ShardPartition:
        """Decompose a frontier (any order, duplicates allowed) into
        per-shard local-id buckets.

        A stable sort on the shard assignment groups positions by owning
        shard while preserving original order inside each group — for the
        dedup path's sorted unique ids the permutation is the identity
        and segments are contiguous sorted runs, exactly the
        ``searchsorted`` split the exchange protocol describes.  Each
        segment pads to its own pow2 bucket with the shard-LOCAL cached
        pad id (fallback: local row 0, still in-shard), and ``seg_live``
        clamps the live window so padding is never staged as a miss."""
        ids = np.asarray(ids)
        asgn = self.plan.shard_of(ids)
        order = np.argsort(asgn, kind="stable")
        identity = bool(np.array_equal(order, np.arange(ids.size)))
        starts = np.searchsorted(asgn[order], np.arange(self.num_shards + 1))
        live_limit = ids.size if num_live is None else int(num_live)
        seg_ids: list = []
        seg_len: list = []
        seg_live: list = []
        for s in range(self.num_shards):
            seg_pos = order[starts[s] : starts[s + 1]]
            if seg_pos.size == 0:
                seg_ids.append(None)
                seg_len.append(0)
                seg_live.append(0)
                continue
            lo, _ = self.plan.bounds(s)
            local = (ids[seg_pos] - lo).astype(np.int32)
            bucket = pow2_bucket(int(local.size))
            pad = self.shards[s].pad_node_id()
            buf = np.full(bucket, pad if pad >= 0 else 0, np.int32)
            buf[: local.size] = local
            seg_ids.append(buf)
            seg_len.append(int(local.size))
            # Positions inside a segment keep ascending original order
            # (stable sort), so the live ones are a prefix.
            seg_live.append(int(np.searchsorted(seg_pos, live_limit)))
        inv = None
        if not identity:
            inv = np.empty(ids.size, np.int64)
            inv[order] = np.arange(ids.size)
        return ShardPartition(
            ids=ids,
            asgn=asgn,
            order=order,
            inv=inv,
            seg_ids=seg_ids,
            seg_len=seg_len,
            seg_live=seg_live,
        )

    # ----------------------------------------------------------- prefetch
    def prefetch(
        self,
        part: ShardPartition,
        *,
        pack_in_thread: bool = True,
        down: set | None = None,
    ) -> ShardedPrefetch:
        """Stage each shard's live missed rows onto that shard's device.

        Mirrors :meth:`FeatureStore.prefetch_misses` per shard with
        ``num_live=seg_live[s]``: the union of per-shard live windows is
        exactly the frontier's live prefix, so the summed staging count —
        and the rows staged — match the single-device path.  Shards in
        ``down`` (failover, see :meth:`gather`) are skipped — their device
        is lost, and the host-path failover gather reads nothing staged."""
        parts: list = []
        total = 0
        for s, buf in enumerate(part.seg_ids):
            if buf is None or (down is not None and s in down):
                parts.append(None)
                continue
            staged = self.shards[s].prefetch_misses(
                buf,
                pack_in_thread=pack_in_thread,
                num_live=part.seg_live[s],
                device=self.devices[s % len(self.devices)] if self.devices else None,
            )
            parts.append(staged)
            total += staged.num_miss
        return ShardedPrefetch(parts=parts, num_miss=total)

    # ------------------------------------------------------------- gather
    def gather(
        self,
        part: ShardPartition,
        *,
        use_kernel: bool = False,
        gather_buffers: int = 2,
        prefetched: ShardedPrefetch | None = None,
        row_block: int | None = None,
        tracer=None,
        injector=None,
        down: set | None = None,
    ):
        """Per-shard gather + exchange-back + reassembly.

        Returns ``(features[B, F], hit[B])`` over all ``B`` frontier
        positions — bit-for-bit :meth:`FeatureStore.gather` over the same
        ids: every shard's rows are copies of the same host/hot rows, the
        exchange is pure ``device_put``/concat, and the inverse
        permutation restores the original position order.

        ``injector`` (core/faults.py, optional) charges one
        ``shard_exchange`` fault site per participating shard — restricted
        to the rule's named ``shard`` when it has one — with the raised
        :class:`InjectedFault` carrying the victim shard id.  ``down``
        names shards currently failed over: their segments skip the
        device exchange entirely and are served from the shard's HOST
        mirror (numpy, host memory — the path that survives a lost
        device).  Host-mirror rows are the same bits the device tables
        were filled from and the hit mask still comes from the shard's
        position map, so failover changes WHERE bytes come from, never
        values or hit accounting (per-shard sums still tile the global
        counters — tests/test_faults.py).

        ``tracer`` (core/trace.py, optional) records one ``exchange`` span
        per participating shard on its own ``shard s`` lane — the local
        gather dispatch plus the exchange-back ``device_put`` — and a
        ``reassemble`` span for the concat + inverse permutation;
        failed-over segments get a ``failover`` span instead."""
        tracer = resolve_tracer(tracer)
        rule = injector.plan.rule_for("shard_exchange") if injector is not None else None
        parts_f: list = []
        parts_h: list = []
        for s, buf in enumerate(part.seg_ids):
            if buf is None:
                continue
            if down is not None and s in down:
                with tracer.span(
                    "failover",
                    lane=f"shard {s}",
                    args={"rows": part.seg_len[s]} if tracer.enabled else None,
                ):
                    feats_s, hit_s = self._failover_gather(s, buf, part.seg_len[s])
                parts_f.append(feats_s)
                parts_h.append(hit_s)
                continue
            if rule is not None and (rule.shard is None or rule.shard == s):
                try:
                    injector.check("shard_exchange")
                except InjectedFault as err:
                    if err.shard is None:
                        err.shard = s  # attribute the loss to this exchange
                    raise
            with tracer.span(
                "exchange",
                lane=f"shard {s}",
                args={"rows": part.seg_len[s]} if tracer.enabled else None,
            ):
                dev = self.devices[s % len(self.devices)] if self.devices else None
                ids_dev = jax.device_put(buf, dev) if dev is not None else jnp.asarray(buf)
                pf = prefetched.parts[s] if prefetched is not None else None
                feats_s, hit_s = self.shards[s].gather(
                    ids_dev,
                    use_kernel=use_kernel,
                    gather_buffers=gather_buffers,
                    prefetched=pf,
                    row_block=row_block,
                )
                n = part.seg_len[s]
                feats_s, hit_s = feats_s[:n], hit_s[:n]
                if self.assemble_device is not None:
                    feats_s = jax.device_put(feats_s, self.assemble_device)
                    hit_s = jax.device_put(hit_s, self.assemble_device)
            parts_f.append(feats_s)
            parts_h.append(hit_s)
        with tracer.span("reassemble", lane="exchange"):
            feats = parts_f[0] if len(parts_f) == 1 else jnp.concatenate(parts_f, axis=0)
            hit = parts_h[0] if len(parts_h) == 1 else jnp.concatenate(parts_h, axis=0)
            if part.inv is not None:
                inv = jnp.asarray(part.inv.astype(np.int32))
                feats, hit = feats[inv], hit[inv]
        return feats, hit

    def _failover_gather(self, s: int, buf: np.ndarray, n: int):
        """Serve a DOWN shard's segment from its host mirror.

        The numpy host mirror (``_host_np``, seeded at partition time)
        lives in host memory and survives the loss of the shard's device;
        rows are the same bits the device tables hold and the hit mask is
        the same position-map test, so the failover route is bit-for-bit
        the exchange route — only slower (host gather + one device_put of
        the segment).  ``n`` trims the pow2 pad before assembly, exactly
        like the exchange path."""
        fb = self.shards[s]
        local = np.asarray(buf[:n], np.int64)
        feats_np = fb.host_np()[local]
        hit_np = fb.position_np()[local] >= 0
        if self.assemble_device is not None:
            return (
                jax.device_put(feats_np, self.assemble_device),
                jax.device_put(hit_np, self.assemble_device),
            )
        return jnp.asarray(feats_np), jnp.asarray(hit_np)
