"""Sharded dual-cache serving across a JAX device mesh.

Layout (ARCHITECTURE §10): the feature table + feature cache are
range-partitioned over a 1-D ``shard`` mesh (graph/shard.py — each shard
holds its id range's host slice and a local hot table re-slotted from the
global fill), while the adjacency cache is **replicated** per shard so
sampling never crosses devices.  Streams round-robin over the replicas;
each batch's frontier rides the all-to-all exchange: the dedup path's
sorted unique ids split into contiguous per-shard segments, every shard
gathers only its resident rows on its own device, and the results are
exchanged back to the assembling device and reassembled through the
existing inverse map.

Per-shard Eq. 1 allocation runs on per-shard telemetry — each shard's
slice of the visit counts scales its budget and stage times
(:func:`repro.core.allocation.shard_allocations`) — and because Eq. 1's
split fraction is scale-invariant, every shard's adj:feat split matches
the global one: the globally-ranked fill partitions by id range without
moving a single row.  That coordination is what makes sharded serving
**bit-for-bit** equivalent to the single-device path — logits, hit masks,
per-epoch counters, and refresh deltas are all identical across mesh
sizes and the full knob grid (tests/test_sharded_serve.py, run on a
4-virtual-device CPU mesh in CI via
``XLA_FLAGS=--xla_force_host_platform_device_count=4``).

Online refresh stays globally coordinated: the shared
:class:`~repro.runtime.cache_refresh.CacheRefreshManager` re-allocates
and delta-refills the base caches, and the server then *repartitions*
the per-shard stores and replicas to the new epoch on the same retire
boundary, recording genuinely per-shard allocations from the sliced
history.
"""

from __future__ import annotations

import dataclasses

import jax
import numpy as np

from repro.core.allocation import shard_allocations
from repro.core.faults import InjectedFault
from repro.graph.csc import BYTES_PER_ADJ_ELEMENT
from repro.graph.sampling import DedupFrontier
from repro.graph.shard import ShardedFeatureStore, make_shard_plan
from repro.launch.mesh import make_serving_mesh, serving_devices
from repro.runtime.gnn_engine import StreamRuntime
from repro.runtime.gnn_serve import MultiStreamServer, ServeReport
from repro.runtime.pipeline import BatchContext

__all__ = ["ShardedDualCache", "ShardedStreamRuntime", "ShardedServer"]


@dataclasses.dataclass
class ShardedDualCache:
    """The DualCache's sharded runtime view: per-shard feature stores +
    per-device adjacency replicas, rebuilt (repartitioned) whenever the
    base caches move to a new epoch.

    ``base`` stays the single source of truth — the sample stage's dedup
    pad id, the refresh manager, and the epoch counter all read it — so
    the sharded layout can never drift from the global fill."""

    base: object  # core.cache.DualCache
    plan: object  # graph.shard.ShardPlan
    store: ShardedFeatureStore
    adj_replicas: list
    devices: list | None
    epoch: int
    # Failover state (core/faults.py shard_exchange site): shard id →
    # retired batches left until rejoin (-1 = until process end).  While a
    # shard is down its id-range is served from the host-mirror fallback
    # (ShardedFeatureStore._failover_gather) — values and hit accounting
    # bit-identical, only the byte route changes.
    down: dict = dataclasses.field(default_factory=dict)
    failovers: list = dataclasses.field(default_factory=list)

    @property
    def down_set(self) -> set:
        return set(self.down)

    def mark_down(self, shard: int, *, down_for: int | None = None, call: int = 0) -> None:
        """Record a lost shard; idempotent while already down."""
        if shard not in self.down:
            self.down[shard] = -1 if down_for is None else int(down_for)
            self.failovers.append(
                {"shard": int(shard), "down_for": self.down[shard], "call": int(call)}
            )

    def note_retired(self) -> list[int]:
        """Tick rejoin countdowns at a retire boundary; returns the shards
        that just rejoined (their device exchange resumes on the next
        batch — the host fallback was bit-identical, so rejoin is also
        invisible to outputs)."""
        rejoined = []
        for shard in list(self.down):
            if self.down[shard] < 0:
                continue
            self.down[shard] -= 1
            if self.down[shard] <= 0:
                del self.down[shard]
                rejoined.append(shard)
        return rejoined

    @classmethod
    def build(cls, caches, num_shards: int, devices=None) -> "ShardedDualCache":
        plan = make_shard_plan(caches.store.num_nodes, num_shards)
        return cls(
            base=caches,
            plan=plan,
            store=ShardedFeatureStore.partition_store(caches.store, plan, devices),
            adj_replicas=cls._replicate_adj(caches.dgraph, devices),
            devices=devices,
            epoch=caches.epoch,
        )

    @staticmethod
    def _replicate_adj(dgraph, devices) -> list:
        """One adjacency replica per shard device (deduplicated: shards
        mapped to the same physical device share one copy; the
        co-resident layout shares the base arrays outright)."""
        if not devices:
            return [dgraph]
        copies: dict = {}
        return [copies.setdefault(d, jax.device_put(dgraph, d)) for d in devices]

    def adj_replica(self, i: int):
        return self.adj_replicas[i % len(self.adj_replicas)]

    def repartition(self) -> dict:
        """Re-slice the per-shard stores and replicas from the base caches
        (call after a base refresh lands).  Returns the per-shard delta —
        cached-row counts before/after — for the repartition log."""
        before = self.store.shard_cached_rows()
        self.store = ShardedFeatureStore.partition_store(self.base.store, self.plan, self.devices)
        self.adj_replicas = self._replicate_adj(self.base.dgraph, self.devices)
        self.epoch = self.base.epoch
        return {
            "epoch": self.epoch,
            "rows_before": before,
            "rows_after": self.store.shard_cached_rows(),
        }


class ShardedStreamRuntime(StreamRuntime):
    """A :class:`StreamRuntime` whose cache accesses route through the
    sharded layout.  Only the three cache-access hooks (and host-side
    per-shard accounting) differ from the base class: control flow, RNG,
    and every counter the reports surface stay byte-identical."""

    def __init__(self, *args, sharded: ShardedDualCache, replica: int = 0, **kwargs):
        super().__init__(*args, **kwargs)
        self.sharded = sharded
        self.replica = replica
        k = sharded.plan.num_shards
        self.shard_feat_hits = np.zeros(k, np.int64)
        self.shard_feat_lookups = np.zeros(k, np.int64)
        self.shard_gathered_rows = np.zeros(k, np.int64)
        self.shard_prefetched_rows = np.zeros(k, np.int64)

    # --------------------------------------------------- cache-access hooks
    def _sample_graph(self):
        return self.sharded.adj_replica(self.replica)

    def _resolve_dedup(self, ctx, block):
        view = super()._resolve_dedup(ctx, block)
        assemble = self.sharded.store.assemble_device
        if assemble is not None:
            # The inverse map was produced on this stream's sampling
            # replica; the forward consumes it together with the
            # exchanged rows on the assembling device, so re-home it here
            # (a pure copy — the reconstruction stays bit-identical).
            dd, nu, bucket, uids = view
            dd = DedupFrontier(
                unique_ids=dd.unique_ids,
                inverse=jax.device_put(dd.inverse, assemble),
                num_unique=dd.num_unique,
            )
            view = (dd, nu, bucket, uids)
            ctx.outputs["_dedup"] = view
        return view

    def _partition(self, ctx, ids):
        part = ctx.outputs.get("_shardpart")
        if part is None:
            num_live = self._dedup_view(ctx)[1] if self.dedup else None
            part = self.sharded.store.partition(np.asarray(ids), num_live=num_live)
            ctx.outputs["_shardpart"] = part
        return part

    def _prefetch(self, ctx, nodes, num_live=None):
        del num_live  # the partition's per-shard live windows carry it
        if self.injector is not None:
            # Charged ONCE per batch at the runtime level (the per-shard
            # fan-out below is one logical staging op), mirroring the
            # single-device FeatureStore.prefetch_misses site.
            self.injector.check("prefetch")
        staged = self.sharded.store.prefetch(
            self._partition(ctx, nodes), down=self.sharded.down_set or None
        )
        for s, p in enumerate(staged.parts):
            if p is not None:
                self.shard_prefetched_rows[s] += p.num_miss
        return staged

    def _gather(self, ctx, indices, **gather_kw):
        if self.injector is not None:
            # Same once-per-batch charging as FeatureStore.gather: the
            # whole-frontier host path, then the kernel route when on.
            self.injector.check("host_fetch")
            if gather_kw.get("use_kernel"):
                self.injector.check("kernel_gather")
        part = self._partition(ctx, indices)
        for s, buf in enumerate(part.seg_ids):
            if buf is not None:
                self.shard_gathered_rows[s] += len(buf)
        while True:
            try:
                return self.sharded.store.gather(
                    part,
                    tracer=self.tracer,
                    injector=self.injector,
                    down=self.sharded.down_set or None,
                    **gather_kw,
                )
            except InjectedFault as err:
                if err.site != "shard_exchange" or err.shard is None:
                    raise
                # Lost device mid-exchange: fail the shard over to its
                # host mirror and redo the gather — already-exchanged
                # segments re-gather the same bits, the victim's segment
                # takes the fallback route, and the loop converges (a
                # downed shard is never charged again).
                rule = self.injector.plan.rule_for("shard_exchange")
                self.sharded.mark_down(
                    err.shard,
                    down_for=rule.down_for if rule is not None else None,
                    call=err.call,
                )
                if self.tracer.enabled:
                    self.tracer.complete(
                        "shard-down",
                        lane="faults",
                        ts_us=self.tracer.now_us(),
                        dur_us=0.0,
                        args={"shard": err.shard, "call": err.call},
                    )

    # ----------------------------------------------------------- accounting
    def record(self, ctx) -> None:
        super().record(ctx)
        part = ctx.outputs.get("_shardpart")
        if part is None:
            return
        feature_out = ctx.outputs["feature"]
        if self.dedup:
            # Per-VISIT accounting by owning shard: each unique node's hit
            # bit weighted by its visit multiplicity — sums across shards
            # to the global per-visit counters (tests/test_shard.py).
            dd, nu, _, _ = self._dedup_view(ctx)
            mult = np.bincount(np.asarray(dd.inverse), minlength=nu)[:nu].astype(np.int64)
            hit_u = np.asarray(feature_out[3])[:nu].astype(bool)
            asgn = part.asgn[:nu]
            np.add.at(self.shard_feat_lookups, asgn, mult)
            np.add.at(self.shard_feat_hits, asgn[hit_u], mult[hit_u])
        else:
            hit = np.asarray(feature_out[1]).astype(bool)
            self.shard_feat_lookups += np.bincount(
                part.asgn, minlength=self.sharded.plan.num_shards
            ).astype(np.int64)
            self.shard_feat_hits += np.bincount(
                part.asgn[hit], minlength=self.sharded.plan.num_shards
            ).astype(np.int64)


class ShardedServer(MultiStreamServer):
    """:class:`MultiStreamServer` over the sharded dual cache.

    ``mesh`` (or ``num_shards``) picks the layout: shards map round-robin
    onto the mesh's devices, and when the mesh has a single device the
    shards co-reside there — same partition math, same per-shard
    accounting, no cross-device copies (mesh size 1 is bit-for-bit the
    base server; asserted in tests/test_sharded_serve.py).  All base
    knobs (depth, prefetch, kernel, dedup, refresh, admission subclasses)
    compose unchanged."""

    def __init__(self, engine, *, num_shards: int | None = None, mesh=None, **kwargs):
        super().__init__(engine, **kwargs)
        if num_shards is None and self.config.mesh:
            # ServeConfig.mesh is the requested shard count (0 = derive
            # from the device mesh); the ``mesh`` keyword here is the JAX
            # mesh object itself and stays a live parameter.
            num_shards = self.config.mesh
        if mesh is None:
            mesh = make_serving_mesh(num_shards or 1)
        devices = serving_devices(mesh)
        if num_shards is None:
            num_shards = len(devices)
        self.mesh = mesh
        self.num_shards = num_shards
        shard_devices = [devices[s % len(devices)] for s in range(num_shards)]
        if len(set(devices)) == 1:
            # One physical device → co-resident shards; skip the (no-op
            # but not free) cross-device transfer plumbing entirely.
            shard_devices = None
        self.sharded = ShardedDualCache.build(
            engine.pipeline.caches, num_shards, shard_devices
        )
        self.repartition_log: list[dict] = []
        self.shard_allocations = self._initial_shard_allocations()

    # ----------------------------------------------------------- plumbing
    def _make_runtime(self, sid: int, seed: int, *, collect_outputs: bool):
        return ShardedStreamRuntime(
            self.engine.pipeline,
            self.engine.params,
            model=self.engine.model,
            fanouts=self.engine.fanouts,
            num_nodes=self.engine.dataset.num_nodes,
            key=jax.random.PRNGKey(seed + 1),
            collect_outputs=collect_outputs,
            prefetch=self.prefetch,
            use_kernel=self.use_kernel,
            gather_buffers=self.gather_buffers,
            dedup=self.dedup,
            injector=self.injector,
            retry_policy=self.retry_policy,
            degraded_mode=self.degraded_mode,
            sharded=self.sharded,
            replica=sid % self.num_shards,
        )

    def _initial_shard_allocations(self):
        """Per-shard Eq. 1 from the presample profile (the same counts
        the global fill ranked on); None for cacheless policies."""
        alloc = self.engine.pipeline.caches.allocation
        if alloc is None:
            return None
        plan = self.sharded.plan
        ps = self.engine.pipeline.presample
        if ps is not None:
            counts = np.asarray(ps.node_counts, np.float64)
            weights = [float(counts[lo:hi].sum()) for lo, hi in map(plan.bounds, range(plan.num_shards))]
            sample_times = list(ps.sample_times)
            feature_times = list(ps.feature_times)
        else:
            weights = []
            sample_times = [alloc.sample_fraction]
            feature_times = [1.0 - alloc.sample_fraction]
        if not any(w > 0 for w in weights):
            weights = [float(hi - lo) for lo, hi in map(plan.bounds, range(plan.num_shards))]
        return shard_allocations(
            alloc,
            weights,
            sample_times=sample_times,
            feature_times=feature_times,
            adj_need_bytes=self.engine.dataset.graph.num_edges * BYTES_PER_ADJ_ELEMENT,
            feat_need_bytes=self.engine.dataset.features.nbytes,
        )

    def _on_retire(self, ctx) -> None:
        super()._on_retire(ctx)
        if self.sharded.down:
            # Failover rejoin ticks on the same retire boundary every
            # other epoch-style transition uses, so no batch ever sees a
            # mixed layout mid-flight.
            for shard in self.sharded.note_retired():
                if self.tracer.enabled:
                    self.tracer.instant("shard-rejoin", lane="faults", args={"shard": shard})

    def _apply_refresh_event(self, event) -> None:
        super()._apply_refresh_event(event)
        # The manager refreshed the BASE caches (global Eq. 1 + globally
        # ranked delta re-fill); re-slice the shards to the new epoch on
        # the same retire boundary so no batch ever sees a mixed layout,
        # and record the genuinely per-shard allocations from the sliced
        # history.
        stats = self.sharded.repartition()
        stats["reason"] = event.reason
        self.repartition_log.append(stats)
        if self.refresh_manager is not None:
            self.shard_allocations = self.refresh_manager.shard_allocations(self.sharded.plan)

    # ---------------------------------------------------------------- run
    def _warmup_sharded(self, seeds: np.ndarray) -> None:
        """Compile each replica's sampler + the per-shard gathers + the
        forward outside the timed loop, using a scratch runtime per
        replica (stream state and RNG sequences untouched)."""
        for r in range(min(self.num_shards, len(self.sharded.adj_replicas))):
            rt = self._make_runtime(r, self.engine.seed, collect_outputs=False)
            # Warmup must not consume fault-plan draws (the serve loop's
            # replay is a pure function of plan + serve-path call index)
            # nor fault before serving starts.
            rt.injector = None
            rt.retry_policy = None
            ctx = BatchContext(-1 - r, np.asarray(seeds))
            ctx.outputs["sample"] = rt.sample(ctx)
            if self.prefetch:
                ctx.outputs["prefetch"] = rt.prefetch_stage(ctx)
            ctx.outputs["feature"] = rt.feature(ctx)
            jax.block_until_ready(rt.compute(ctx))

    def run(self, *, warmup: bool = True, raise_on_error: bool = True) -> ServeReport:
        if warmup:
            seeds = self._warmup_seeds()
            if seeds is not None:
                self._warmup_sharded(seeds)
        return super().run(warmup=False, raise_on_error=raise_on_error)

    # ------------------------------------------------------------- report
    def _shard_summaries(self) -> list[dict]:
        k = self.num_shards
        hits = np.zeros(k, np.int64)
        lookups = np.zeros(k, np.int64)
        gathered = np.zeros(k, np.int64)
        prefetched = np.zeros(k, np.int64)
        adj_hits = np.zeros(k, np.int64)
        adj_lookups = np.zeros(k, np.int64)
        for s in self.streams:
            rt = s.runtime
            hits += rt.shard_feat_hits
            lookups += rt.shard_feat_lookups
            gathered += rt.shard_gathered_rows
            prefetched += rt.shard_prefetched_rows
            # Adjacency traffic lands on the stream's sampling replica.
            adj_hits[rt.replica % k] += rt.adj_hits
            adj_lookups[rt.replica % k] += rt.adj_lookups
        rows_cached = self.sharded.store.shard_cached_rows()
        out = []
        for i in range(k):
            entry = {
                "shard": i,
                "rows_cached": rows_cached[i],
                "feat_hits": int(hits[i]),
                "feat_lookups": int(lookups[i]),
                "adj_hits": int(adj_hits[i]),
                "adj_lookups": int(adj_lookups[i]),
                "gathered_rows": int(gathered[i]),
                "prefetched_rows": int(prefetched[i]),
            }
            if self.shard_allocations is not None:
                a = self.shard_allocations[i]
                entry["allocation"] = {
                    "total_bytes": a.total_bytes,
                    "adj_bytes": a.adj_bytes,
                    "feat_bytes": a.feat_bytes,
                    "sample_fraction": round(a.sample_fraction, 6),
                }
            out.append(entry)
        return out

    def _resolved_config(self):
        # Echo the shard count actually built (mesh=0 requests derive it
        # from the device mesh, so the request alone doesn't say).
        return super()._resolved_config().replace(mesh=self.num_shards)

    def _serve_report(self, wall: float) -> ServeReport:
        rep = super()._serve_report(wall)
        rep.num_shards = self.num_shards
        rep.shards = self._shard_summaries()
        if self.sharded.failovers:
            for shard, entry in enumerate(rep.shards):
                entry["failed_over"] = any(
                    f["shard"] == shard for f in self.sharded.failovers
                )
            rep.failovers = list(self.sharded.failovers)
        return rep
