"""End-to-end sampled GNN inference engine (the system Fig. 5 describes).

Pipeline per mini-batch: sample blocks (adjacency cache aware) → gather
input-frontier features (feature cache aware; RAIN reuses the previous
batch instead) → run the GNN.  The engine times each stage exactly the way
the paper decomposes Fig. 1/7, counts cache hits, and also reports a
*modeled* transfer time using bandwidth constants so the CPU-only container
can be projected onto the paper's PCIe/GPU (or a TPU host-HBM) topology.

Batch execution is delegated to the staged executor in
:mod:`repro.runtime.pipeline`, controlled by the ``pipeline_depth`` knob:
``depth=1`` is the paper's serial loop (a device sync after every stage —
the timing semantics of Fig. 1/7), ``depth>1`` keeps that many batches in
flight so batch *i+1*'s sampling/gather overlap batch *i*'s GNN forward.
Four further execution knobs — ``prefetch`` (stage batch *i+1*'s missed
host feature rows onto the device during batch *i*'s forward),
``use_kernel`` (route gathers through the Pallas ``cached_gather``
kernel), ``gather_buffers`` (the kernel's row copies in flight), and
``dedup`` (sort-and-unique each input frontier on device and
gather/prefetch/model one row per DISTINCT node, expanding through the
inverse map) — default from the prepared pipeline.  Outputs, hit counts,
and batch order are identical under every knob combination; only where
the bytes move (and therefore wall clock) changes.
"""

from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.config import EngineConfig, coalesce
from repro.core.faults import InjectedFault
from repro.core.policies import PreparedPipeline, prepare
from repro.core.retry import RetryExhausted, StageTimeout, call_with_retry
from repro.core.trace import resolve_tracer
from repro.graph.datasets import SyntheticGraphDataset
from repro.graph.sampling import pow2_bucket, sample_blocks
from repro.kernels.cached_gather.kernel import ROW_BLOCK
from repro.models import gnn as gnn_models
from repro.runtime.pipeline import PipelinedExecutor, Stage
from repro.utils.timing import StageClock

__all__ = [
    "GNNInferenceEngine",
    "InferenceReport",
    "StreamRuntime",
    "auto_pipeline_depth",
    "stream_stages",
    "summarize_epoch_counters",
]

# Link speeds for the modeled-transfer projection (bytes/s).
PCIE4_BW = 25e9  # paper's RTX 4090 host link (the UVA miss path)
HBM_BW = 819e9  # TPU v5e HBM (the cache-hit path)

ADJ_ENTRY_BYTES = 4  # one int32 neighbor id per adjacency lookup


def modeled_transfer_seconds(
    *,
    feat_lookups: int,
    feat_hits: int,
    adj_lookups: int,
    adj_hits: int,
    feat_row_bytes: int,
    slow_bw: float = PCIE4_BW,
    fast_bw: float = HBM_BW,
) -> float:
    """Project byte movement onto a slow (miss) / fast (hit) link pair.

    The one transfer model shared by the per-engine
    :class:`InferenceReport` and the aggregate multi-stream
    :class:`~repro.runtime.gnn_serve.ServeReport`."""
    miss_bytes = (feat_lookups - feat_hits) * feat_row_bytes + (
        adj_lookups - adj_hits
    ) * ADJ_ENTRY_BYTES
    hit_bytes = feat_hits * feat_row_bytes + adj_hits * ADJ_ENTRY_BYTES
    return miss_bytes / slow_bw + hit_bytes / fast_bw


@dataclasses.dataclass
class InferenceReport:
    policy: str
    num_batches: int
    sample_seconds: float
    feature_seconds: float
    compute_seconds: float
    prep_seconds: float
    adj_hits: int
    adj_lookups: int
    feat_hits: int
    feat_lookups: int
    feat_row_bytes: int
    pipeline_depth: int = 1
    prefetch: bool = False
    prefetch_seconds: float = 0.0
    prefetched_rows: int = 0  # live missed rows the prefetch stage staged
    staged_rows: int = 0  # rows its device_puts moved, pow2 pack padding included
    pack_buffer_allocs: int = 0  # host pack buffers it allocated (0 once warm)
    # Unique-frontier accounting: ``unique_rows`` sums each batch's
    # distinct input nodes, ``gathered_rows`` the rows the feature stage
    # actually pulled (the pow2 gather buckets under dedup, every
    # duplicate otherwise).  feat_lookups stays the per-visit count, so
    # hit rates are dedup-invariant.
    dedup: bool = False
    unique_rows: int = 0
    gathered_rows: int = 0
    # Edge scores the forward computed, heads counted (Σ over layers of
    # S_dst × (fanout + 1) × heads; 0 for models without attention),
    # counted on the host from static shapes.
    attn_edges: int = 0
    # Online-refresh accounting (empty/None when refresh is off, keeping
    # the report — and every baseline comparison over it — unchanged):
    refresh_events: list = dataclasses.field(default_factory=list)
    epoch_hits: dict | None = None  # epoch -> per-epoch hit-rate summary
    # The RESOLVED config the run actually executed with (every knob
    # concrete, server-level overrides applied) — the single source the
    # knob echo comes from, so it can never drift from execution.
    config: EngineConfig | None = None
    # MetricsRegistry.snapshot() at report time when the run was given a
    # registry (``--metrics``); None otherwise.
    metrics: dict | None = None
    # Fault-tolerance outcomes (zero without an injector).
    kernel_fallbacks: int = 0  # kernel_gather faults rerouted to the table path
    degraded_batches: int = 0  # batches served cache-only (miss path down)

    @property
    def total_seconds(self) -> float:
        # With pipeline_depth > 1 the stage seconds are dispatch times plus
        # each stage's retire-boundary drain, so the sum still tracks the
        # loop's wall clock — overlapped waiting is simply no longer
        # double-counted across stages.  The prefetch stage (off by
        # default) books the host→device staging of missed rows.
        return (
            self.sample_seconds
            + self.prefetch_seconds
            + self.feature_seconds
            + self.compute_seconds
        )

    @property
    def adj_hit_rate(self) -> float:
        return self.adj_hits / max(self.adj_lookups, 1)

    @property
    def feat_hit_rate(self) -> float:
        return self.feat_hits / max(self.feat_lookups, 1)

    @property
    def duplication_factor(self) -> float:
        """Mean input-frontier duplication: per-visit lookups over distinct
        rows — the redundancy the dedup path removes (1.0 when off)."""
        if not self.unique_rows:
            return 1.0
        return self.feat_lookups / self.unique_rows

    def modeled_transfer_seconds(self, slow_bw: float = PCIE4_BW, fast_bw: float = HBM_BW) -> float:
        """Project byte movement onto a slow (miss) / fast (hit) link pair."""
        return modeled_transfer_seconds(
            feat_lookups=self.feat_lookups,
            feat_hits=self.feat_hits,
            adj_lookups=self.adj_lookups,
            adj_hits=self.adj_hits,
            feat_row_bytes=self.feat_row_bytes,
            slow_bw=slow_bw,
            fast_bw=fast_bw,
        )

    def to_dict(self) -> dict:
        """The report as one JSON-safe dict: the summary metrics plus the
        resolved :class:`~repro.core.config.EngineConfig` echo.  Knobs are
        read off ``config`` when present — NOT re-listed by hand — so a
        server-level override (e.g. a per-stream depth) can never drift
        from what actually executed."""
        return self.summary()

    def summary(self) -> dict:
        out = {
            "policy": self.policy,
            "batches": self.num_batches,
            "pipeline_depth": (
                self.config.pipeline_depth if self.config is not None else self.pipeline_depth
            ),
            "prefetch": self.config.prefetch if self.config is not None else self.prefetch,
            "dedup": self.config.dedup if self.config is not None else self.dedup,
            "sample_s": round(self.sample_seconds, 4),
            "prefetch_s": round(self.prefetch_seconds, 4),
            "feature_s": round(self.feature_seconds, 4),
            "compute_s": round(self.compute_seconds, 4),
            "total_s": round(self.total_seconds, 4),
            "prep_s": round(self.prep_seconds, 4),
            "adj_hit_rate": round(self.adj_hit_rate, 4),
            "feat_hit_rate": round(self.feat_hit_rate, 4),
            "modeled_transfer_s": round(self.modeled_transfer_seconds(), 6),
        }
        if self.config is not None:
            out["config"] = self.config.to_dict()
        if self.dedup:
            out["unique_rows"] = self.unique_rows
            out["gathered_rows"] = self.gathered_rows
            out["duplication_factor"] = round(self.duplication_factor, 2)
        if self.refresh_events:
            # Per-epoch rates replace the single lifetime aggregate as the
            # headline when the cache changed mid-run — a lifetime mean
            # hides exactly the recovery a refresh exists to produce.
            out["refresh_events"] = [e.summary() for e in self.refresh_events]
            out["per_epoch"] = self.epoch_hits
        if self.metrics is not None:
            out["metrics"] = self.metrics
        return out


class StreamRuntime:
    """Cross-batch state and stage logic for ONE stream of mini-batches.

    Owns the stream's RNG key sequence, RAIN's previous-batch reuse state,
    the hit counters, and (optionally) the collected logits.  The engine
    runs exactly one ``StreamRuntime``; the multi-stream server
    (:mod:`repro.runtime.gnn_serve`) runs one per request stream against a
    single shared :class:`~repro.core.cache.DualCache` — the stage methods
    only *read* the caches (they are immutable at serve time), so batches
    from different streams interleave freely while each stream's RNG
    sequence, reuse ordering, and hit accounting stay bit-identical to a
    solo run (tested in tests/test_gnn_serve.py).

    Stage methods are invoked in per-stream batch order at any pipeline
    depth (the executor dispatches in admission order), which is what the
    mutable ``key`` / ``prev_*`` state relies on.
    """

    def __init__(
        self,
        pipe: PreparedPipeline,
        params,
        *,
        model: str,
        fanouts: tuple[int, ...],
        num_nodes: int,
        key,
        collect_outputs: bool = False,
        prefetch: bool | None = None,
        use_kernel: bool | None = None,
        gather_buffers: int | None = None,
        dedup: bool | None = None,
        injector=None,
        retry_policy=None,
        degraded_mode: bool = False,
    ):
        self.pipe = pipe
        self.params = params
        self.model = model
        self.fanouts = tuple(fanouts)
        self.key = key
        # Execution knobs default from the prepared pipeline so every
        # consumer (engine, presampler, serving layer) resolves them the
        # same way; explicit arguments override per run.
        self.prefetch = pipe.prefetch if prefetch is None else prefetch
        self.use_kernel = pipe.use_kernel if use_kernel is None else use_kernel
        self.gather_buffers = pipe.gather_buffers if gather_buffers is None else gather_buffers
        # RAIN's cross-batch reuse map addresses individual frontier
        # positions of the previous batch, which is exactly the layout
        # dedup collapses — and RAIN already removes the cross-batch share
        # of the redundancy dedup targets — so the two are mutually
        # exclusive and reuse wins.
        self.dedup = (pipe.dedup if dedup is None else dedup) and not pipe.reuse_prev_batch
        # Fault-tolerance wiring (core/faults.py, core/retry.py): with the
        # injector absent and no retry policy, every guard below is a single
        # ``is not None`` test — the stage bytecode, RNG draws, and all
        # accounting are bit-identical to a build without this subsystem.
        self.injector = injector
        self.retry_policy = retry_policy
        self.degraded_mode = degraded_mode
        self.stage_retries = 0  # backoff retries across all sites
        self.degraded_batches = 0  # batches served cache-only (miss path down)
        self.kernel_fallbacks = 0  # kernel_gather faults rerouted to the table path
        self._retry_seq = 0  # per-stream retry-key sequence (deterministic jitter)
        self.adj_hits = 0
        self.adj_lookups = 0
        self.feat_hits = 0
        self.feat_lookups = 0
        self.prefetched_rows = 0
        self.staged_rows = 0  # rows the prefetch device_puts moved (pack padding included)
        self.pack_buffer_allocs = 0  # host pack buffers the prefetch stage allocated
        self.unique_rows = 0  # sum of per-batch distinct input nodes (dedup)
        self.gathered_rows = 0  # rows the feature stage actually gathered
        self.attn_edges = 0  # edge scores the forward computed, heads counted
        # Per-cache-epoch hit counters: epoch -> [adj_hits, adj_lookups,
        # feat_hits, feat_lookups, batches].  With refresh off everything
        # lands in epoch 0 and the lifetime counters above tell the whole
        # story; with refresh on the split is what the drift benchmark and
        # serve reports surface.
        self.epoch_counters: dict[int, list[int]] = {}
        # Serve-time telemetry sink (set by the refresh manager); None in
        # the default path, which then records nothing at retire.
        self.telemetry = None
        # Observability handle (core/trace.py), installed by the owning
        # engine/server; the no-op default keeps stage methods free.
        self.tracer = resolve_tracer(None)
        self.outputs: list[np.ndarray] | None = [] if collect_outputs else None
        # RAIN cross-batch reuse state (only touched when the policy asks).
        self._prev_map = np.full(num_nodes, -1, np.int64)
        self._prev_feats = None
        self._prev_nodes = None

    # ---------------------------------------------------- fault tolerance
    def _with_retry(self, ctx, site: str, fn):
        """Run ``fn`` under the stream's retry policy, charging backoff
        retries to ``site``.  Only *injected* faults and per-stage timeouts
        are retryable — real bugs propagate on the first attempt.  The
        jitter key is ``(site, seq)`` with a per-stream sequence counter, so
        the delay schedule is a pure function of the policy seed and the
        order faults land, never of wall-clock."""
        if self.retry_policy is None:
            return fn()
        self._retry_seq += 1
        seq = self._retry_seq

        def _on_retry(attempt, delay, err):
            self.stage_retries += 1
            ctx.outputs["_retried"] = ctx.outputs.get("_retried", 0) + 1
            if self.tracer.enabled:
                self.tracer.complete(
                    "retry",
                    lane="faults",
                    ts_us=self.tracer.now_us(),
                    dur_us=delay * 1e6,
                    args={"site": site, "attempt": attempt},
                )

        return call_with_retry(
            fn,
            policy=self.retry_policy,
            key=(site, seq),
            retryable=(InjectedFault, StageTimeout),
            on_retry=_on_retry,
        )

    def _mark_degraded(self, ctx) -> None:
        """Flag the batch as served degraded (cache-only hit rows, zero
        miss rows) so retire-time accounting and the serve report surface
        it per request."""
        self.degraded_batches += 1
        ctx.outputs["_degraded"] = True
        if self.tracer.enabled:
            self.tracer.complete(
                "degraded",
                lane="faults",
                ts_us=self.tracer.now_us(),
                dur_us=0.0,
                args={"site": "host_fetch"},
            )

    def _trace_kw(self, ctx) -> dict:
        """Lane and args of a span inside ``ctx``'s stages: the batch's slot
        lane and its index (empty when tracing is off)."""
        if not self.tracer.enabled:
            return {}
        return {"lane": PipelinedExecutor.slot_lane(ctx), "args": {"batch": ctx.index}}

    # ------------------------------------------------------------- stages
    def sample(self, ctx):
        # Stamp the cache epoch the batch dispatches against — retire-time
        # accounting attributes its hits to this epoch even if a refresh
        # lands while the batch is still in flight.
        ctx.epoch = self.pipe.caches.epoch
        if self.injector is not None and self.injector.active("adj_fetch"):
            # Charge BEFORE the RNG key split so a retried attempt replays
            # the exact same batch — the fault site is idempotent.  There
            # is no degraded fallback for adjacency: without the graph
            # there is nothing to sample, so exhausted retries propagate.
            self._with_retry(ctx, "adj_fetch", lambda: self.injector.check("adj_fetch"))
        self.key, sub = jax.random.split(self.key)
        block = sample_blocks(
            sub,
            self._sample_graph(),
            jnp.asarray(ctx.payload),
            self.fanouts,
            dedup=self.dedup,
            # Pad the unique bucket's tail with a known-cached id (traced
            # operand — a refresh-epoch pad change recompiles nothing), so
            # pad slots are feature-cache hits, never phantom miss rows.
            dedup_pad_id=self.pipe.caches.store.pad_node_id() if self.dedup else None,
        )
        # Dispatch the hit-stat reductions here, in-pipeline: dispatched
        # at retire time they would queue behind the *next* batch's
        # stages on the device stream and serialize the pipeline.
        bh, bt = block.adj_hit_stats()
        if self.dedup:
            # Resolve the unique view HERE so the one forced sync the
            # dedup path needs (pulling the num_unique scalar — the
            # analogue of the prefetch stage's miss-index read) is booked
            # to the sampling stage that produced it; the downstream
            # stages then only dispatch against the already-sliced bucket.
            self._resolve_dedup(ctx, block)
        return block, bh, bt

    def _resolve_dedup(self, ctx, block):
        """Cache the batch's unique-frontier view on its context:
        ``(dedup, num_unique, bucket, unique_ids[:bucket])``.

        The bucket is each batch's own pow2 ceiling, so ``gathered_rows <=
        2 * unique_rows`` holds per batch (the bound the dedup gate and
        docs state) and batches with the same bucket share compiled
        gather/forward programs — O(log S) distinct shapes worst case,
        each compiled once on first use."""
        dd = block.dedup
        with self.tracer.span("sync:num_unique", **self._trace_kw(ctx)):
            nu = int(dd.num_unique)
        bucket = pow2_bucket(nu, int(dd.unique_ids.shape[0]))
        view = (dd, nu, bucket, dd.unique_ids[:bucket])
        ctx.outputs["_dedup"] = view
        return view

    def _dedup_view(self, ctx):
        return ctx.outputs["_dedup"]

    # ------------------------------------------------- cache-access hooks
    # The sharded serving layer (runtime/sharded_serve.py) overrides these
    # three — and ONLY these — so every stage's control flow, RNG use, and
    # accounting stays byte-identical across layouts.
    def _sample_graph(self):
        """The DeviceGraph the sample stage expands against (per-shard
        adjacency replica in the sharded path)."""
        return self.pipe.caches.dgraph

    def _prefetch(self, ctx, nodes, num_live=None):
        """Stage a batch's missed host rows; returns an object exposing
        ``num_miss``, ``staged_rows`` and ``pack_buffer_allocs`` that the
        consuming ``_gather`` accepts via its ``prefetched`` keyword."""
        return self.pipe.caches.store.prefetch_misses(
            nodes,
            num_live=num_live,
            injector=self.injector,
            tracer=self.tracer,
            **self._trace_kw(ctx),
        )

    def _gather(self, ctx, indices, **gather_kw):
        """Two-source feature gather over ``indices`` → ``(feats, hit)``."""
        del ctx
        return self.pipe.caches.store.gather(indices, injector=self.injector, **gather_kw)

    def _gather_ft(self, ctx, indices, **gather_kw):
        """``_gather`` under the fault-tolerance envelope.

        With no injector this IS ``_gather`` (one ``is None`` test).  With
        one, the gather runs under retry; when retries exhaust (or the
        policy is fail-fast) the recovery depends on the faulted site:

        * ``kernel_gather`` — reroute to the table gather (``use_kernel``
          off).  Numerically bit-identical by the kernel-parity contract,
          so the batch is NOT degraded; only ``kernel_fallbacks`` counts.
        * ``host_fetch`` with ``degraded_mode`` — serve cache-only: hit
          rows real, miss rows zero, batch marked degraded
          (:meth:`FeatureStore.gather_cache_only`).
        * otherwise — propagate.
        """
        if self.injector is None:
            return self._gather(ctx, indices, **gather_kw)
        try:
            return self._with_retry(
                ctx, "host_fetch", lambda: self._gather(ctx, indices, **gather_kw)
            )
        except (InjectedFault, RetryExhausted, StageTimeout) as err:
            root = err.last if isinstance(err, RetryExhausted) else err
            site = getattr(root, "site", None)
            if site == "kernel_gather":
                self.kernel_fallbacks += 1
                if self.tracer.enabled:
                    self.tracer.complete(
                        "kernel-fallback",
                        lane="faults",
                        ts_us=self.tracer.now_us(),
                        dur_us=0.0,
                        args={"site": site},
                    )
                fallback_kw = dict(gather_kw)
                fallback_kw["use_kernel"] = False
                fallback_kw.pop("row_block", None)
                return self._gather(ctx, indices, **fallback_kw)
            if site == "host_fetch" and self.degraded_mode:
                self._mark_degraded(ctx)
                return self._gather_cache_only(ctx, indices)
            raise

    def _gather_cache_only(self, ctx, indices):
        """Degraded-mode gather: cached rows only (overridable hook)."""
        del ctx
        return self.pipe.caches.store.gather_cache_only(indices)

    def prefetch_stage(self, ctx):
        """Stage the *missed* host rows for this batch onto the device.

        Sits between ``sample`` and ``feature``: with ``depth > 1`` this
        runs for batch ``i+1`` while batch ``i``'s GNN forward is still in
        flight, so the host→device copy of the miss rows hides behind
        compute — the transfer-inefficiency DCI targets on the miss path.
        The feature stage then reads misses from the staged buffer; the
        hit mask (and all accounting) still comes from ``position_map``,
        so hit/miss counts are bit-identical with prefetch on or off.
        Under ``dedup`` only the batch's DISTINCT missed rows are staged —
        the gather consuming the pack runs over the unique bucket.  The
        device→host pull of the ids is the ``prefetch:pull`` span; the
        store's staging spans follow it (:meth:`FeatureStore.prefetch_misses`)."""
        nu = None
        with self.tracer.span("prefetch:pull", **self._trace_kw(ctx)):
            if self.dedup:
                _, nu, _, uids = self._dedup_view(ctx)
                nodes = np.asarray(uids)
            else:
                nodes = np.asarray(ctx.outputs["sample"][0].input_nodes)
        stage = lambda: self._prefetch(ctx, nodes, num_live=nu)  # noqa: E731
        if self.injector is None:
            staged = stage()
        else:
            try:
                staged = self._with_retry(ctx, "prefetch", stage)
            except (InjectedFault, RetryExhausted, StageTimeout) as err:
                root = err.last if isinstance(err, RetryExhausted) else err
                if getattr(root, "site", None) != "prefetch" or not self.degraded_mode:
                    raise
                # Prefetch down: skip staging and let the feature stage
                # gather misses over the ordinary host path.  Outputs and
                # hit accounting are bit-identical (prefetch only moves
                # bytes early), so the batch is NOT marked degraded.
                return None
        self.prefetched_rows += staged.num_miss
        self.staged_rows += staged.staged_rows
        self.pack_buffer_allocs += staged.pack_buffer_allocs
        return staged

    def feature(self, ctx):
        block = ctx.outputs["sample"][0]
        gather_kw = dict(
            use_kernel=self.use_kernel,
            gather_buffers=self.gather_buffers,
            prefetched=ctx.outputs.get("prefetch"),
        )
        if self.dedup:
            # Gather each distinct row once (sorted ids → the row-block
            # kernel's contiguous runs when the kernel route is on); the
            # per-visit hit mask is the unique mask expanded through the
            # inverse map, so every count downstream is bit-identical to
            # the duplicate-carrying gather.
            dd, nu, bucket, uids = self._dedup_view(ctx)
            feats_u, hit_u = self._gather_ft(
                ctx, uids, row_block=ROW_BLOCK if self.use_kernel else None, **gather_kw
            )
            hit = hit_u[dd.inverse]
            self.unique_rows += nu
            self.gathered_rows += bucket
            return feats_u, hit, jnp.sum(hit), hit_u
        self.gathered_rows += int(block.input_nodes.shape[0])
        if self.pipe.reuse_prev_batch and self._prev_feats is not None:
            nodes = np.asarray(block.input_nodes)
            pos = self._prev_map[nodes]
            hit_np = pos >= 0
            reused = self._prev_feats[jnp.asarray(np.maximum(pos, 0))]
            fresh, _ = self._gather_ft(ctx, block.input_nodes, **gather_kw)
            feats = jnp.where(jnp.asarray(hit_np)[:, None], reused, fresh)
            hit = jnp.asarray(hit_np)
        else:
            feats, hit = self._gather_ft(ctx, block.input_nodes, **gather_kw)
        if self.pipe.reuse_prev_batch:
            # The *next* batch's gather reads this state, so it must be
            # updated here rather than at retire time — with depth > 1
            # batch i retires only after batch i+1 has dispatched.
            if self._prev_nodes is not None:
                self._prev_map[self._prev_nodes] = -1
            self._prev_nodes = np.asarray(block.input_nodes)
            self._prev_map[self._prev_nodes] = np.arange(len(self._prev_nodes))
            self._prev_feats = feats
        return feats, hit, jnp.sum(hit)

    def compute(self, ctx):
        feats = ctx.outputs["feature"][0]
        # Read the inverse off the resolved dedup view (not the raw block):
        # the sharded runtime re-homes it onto the assembling device there,
        # and for the base path the view holds the block's inverse as-is.
        inverse = self._dedup_view(ctx)[0].inverse if self.dedup else None
        return gnn_models.forward(
            self.params, feats, model=self.model, fanouts=self.fanouts, inverse_index=inverse
        )

    def record(self, ctx) -> None:
        """Host-side accounting; runs per batch, in order, after the batch's
        stage outputs (incl. the stat scalars) are ready, so the int()
        conversions only pay a tiny device→host transfer.  Each pull is a
        span: ``sync:stats``, ``sync:telemetry`` (refresh only) and
        ``sync:logits``."""
        block, bh, bt = ctx.outputs["sample"]
        feature_out = ctx.outputs["feature"]
        hit, hsum = feature_out[1], feature_out[2]
        trace_kw = self._trace_kw(ctx)
        with self.tracer.span("sync:stats", **trace_kw):
            bh, bt, hsum, lookups = int(bh), int(bt), int(hsum), int(hit.shape[0])
        self.adj_hits += bh
        self.adj_lookups += bt
        self.feat_hits += hsum
        self.feat_lookups += lookups
        self.attn_edges += gnn_models.attn_edges(
            self.params, self.model, self.fanouts, np.shape(ctx.payload)[0]
        )
        per_epoch = self.epoch_counters.setdefault(ctx.epoch, [0, 0, 0, 0, 0])
        per_epoch[0] += bh
        per_epoch[1] += bt
        per_epoch[2] += hsum
        per_epoch[3] += lookups
        per_epoch[4] += 1
        if self.telemetry is not None:
            with self.tracer.span("sync:telemetry", **trace_kw):
                if self.dedup:
                    # Scatter once per unique node, weighted by its visit
                    # multiplicity — counters come out bit-identical to the
                    # per-visit form (a node's hit bit is the same for every
                    # visit within a batch).
                    dd, nu, _, uids = self._dedup_view(ctx)
                    mult = np.bincount(np.asarray(dd.inverse), minlength=nu)[:nu]
                    self.telemetry.observe_batch(
                        np.asarray(uids)[:nu],
                        np.asarray(feature_out[3])[:nu],
                        block.edge_slots,
                        multiplicities=mult,
                    )
                else:
                    self.telemetry.observe_batch(block.input_nodes, hit, block.edge_slots)
        if self.outputs is not None:
            with self.tracer.span("sync:logits", **trace_kw):
                self.outputs.append(np.asarray(ctx.outputs["compute"]))

    def epoch_hit_rates(self) -> dict[int, dict]:
        """Per-epoch hit-rate summary (one entry per cache epoch served)."""
        return summarize_epoch_counters(self.epoch_counters)


def stream_stages(runtime_of, *, prefetch: bool = False) -> list[Stage]:
    """The sample → [prefetch] → feature → compute pipeline over
    :class:`StreamRuntime`s.

    ``runtime_of(ctx)`` resolves the runtime a batch belongs to: the engine
    passes a constant (one stream), the serving layer reads it off
    ``ctx.stream``.  Sync values mirror what each stage leaves in flight —
    they are what the serial clock blocks on and the overlap clock drains.

    ``prefetch=True`` inserts the miss-row staging stage between sample
    and feature (see :meth:`StreamRuntime.prefetch_stage`); the executor
    drops the ``None`` placeholder when it is off, so the stage list —
    and with it the depth=1 serial timing semantics — is unchanged by
    default.
    """
    return [
        Stage(
            "sample",
            lambda c: runtime_of(c).sample(c),
            lambda c: (c.outputs["sample"][0].frontiers[-1], c.outputs["sample"][1]),
        ),
        Stage(
            "prefetch",
            lambda c: runtime_of(c).prefetch_stage(c),
            lambda c: c.outputs["prefetch"],
        )
        if prefetch
        else None,
        Stage(
            "feature",
            lambda c: runtime_of(c).feature(c),
            lambda c: (c.outputs["feature"][0], c.outputs["feature"][2]),
        ),
        Stage("compute", lambda c: runtime_of(c).compute(c), lambda c: c.outputs["compute"]),
    ]


def summarize_epoch_counters(counters: dict[int, list[int]]) -> dict[int, dict]:
    """Per-epoch hit-rate summary from ``[adj_hits, adj_lookups, feat_hits,
    feat_lookups, batches]`` counter lists (the StreamRuntime layout) —
    shared by the per-stream and the serve-aggregate reports."""
    return {
        epoch: {
            "batches": c[4],
            "adj_hit_rate": round(c[0] / max(c[1], 1), 4),
            "feat_hit_rate": round(c[2] / max(c[3], 1), 4),
        }
        for epoch, c in sorted(counters.items())
    }


# Below this, a measured stage lap is indistinguishable from clock noise —
# a cache-hit-everything first batch can legitimately measure ~0 prep, and
# a ratio against a ~0 denominator would pin the derived depth at the cap.
DEGENERATE_LAP_SECONDS = 1e-6


def auto_pipeline_depth(prep_seconds: float, compute_seconds: float, *, max_depth: int = 4) -> int:
    """Pick an executor window from the measured compute:prep ratio.

    The pipeline hides batch *i+1*'s preparation (sample + gather) behind
    batch *i*'s forward, so ``depth=2`` already wins everything when
    compute >= prep.  When prep dominates, a deeper window keeps the
    device fed across several short forwards — roughly one extra slot per
    compute-sized chunk of prep — saturating at ``max_depth`` (beyond
    that the run is prep-bound and more slots only hold memory).

    Degenerate probes: a ~zero PREP lap means there is nothing to hide
    behind compute — return 1 (serial; callers treat it as "re-derive on
    the next window" rather than caching it).  A ~zero COMPUTE lap with
    real prep used to divide by ~0 and pin the depth at the cap; it now
    returns the 2 a compute-free measurement actually supports.
    """
    if prep_seconds <= DEGENERATE_LAP_SECONDS:
        return 1
    if compute_seconds <= DEGENERATE_LAP_SECONDS:
        return 2
    return max(2, min(max_depth, 1 + round(prep_seconds / compute_seconds)))


class GNNInferenceEngine:
    def __init__(
        self,
        dataset: SyntheticGraphDataset,
        *,
        model: str = "graphsage",
        fanouts: tuple[int, ...] = (15, 10, 5),
        batch_size: int = 1024,
        seed: int = 0,
        params=None,
        pipeline_depth: int | str = 1,
    ):
        self.dataset = dataset
        self.model = model
        self.fanouts = tuple(fanouts)
        self.batch_size = batch_size
        self.seed = seed
        self.pipeline_depth = pipeline_depth
        key = jax.random.PRNGKey(seed)
        self.params = params if params is not None else gnn_models.init_params(
            key, model, dataset.spec.feat_dim, dataset.spec.num_classes
        )
        self.pipeline: PreparedPipeline | None = None
        self.last_outputs: list[np.ndarray] | None = None
        self._auto_depth: int | None = None  # resolved "auto" depth, cached

    # ------------------------------------------------------------ prepare
    def prepare(
        self,
        policy: str,
        *,
        config: EngineConfig | None = None,
        total_cache_bytes: int = 0,
        n_presample: int = 8,
        pipeline_depth: int = 1,
        stream_seeds: list[int] | None = None,
        prefetch: bool | None = None,
        use_kernel: bool | None = None,
        gather_buffers: int | None = None,
        dedup: bool | None = None,
    ):
        # Presampling defaults to serial (depth=1): its per-stage times feed
        # Eq. 1, and the paper's split assumes fully synchronized stages.
        # Visit counts are depth-invariant, so overlapped presampling only
        # shifts the measured sample:feature ratio toward dispatch cost.
        # ``stream_seeds`` profiles the union workload of several request
        # streams (multi-stream serving) at the same total presample budget.
        # ``config`` carries the gather knobs recorded on the prepared
        # pipeline as the defaults for every run (and every serving stream)
        # against it; the loose keyword forms are deprecated (coalesce).
        cfg = coalesce(
            config,
            _context="GNNInferenceEngine.prepare",
            prefetch=prefetch,
            use_kernel=use_kernel,
            gather_buffers=gather_buffers,
            dedup=dedup,
        )
        self.pipeline = prepare(
            policy,
            self.dataset,
            total_cache_bytes=total_cache_bytes,
            fanouts=self.fanouts,
            batch_size=self.batch_size,
            n_presample=n_presample,
            seed=self.seed,
            pipeline_depth=pipeline_depth,
            stream_seeds=stream_seeds,
            prefetch=bool(cfg.prefetch),
            use_kernel=bool(cfg.use_kernel),
            gather_buffers=2 if cfg.gather_buffers is None else cfg.gather_buffers,
            dedup=bool(cfg.dedup),
        )
        return self.pipeline

    # ---------------------------------------------------------------- run
    def _batches(self, max_batches: int | None) -> list[np.ndarray]:
        test = self.dataset.test_idx
        nb = max(len(test) // self.batch_size, 1)
        need = nb * self.batch_size
        if len(test) < need:  # tiny datasets: cycle to fill one batch
            reps = -(-need // max(len(test), 1))
            test = np.tile(test, reps)
        arr = test[:need].reshape(nb, self.batch_size)
        order = (
            self.pipeline.batch_order
            if self.pipeline is not None and self.pipeline.batch_order is not None
            else np.arange(nb)
        )
        if max_batches is not None:
            order = order[:max_batches]
        return [arr[i] for i in order]

    def warmup(
        self,
        seeds: np.ndarray,
        *,
        prefetch: bool | None = None,
        use_kernel: bool | None = None,
        gather_buffers: int | None = None,
        dedup: bool | None = None,
    ) -> None:
        """Trigger compilation outside any timed region (cache array shapes
        differ per policy/budget, so each prepared pipeline compiles once —
        shared by every stream that serves against it).  The gather is
        warmed with the same execution knobs the run will use (prefetch
        scatter / kernel route / dedup bucket compile to different
        programs).

        Under ``dedup`` the gather and forward programs specialize on the
        per-batch pow2 unique bucket.  Warming the probe batch's bucket
        covers every batch sharing it (unique counts are stable within a
        workload, so that is usually all of them); a batch landing in a
        different bucket pays one in-run compile — the same exposure as
        any first-of-a-shape dispatch.
        """
        if self.pipeline is None:
            raise RuntimeError("call prepare() first")
        pipe = self.pipeline
        prefetch = pipe.prefetch if prefetch is None else prefetch
        use_kernel = pipe.use_kernel if use_kernel is None else use_kernel
        gather_buffers = pipe.gather_buffers if gather_buffers is None else gather_buffers
        dedup = (pipe.dedup if dedup is None else dedup) and not pipe.reuse_prev_batch
        dgraph, store = pipe.caches.dgraph, pipe.caches.store
        wblock = sample_blocks(
            jax.random.PRNGKey(self.seed + 1), dgraph, jnp.asarray(seeds), self.fanouts,
            dedup=dedup,
            dedup_pad_id=store.pad_node_id() if dedup else None,
        )
        s = int(wblock.input_nodes.shape[0])
        if dedup:
            nu = int(wblock.dedup.num_unique)
            bucket = pow2_bucket(nu, s)
            gather_ids = wblock.dedup.unique_ids[:bucket]
            inverse = wblock.dedup.inverse
            row_block = ROW_BLOCK if use_kernel else None
        else:
            nu = None
            gather_ids, inverse, row_block = wblock.input_nodes, None, None
        # num_live mirrors the serve path's prefetch stage: only the live
        # prefix can stage misses, so warmup packs the same bucket sizes
        # the run will (and, with the cached pad id, the tail could not
        # stage duplicate miss rows even without it).
        prefetched = (
            store.prefetch_misses(np.asarray(gather_ids), num_live=nu) if prefetch else None
        )
        wfeats, _ = store.gather(
            gather_ids,
            use_kernel=use_kernel,
            gather_buffers=gather_buffers,
            prefetched=prefetched,
            row_block=row_block,
        )
        if prefetch:
            # The miss count varies per batch, so the staged pack's padded
            # bucket size — and with it the consuming gather program —
            # varies too.  Warm every possible bucket (O(log S) of them)
            # with synthetic all-pad packs, so no batch's first-of-a-bucket
            # gather compiles inside a timed run.
            from repro.graph.features import PrefetchedMisses

            g = int(gather_ids.shape[0])
            bucket = 1
            while bucket <= g:
                synth = PrefetchedMisses(
                    rows=jnp.zeros((min(bucket, g), store.feat_dim), store.host_table.dtype),
                    idx=jnp.full((min(bucket, g),), g, jnp.int32),
                    pack_pos=jnp.zeros((g,), jnp.int32),
                    num_miss=0,
                )
                store.gather(
                    gather_ids,
                    use_kernel=use_kernel,
                    gather_buffers=gather_buffers,
                    prefetched=synth,
                    row_block=row_block,
                )
                bucket <<= 1
        jax.block_until_ready(
            gnn_models.forward(
                self.params, wfeats, model=self.model, fanouts=self.fanouts,
                inverse_index=inverse,
            )
        )

    def warmup_refresh_growth(
        self,
        seeds: np.ndarray,
        *,
        use_kernel: bool | None = None,
        gather_buffers: int | None = None,
        dedup: bool | None = None,
    ) -> None:
        """Pre-compile the gather at the hot table's NEXT growth bucket.

        ``refresh_feature_cache`` grows the device hot table by doubling
        (capped at the node count), and the gather program specializes on
        the table's physical row count — so the first batch after a
        growing refresh would otherwise pay that compile *inside* the
        serve loop, exactly the pause a delta re-fill exists to avoid.
        This warms the post-growth program off the serve path against a
        zero-filled ghost table at the doubled size: same position map,
        same index shapes, same route (kernel/prefetched knobs), so the
        compiled program is the one the post-refresh store dispatches.
        A no-op when the table cannot grow (already at the node count) or
        the policy built no refreshable caches.
        """
        if self.pipeline is None:
            raise RuntimeError("call prepare() first")
        pipe = self.pipeline
        if not pipe.caches.refreshable:
            return
        from repro.graph.features import FeatureStore

        store = pipe.caches.store
        use_kernel = pipe.use_kernel if use_kernel is None else use_kernel
        gather_buffers = pipe.gather_buffers if gather_buffers is None else gather_buffers
        dedup = (pipe.dedup if dedup is None else dedup) and not pipe.reuse_prev_batch
        physical = int(store.hot_table.shape[0])
        grow_to = min(2 * physical, store.num_nodes)
        if grow_to <= physical:
            return
        ghost = FeatureStore(
            host_table=store.host_table,
            hot_table=jnp.zeros((grow_to, store.feat_dim), store.hot_table.dtype),
            position_map=store.position_map,
        )
        object.__setattr__(ghost, "_host_np", store.host_np())
        object.__setattr__(ghost, "_position_np", store.position_np())
        if use_kernel:
            object.__setattr__(ghost, "_host_lanes", store.kernel_tables()[1])
        wblock = sample_blocks(
            jax.random.PRNGKey(self.seed + 1), pipe.caches.dgraph, jnp.asarray(seeds),
            self.fanouts, dedup=dedup,
            dedup_pad_id=store.pad_node_id() if dedup else None,
        )
        if dedup:
            bucket = pow2_bucket(int(wblock.dedup.num_unique), int(wblock.input_nodes.shape[0]))
            gather_ids = wblock.dedup.unique_ids[:bucket]
            row_block = ROW_BLOCK if use_kernel else None
        else:
            gather_ids, row_block = wblock.input_nodes, None
        feats, _ = ghost.gather(
            gather_ids, use_kernel=use_kernel, gather_buffers=gather_buffers,
            row_block=row_block,
        )
        jax.block_until_ready(feats)

    # ------------------------------------------------------ adaptive depth
    def resolve_pipeline_depth(self, depth=None, *, seeds=None) -> int:
        """Resolve the ``pipeline_depth`` knob, including ``"auto"``.

        ``"auto"`` probes ONE serial batch against the prepared pipeline
        (after an untimed warmup, so compilation is excluded) and derives
        the window from the measured compute:prep ratio — the same
        decomposition bench_breakdown's serial rows report.  The probe
        uses its own RNG stream, so the run it sizes is unaffected; the
        result is cached on the engine — EXCEPT a degenerate probe (a
        ~zero prep lap, e.g. a cache-hit-everything first batch), which
        resolves to serial depth 1 for this run but is NOT cached, so the
        next resolve (or a refresh window) re-derives from a real
        measurement."""
        if depth is None:
            depth = self.pipeline_depth
        if depth != "auto":
            return int(depth)
        if self._auto_depth is None:
            if self.pipeline is None:
                raise RuntimeError("call prepare() before resolving pipeline_depth='auto'")
            if seeds is None:
                seeds = self._batches(1)[0]
            sample_s, feature_s, compute_s = self._probe_stage_seconds(np.asarray(seeds))
            derived = auto_pipeline_depth(sample_s + feature_s, compute_s)
            if derived < 2:
                return 1  # degenerate probe: don't cache, re-derive next time
            self._auto_depth = derived
        return self._auto_depth

    def _probe_stage_seconds(self, seeds: np.ndarray) -> tuple[float, float, float]:
        """Fully synchronized per-stage seconds for one batch (best of 2)."""
        self.warmup(seeds)
        pipe = self.pipeline
        best = None
        for rep in range(2):
            key = jax.random.PRNGKey(self.seed + 1000 + rep)
            t0 = time.perf_counter()
            block = sample_blocks(key, pipe.caches.dgraph, jnp.asarray(seeds), self.fanouts)
            jax.block_until_ready(block.frontiers[-1])
            t1 = time.perf_counter()
            feats, _ = pipe.caches.store.gather(block.input_nodes)
            jax.block_until_ready(feats)
            t2 = time.perf_counter()
            out = gnn_models.forward(self.params, feats, model=self.model, fanouts=self.fanouts)
            jax.block_until_ready(out)
            t3 = time.perf_counter()
            lap = (t1 - t0, t2 - t1, t3 - t2)
            best = lap if best is None or sum(lap) < sum(best) else best
        return best

    def run(
        self,
        *,
        config: EngineConfig | None = None,
        max_batches: int | None = None,
        warmup: bool = True,
        pipeline_depth: int | None = None,
        collect_outputs: bool = False,
        batches: list[np.ndarray] | None = None,
        prefetch: bool | None = None,
        use_kernel: bool | None = None,
        gather_buffers: int | None = None,
        dedup: bool | None = None,
        refresh=None,
        tracer=None,
        metrics=None,
        injector=None,
        retry_policy=None,
        degraded_mode: bool = False,
    ):
        """Run inference over the dataset's test batches (or explicit seed
        ``batches``) and return the stage-time / hit-rate report.

        ``tracer``/``metrics`` are live observability handles
        (core/trace.py) — keyword-only and not part of ``EngineConfig``
        (which stays a frozen JSON-safe value object).  A
        :class:`~repro.core.trace.Tracer` records the run's timeline
        (slot-lane batch/stage spans, refresh epochs); a
        :class:`~repro.core.trace.MetricsRegistry` is folded with the
        run's aggregate outcomes and snapshotted onto ``report.metrics``.
        Both default to off with effectively zero cost, and neither
        perturbs outputs (bit-for-bit equivalence-tested).

        ``config`` is the one knob object (:class:`~repro.core.config.
        EngineConfig`): mode, executor window, the four gather knobs, the
        layer-wise chunk size and the refresh trigger.  The loose keyword
        forms below remain as a deprecated one-release shim — any passed
        value merges over ``config`` via :func:`~repro.core.config.
        coalesce`, bit-for-bit equivalent to passing the config directly
        (tests/test_config.py).  Unset knobs default from the prepared
        pipeline; outputs and hit accounting are identical under every
        knob combination (equivalence-tested), only where the miss bytes
        move (and therefore wall clock) changes.

        ``config.mode="layerwise"`` dispatches to the chunked full-graph
        executor (:func:`~repro.runtime.layerwise.run_layerwise`) —
        scoring EVERY node in node-range chunks, layer by layer, with the
        intermediate embeddings spilled host-side behind their own cache —
        and returns its :class:`~repro.runtime.layerwise.LayerwiseReport`
        instead (``batches``/``max_batches``/``refresh`` do not apply).

        ``batches`` overrides the dataset-derived schedule (and RAIN's
        ``batch_order``) — the serving layer and the equivalence tests use
        it to run an exact per-stream batch list.

        ``pipeline_depth`` additionally accepts ``"auto"`` (derive the
        window from a measured compute:prep probe, see
        :meth:`resolve_pipeline_depth`; in layer-wise mode ``"auto"``
        resolves to 2 — chunk prep is pure gather, one overlap slot hides
        it).  ``refresh`` takes a
        :class:`~repro.runtime.cache_refresh.RefreshConfig` (or set the
        config's ``refresh_mode`` fields): an interval mode re-allocates
        and delta re-fills the caches every N retired batches from live
        telemetry.  Outputs are bit-identical with refresh on or off
        (refreshes move bytes, not values); hit accounting then comes per
        epoch via ``report.epoch_hits``.  With BOTH ``"auto"`` depth and
        refresh enabled, each refresh re-derives the window from the
        refreshed stage laps and applies it to the live executor (the
        warmup-time probe only seeds the initial depth)."""
        if self.pipeline is None:
            raise RuntimeError("call prepare() first")
        pipe = self.pipeline
        cfg = coalesce(
            config,
            _context="GNNInferenceEngine.run",
            pipeline_depth=pipeline_depth,
            prefetch=prefetch,
            use_kernel=use_kernel,
            gather_buffers=gather_buffers,
            dedup=dedup,
        )
        if refresh is None:
            refresh = cfg.refresh_config()
        requested_depth = (
            self.pipeline_depth if cfg.pipeline_depth is None else cfg.pipeline_depth
        )
        if cfg.mode == "layerwise":
            from repro.runtime.layerwise import run_layerwise

            depth = 2 if requested_depth == "auto" else int(requested_depth)
            report = run_layerwise(
                self.dataset,
                pipe,
                self.params,
                model=self.model,
                config=cfg.resolved(pipe, pipeline_depth=depth),
                tracer=tracer,
                metrics=metrics,
            )
            self.last_outputs = [report.outputs]
            return report
        tracer = resolve_tracer(tracer)
        if batches is None:
            batches = self._batches(max_batches)
        depth = self.resolve_pipeline_depth(
            requested_depth, seeds=batches[0] if batches else None
        )
        if warmup:
            self.warmup(
                batches[0],
                prefetch=cfg.prefetch,
                use_kernel=cfg.use_kernel,
                gather_buffers=cfg.gather_buffers,
                dedup=cfg.dedup,
            )

        # All cross-batch state (RNG stream, RAIN's reuse map, counters)
        # lives in the StreamRuntime; stage methods run in batch order at
        # any depth, preserving the serial key sequence and reuse ordering.
        rt = StreamRuntime(
            pipe,
            self.params,
            model=self.model,
            fanouts=self.fanouts,
            num_nodes=self.dataset.num_nodes,
            key=jax.random.PRNGKey(self.seed + 1),
            collect_outputs=collect_outputs,
            prefetch=cfg.prefetch,
            use_kernel=cfg.use_kernel,
            gather_buffers=cfg.gather_buffers,
            dedup=cfg.dedup,
            injector=injector,
            retry_policy=retry_policy,
            degraded_mode=degraded_mode,
        )
        rt.tracer = tracer
        clock = StageClock(overlap=depth > 1)
        manager = None
        if refresh is not None and refresh.enabled:
            from repro.runtime.cache_refresh import CacheRefreshManager

            manager = CacheRefreshManager(
                pipe,
                self.dataset,
                fanouts=self.fanouts,
                batch_size=self.batch_size,
                config=refresh,
            )
            manager.register_clock(clock, key=0)
            manager.tracer = tracer
            manager.injector = injector
            rt.telemetry = manager.telemetry_for(0)
            if warmup:
                # Refresh-aware warmup: a growing delta re-fill would
                # otherwise compile its first post-growth gather inside
                # the serve loop.
                self.warmup_refresh_growth(
                    batches[0], use_kernel=use_kernel,
                    gather_buffers=gather_buffers, dedup=dedup,
                )
        auto_depth = requested_depth == "auto" and manager is not None

        def on_retire(ctx):
            # Retire runs between batch dispatches, so an interval refresh
            # lands here: in-flight batches keep the old epoch's arrays,
            # the next dispatch reads the new epoch.
            rt.record(ctx)
            if manager is not None:
                event = manager.note_retired()
                if event is not None and auto_depth and manager.suggested_depth:
                    # Refresh-aware "auto": size the window from the
                    # refreshed stage laps instead of the warmup probe.
                    # The executor re-reads ``depth`` between batches, so
                    # the change applies at the next dispatch; depth never
                    # drops below 2, keeping the clock's overlap semantics.
                    executor.depth = manager.suggested_depth

        executor = PipelinedExecutor(
            stream_stages(lambda c: rt, prefetch=rt.prefetch),
            depth=depth,
            clock=clock,
            on_retire=on_retire,
            tracer=tracer,
        )
        executor.run(batches)
        self.last_outputs = rt.outputs

        # The config echoed by the report is the RESOLVED one — every knob
        # read back off the runtime that executed (rt.dedup already folds
        # in RAIN's reuse exclusion), so the echo cannot drift.
        resolved_cfg = cfg.resolved(pipe, pipeline_depth=depth).replace(
            prefetch=rt.prefetch,
            use_kernel=rt.use_kernel,
            gather_buffers=rt.gather_buffers,
            dedup=rt.dedup,
        )
        report = InferenceReport(
            policy=pipe.name,
            num_batches=len(batches),
            sample_seconds=clock.total("sample"),
            feature_seconds=clock.total("feature"),
            compute_seconds=clock.total("compute"),
            prep_seconds=pipe.prep_seconds,
            adj_hits=rt.adj_hits,
            adj_lookups=rt.adj_lookups,
            feat_hits=rt.feat_hits,
            feat_lookups=rt.feat_lookups,
            feat_row_bytes=self.dataset.feature_nbytes_per_row(),
            pipeline_depth=depth,
            prefetch=rt.prefetch,
            prefetch_seconds=clock.total("prefetch"),
            prefetched_rows=rt.prefetched_rows,
            staged_rows=rt.staged_rows,
            pack_buffer_allocs=rt.pack_buffer_allocs,
            dedup=rt.dedup,
            unique_rows=rt.unique_rows,
            gathered_rows=rt.gathered_rows,
            attn_edges=rt.attn_edges,
            refresh_events=list(manager.events) if manager is not None else [],
            epoch_hits=rt.epoch_hit_rates() if manager is not None else None,
            config=resolved_cfg,
            kernel_fallbacks=rt.kernel_fallbacks,
            degraded_batches=rt.degraded_batches,
        )
        if metrics is not None:
            metrics.counter("batches_total", policy=pipe.name).inc(report.num_batches)
            metrics.gauge("feat_hit_rate", policy=pipe.name).set(report.feat_hit_rate)
            metrics.gauge("adj_hit_rate", policy=pipe.name).set(report.adj_hit_rate)
            for name in ("sample", "prefetch", "feature", "compute"):
                metrics.gauge("stage_seconds", policy=pipe.name, stage=name).set(
                    clock.total(name)
                )
            if report.epoch_hits:
                for epoch, rates in report.epoch_hits.items():
                    metrics.gauge("feat_hit_rate", policy=pipe.name, epoch=epoch).set(
                        rates["feat_hit_rate"]
                    )
            report.metrics = metrics.snapshot()
        return report
