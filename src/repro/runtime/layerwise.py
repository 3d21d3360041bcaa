"""Layer-wise full-graph inference — the second execution mode beside sampling.

Sampling amortizes per-seed neighborhood explosion; once the whole graph
needs scoring, every node's features are re-gathered once per seed batch
that touches them.  Layer-wise execution inverts the loop: run layer *k*
over ALL nodes before starting layer *k+1*, walking the node range in
fixed-size chunks.  Each node's input rows are then read exactly
``1 + out_degree`` times per layer — once as a chunk member, once per
out-edge — a bound no sampled schedule meets, at the price of
materializing every intermediate layer.

The executor reuses the whole DCI stack:

  - chunks flow through the staged :class:`~repro.runtime.pipeline.
    PipelinedExecutor` (chunk *i+1*'s gather overlaps chunk *i*'s layer
    compute at ``depth > 1``, same clock semantics as the sampled engine);
  - layer-0 input rows come from the feature :class:`~repro.graph.
    features.FeatureStore` (optionally delta re-filled for the layer-wise
    access pattern, which is EXACT — ``1 + bincount(row_index)`` — where
    presampling could only estimate);
  - layer-*k* outputs spill to a host-side table and come back as layer
    *k+1* inputs through a per-layer EMBEDDING cache
    (:func:`~repro.graph.features.build_embedding_cache`) — the same
    allocation/fill machinery, position-map gather, prefetch staging and
    row-block kernel route as the input features;
  - the budget splits between the two caches by Eq. 1 over probed chunk
    gather laps (:func:`~repro.core.allocation.allocate_layerwise_capacity`).

``dedup`` does not apply here — chunk gathers are range-structured (the
self block IS sorted-unique; neighbor lists duplicate only across
multi-edges) — and the knob is ignored.  ``pipeline_depth="auto"``
resolves to 2: chunk prep is pure gather, so one overlap slot already
hides it behind compute.
"""

from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.allocation import LayerwiseAllocation, allocate_layerwise_capacity
from repro.core.config import EngineConfig
from repro.core.trace import resolve_tracer
from repro.core.policies import PreparedPipeline
from repro.graph.datasets import SyntheticGraphDataset
from repro.graph.features import (
    FeatureStore,
    build_embedding_cache,
    plain_feature_store,
    refresh_feature_cache,
)
from repro.graph.sampling import pow2_bucket
from repro.kernels.cached_gather.kernel import ROW_BLOCK
from repro.models import gnn as gnn_models
from repro.runtime.gnn_engine import modeled_transfer_seconds
from repro.runtime.pipeline import PipelinedExecutor, Stage
from repro.utils.timing import StageClock

__all__ = [
    "ChunkPlan",
    "LayerwiseReport",
    "layerwise_access_counts",
    "plan_chunks",
    "run_layerwise",
]


def layerwise_access_counts(graph) -> np.ndarray:
    """Exact per-node reads per layer: once as a chunk member plus once per
    out-edge (each appearance in ``row_index`` is one neighbor gather).
    The same counts govern the layer-0 feature cache and every
    intermediate embedding cache — the access pattern is the CSC itself,
    not a sampled estimate."""
    return 1 + np.bincount(graph.row_index, minlength=graph.num_nodes).astype(np.int64)


@dataclasses.dataclass(frozen=True)
class ChunkSpec:
    """One node-range chunk's layer-invariant geometry.

    The gather reads one concatenated index vector ``[self | neighbors]``:
    ``chunk_size`` self ids (range ``[lo, lo+cnt)``, tail clipped — the
    clipped rows are dropped at spill) followed by the range's in-edge
    sources padded to a pow2 bucket, so chunks sharing a bucket share
    compiled gather/forward programs (O(log E) distinct shapes).  Pad
    positions are marked in ``pad_mask`` and re-pointed per layer at that
    layer's cached pad id; their gathered rows land in the dropped extra
    segment / clipped tail and are never read, and the ``live`` mask keeps
    them out of the hit accounting either way."""

    lo: int
    cnt: int  # live chunk nodes (== chunk_size except the last chunk)
    n_edges: int  # live in-edges of the range
    base_ids: np.ndarray  # int32[chunk_size + bucket], pads = 0
    pad_mask: np.ndarray  # bool, True at pad positions of base_ids
    seg_ids: jax.Array  # int32[bucket] — edge → local dst, pads → chunk_size
    degrees: jax.Array  # f32[chunk_size] — true in-degrees, pad tail 0
    live: jax.Array  # bool[chunk_size + bucket] — non-pad positions


@dataclasses.dataclass(frozen=True)
class ChunkPlan:
    """The full chunk schedule, built ONCE and shared by every layer (the
    geometry depends only on the CSC and the chunk size)."""

    chunk_size: int
    chunks: list[ChunkSpec]

    @property
    def num_chunks(self) -> int:
        return len(self.chunks)


def plan_chunks(graph, chunk_size: int) -> ChunkPlan:
    n = graph.num_nodes
    col_ptr = np.asarray(graph.col_ptr)
    row_index = np.asarray(graph.row_index)
    deg = np.diff(col_ptr)
    chunks: list[ChunkSpec] = []
    for lo in range(0, n, chunk_size):
        hi = min(lo + chunk_size, n)
        cnt = hi - lo
        e0, e1 = int(col_ptr[lo]), int(col_ptr[hi])
        n_edges = e1 - e0
        bucket = pow2_bucket(n_edges)
        ids = np.zeros(chunk_size + bucket, np.int32)
        # Self block: the range itself; the tail past ``cnt`` is padding.
        ids[:chunk_size] = np.minimum(np.arange(lo, lo + chunk_size), n - 1)
        ids[chunk_size : chunk_size + n_edges] = row_index[e0:e1]
        pad_mask = np.ones(chunk_size + bucket, bool)
        pad_mask[:cnt] = False
        pad_mask[chunk_size : chunk_size + n_edges] = False
        seg = np.full(bucket, chunk_size, np.int32)  # pads → the dropped segment
        seg[:n_edges] = np.repeat(
            np.arange(cnt, dtype=np.int32), deg[lo:hi].astype(np.int64)
        )
        degrees = np.zeros(chunk_size, np.float32)
        degrees[:cnt] = deg[lo:hi]
        chunks.append(
            ChunkSpec(
                lo=lo,
                cnt=cnt,
                n_edges=n_edges,
                base_ids=ids,
                pad_mask=pad_mask,
                seg_ids=jnp.asarray(seg),
                degrees=jnp.asarray(degrees),
                live=jnp.asarray(~pad_mask),
            )
        )
    return ChunkPlan(chunk_size=chunk_size, chunks=chunks)


@dataclasses.dataclass
class LayerwiseReport:
    """Stage-time / hit-rate report for one layer-wise full-graph run —
    the mode's analogue of :class:`~repro.runtime.gnn_engine.
    InferenceReport`, with the feature accounting split by source (layer-0
    input rows vs intermediate embedding rows)."""

    policy: str
    num_nodes: int
    num_layers: int
    chunk_size: int
    num_chunks: int
    num_edges: int
    gather_seconds: float
    compute_seconds: float
    spill_seconds: float
    fill_seconds: float  # per-layer embedding-cache builds (mid-run)
    prep_seconds: float  # split probe + allocation + layer-0 cache re-fill
    feat_hits: int
    feat_lookups: int
    embed_hits: int
    embed_lookups: int
    feat_row_bytes: int
    embed_row_bytes: int
    pipeline_depth: int = 1
    prefetch_seconds: float = 0.0
    prefetched_rows: int = 0
    allocation: LayerwiseAllocation | None = None
    config: EngineConfig | None = None  # the resolved knobs this run used
    outputs: np.ndarray | None = dataclasses.field(default=None, repr=False)
    # MetricsRegistry.snapshot() at report time (``--metrics``); else None.
    metrics: dict | None = None

    @property
    def total_seconds(self) -> float:
        return (
            self.gather_seconds
            + self.prefetch_seconds
            + self.compute_seconds
            + self.spill_seconds
            + self.fill_seconds
        )

    @property
    def feat_hit_rate(self) -> float:
        return self.feat_hits / max(self.feat_lookups, 1)

    @property
    def embed_hit_rate(self) -> float:
        return self.embed_hits / max(self.embed_lookups, 1)

    def modeled_transfer_seconds(self) -> float:
        """Byte movement projected on the same slow/fast link pair as the
        sampled engine — the machine-independent side of the crossover
        benchmark (benchmarks/bench_layerwise.py).  Every layer moves the
        edge list once (the chunk schedule's adjacency reads are
        sequential host slices — all misses)."""
        return modeled_transfer_seconds(
            feat_lookups=self.feat_lookups,
            feat_hits=self.feat_hits,
            adj_lookups=self.num_layers * self.num_edges,
            adj_hits=0,
            feat_row_bytes=self.feat_row_bytes,
        ) + modeled_transfer_seconds(
            feat_lookups=self.embed_lookups,
            feat_hits=self.embed_hits,
            adj_lookups=0,
            adj_hits=0,
            feat_row_bytes=self.embed_row_bytes,
        )

    def summary(self) -> dict:
        out = {
            "policy": self.policy,
            "mode": "layerwise",
            "nodes": self.num_nodes,
            "layers": self.num_layers,
            "chunk_size": self.chunk_size,
            "chunks": self.num_chunks,
            "pipeline_depth": self.pipeline_depth,
            "gather_s": round(self.gather_seconds, 4),
            "prefetch_s": round(self.prefetch_seconds, 4),
            "compute_s": round(self.compute_seconds, 4),
            "spill_s": round(self.spill_seconds, 4),
            "fill_s": round(self.fill_seconds, 4),
            "total_s": round(self.total_seconds, 4),
            "prep_s": round(self.prep_seconds, 4),
            "feat_hit_rate": round(self.feat_hit_rate, 4),
            "embed_hit_rate": round(self.embed_hit_rate, 4),
            "modeled_transfer_s": round(self.modeled_transfer_seconds(), 6),
        }
        if self.config is not None:
            out["config"] = self.config.to_dict()
        if self.metrics is not None:
            out["metrics"] = self.metrics
        return out


def _probe_gather_seconds(store: FeatureStore, ids: jax.Array, reps: int = 2) -> float:
    """Best-of-``reps`` synchronized gather lap over one chunk's index set —
    the layer-wise analogue of presampling's per-stage laps (Eq. 1 input)."""
    feats, _ = store.gather(ids)  # warm the compile outside the lap
    jax.block_until_ready(feats)
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        feats, _ = store.gather(ids)
        jax.block_until_ready(feats)
        best = min(best, time.perf_counter() - t0)
    return best


def _intermediate_width(params, model: str) -> int:
    """Widest intermediate layer output — what sizes the embedding cache's
    need bound (the spill tables are [N, dims[k]] for k = 1..L-1)."""
    out_dim = gnn_models.layer_kind(model).out_dim
    widths = [out_dim(p) for p in params[:-1]]
    return max(widths) if widths else out_dim(params[-1])


def run_layerwise(
    dataset: SyntheticGraphDataset,
    pipe: PreparedPipeline,
    params,
    *,
    model: str,
    config: EngineConfig,
    tracer=None,
    metrics=None,
) -> LayerwiseReport:
    """Score EVERY node: L chained chunked layer passes over the node range.

    ``config`` must be resolved (every knob concrete — the engine's
    dispatch does this); ``config.dedup`` is ignored (see module
    docstring).  Outputs match an L-layer full-neighborhood sampled
    forward within fp tolerance (summation order differs:
    ``segment_sum`` vs the sampled reshape-reduce) —
    tests/test_layerwise.py."""
    tracer = resolve_tracer(tracer)
    graph = dataset.graph
    n = graph.num_nodes
    num_layers = len(params)
    chunk_size = min(int(config.chunk_size), n)
    depth = 2 if config.pipeline_depth == "auto" else int(config.pipeline_depth)
    use_kernel = bool(config.use_kernel)
    gather_buffers = int(config.gather_buffers)
    prefetch = bool(config.prefetch)
    row_block = ROW_BLOCK if use_kernel else None

    plan = plan_chunks(graph, chunk_size)
    access_counts = layerwise_access_counts(graph)

    # ---- Eq. 1 split between the layer-0 feature cache and the (transient,
    # one-live-at-a-time) embedding cache, from probed chunk gather laps.
    t_prep = time.perf_counter()
    total_bytes = pipe.caches.allocation.total_bytes if pipe.caches.allocation else 0
    embed_width = _intermediate_width(params, model)
    embed_row_bytes = embed_width * 4
    alloc = None
    feat_store = pipe.caches.store
    embed_bytes = 0
    if total_bytes > 0 and num_layers > 1:
        probe_ids = jnp.asarray(plan.chunks[0].base_ids)
        t_feat = _probe_gather_seconds(pipe.caches.store, probe_ids)
        ghost = plain_feature_store(np.zeros((n, embed_width), np.float32))
        t_embed = _probe_gather_seconds(ghost, probe_ids)
        alloc = allocate_layerwise_capacity(
            [t_feat],
            [t_embed],
            total_bytes,
            feat_need_bytes=dataset.features.nbytes,
            embed_need_bytes=n * embed_row_bytes,
        )
        embed_bytes = alloc.embed_bytes
        # Delta re-fill the layer-0 cache for the layer-wise access pattern
        # (exact counts) at its new share.  The pipe's own store is NOT
        # mutated — the sampled path keeps its epoch and contents.
        feat_store, _ = refresh_feature_cache(pipe.caches.store, access_counts, alloc.feat_bytes)
    elif total_bytes > 0:  # single layer: no intermediates, whole budget to feats
        feat_store, _ = refresh_feature_cache(pipe.caches.store, access_counts, total_bytes)
    prep_seconds = time.perf_counter() - t_prep

    clock = StageClock(overlap=depth > 1)
    state = {
        "feat_hits": 0,
        "feat_lookups": 0,
        "embed_hits": 0,
        "embed_lookups": 0,
        "prefetched_rows": 0,
        "spill_s": 0.0,
        "fill_s": 0.0,
    }
    out_host: np.ndarray | None = None

    for layer in range(num_layers):
        store = feat_store if layer == 0 else build_store
        activate = layer < num_layers - 1
        out_dim = gnn_models.layer_kind(model).out_dim(params[layer])
        out_host = np.empty((n, out_dim), np.float32)
        pad_id = max(store.pad_node_id(), 0)
        hits_key = "feat_hits" if layer == 0 else "embed_hits"
        lookups_key = "feat_lookups" if layer == 0 else "embed_lookups"

        def gather_fn(ctx, store=store):
            spec, ids = ctx.payload
            staged = ctx.outputs.get("prefetch")
            feats, hit = store.gather(
                jnp.asarray(ids),
                use_kernel=use_kernel,
                gather_buffers=gather_buffers,
                prefetched=staged,
                row_block=row_block,
            )
            return feats, jnp.sum(hit & spec.live)

        def prefetch_fn(ctx, store=store):
            # Pads point at a cached id, so (like the deduped sampled
            # path) they can never stage phantom miss rows; duplicate live
            # misses stage duplicate rows, matching the sampled non-dedup
            # semantics bit for bit.
            _, ids = ctx.payload
            staged = store.prefetch_misses(ids)
            state["prefetched_rows"] += staged.num_miss
            return staged

        def compute_fn(ctx, layer=layer, activate=activate):
            spec, _ = ctx.payload
            feats = ctx.outputs["gather"][0]
            return gnn_models.forward_layer(
                params[layer],
                feats[:chunk_size],
                feats[chunk_size:],
                spec.seg_ids,
                spec.degrees,
                model=model,
                num_dst=chunk_size,
                activate=activate,
            )

        def on_retire(ctx, out_host=out_host, hk=hits_key, lk=lookups_key):
            spec, _ = ctx.payload
            t0 = time.perf_counter()
            h = np.asarray(ctx.outputs["compute"])
            out_host[spec.lo : spec.lo + spec.cnt] = h[: spec.cnt]
            state["spill_s"] += time.perf_counter() - t0
            state[hk] += int(ctx.outputs["gather"][1])
            state[lk] += spec.cnt + spec.n_edges

        executor = PipelinedExecutor(
            [
                Stage("prefetch", prefetch_fn, lambda c: c.outputs["prefetch"])
                if prefetch
                else None,
                Stage("gather", gather_fn, lambda c: c.outputs["gather"]),
                Stage("compute", compute_fn, lambda c: c.outputs["compute"]),
            ],
            depth=depth,
            clock=clock,
            on_retire=on_retire,
            tracer=tracer,
        )
        payloads = []
        for spec in plan.chunks:
            ids = spec.base_ids if pad_id == 0 else np.where(spec.pad_mask, pad_id, spec.base_ids)
            payloads.append((spec, np.asarray(ids, np.int32)))
        # Warm one representative chunk per distinct bucket shape, so the
        # first-of-a-shape compiles land outside the timed laps.
        seen = set()
        for spec, ids in payloads:
            shape = spec.base_ids.shape[0]
            if shape in seen:
                continue
            seen.add(shape)
            feats, _ = store.gather(
                jnp.asarray(ids),
                use_kernel=use_kernel,
                gather_buffers=gather_buffers,
                row_block=row_block,
            )
            jax.block_until_ready(
                gnn_models.forward_layer(
                    params[layer],
                    feats[:chunk_size],
                    feats[chunk_size:],
                    spec.seg_ids,
                    spec.degrees,
                    model=model,
                    num_dst=chunk_size,
                    activate=activate,
                )
            )
        # One enclosing span per layer pass on the "layers" lane; the
        # executor's slot lanes carry the per-chunk batch/stage spans
        # nested under it in time, so a trace shows L layer blocks each
        # filled with its chunk pipeline.
        with tracer.span(
            f"layer {layer}",
            lane="layers",
            args={"layer": layer, "chunks": plan.num_chunks} if tracer.enabled else None,
        ):
            executor.run(payloads)

        if activate:
            # Next layer's input store: the spilled table behind a fresh
            # embedding cache.  Only one is live at a time, so it gets the
            # full per-layer embedding share.
            t0 = time.perf_counter()
            with tracer.span("embed-fill", lane="layers", args={"layer": layer}):
                build_store = build_embedding_cache(out_host, access_counts, embed_bytes)
            state["fill_s"] += time.perf_counter() - t0

    report = LayerwiseReport(
        policy=pipe.name,
        num_nodes=n,
        num_layers=num_layers,
        chunk_size=chunk_size,
        num_chunks=plan.num_chunks,
        num_edges=graph.num_edges,
        gather_seconds=clock.total("gather"),
        compute_seconds=clock.total("compute"),
        spill_seconds=state["spill_s"],
        fill_seconds=state["fill_s"],
        prep_seconds=prep_seconds,
        feat_hits=state["feat_hits"],
        feat_lookups=state["feat_lookups"],
        embed_hits=state["embed_hits"],
        embed_lookups=state["embed_lookups"],
        feat_row_bytes=dataset.feature_nbytes_per_row(),
        embed_row_bytes=embed_row_bytes,
        pipeline_depth=depth,
        prefetch_seconds=clock.total("prefetch"),
        prefetched_rows=state["prefetched_rows"],
        allocation=alloc,
        config=config,
        outputs=out_host,
    )
    if metrics is not None:
        metrics.counter("chunks_total", mode="layerwise").inc(num_layers * plan.num_chunks)
        metrics.gauge("feat_hit_rate", mode="layerwise").set(report.feat_hit_rate)
        metrics.gauge("embed_hit_rate", mode="layerwise").set(report.embed_hit_rate)
        for name in ("gather", "prefetch", "compute"):
            metrics.gauge("stage_seconds", mode="layerwise", stage=name).set(clock.total(name))
        report.metrics = metrics.snapshot()
    return report
