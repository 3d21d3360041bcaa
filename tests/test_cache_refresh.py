"""Online cache-refresh subsystem (runtime/cache_refresh.py + friends).

Load-bearing guarantees:

  * a refresh NEVER changes values — sampled blocks, gathered rows, and
    logits are bit-identical with refresh on or off (the sort order and
    host tables are frozen; a refresh moves bytes, not results);
  * re-fills are deltas — kept feature rows stay in their device slots,
    unchanged adjacency segments are copied from the old cache, and the
    refreshed caches equal what a from-scratch fill at the same counts
    and budget would select;
  * epoch accounting — per-epoch hit counters partition the lifetime
    counters exactly;
  * serve-time join/leave triggers an incremental refresh and unchanged
    streams stay serial-equivalent.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from test_sampling import assert_blocks_match_reference, reference_graph

from repro.core.cache import DualCache
from repro.core.config import EngineConfig
from repro.core.allocation import CacheAllocation
from repro.core.telemetry import WorkloadTelemetry, merge_windows
from repro.graph.csc import build_adj_cache, refresh_adj_cache, two_level_sort
from repro.graph.features import build_feature_cache, refresh_feature_cache, select_hot_rows
from repro.runtime.cache_refresh import CacheRefreshManager, RefreshConfig
from repro.runtime.gnn_engine import GNNInferenceEngine, auto_pipeline_depth
from repro.runtime.gnn_serve import MultiStreamServer, make_stream_batches
from repro.runtime.request_queue import flash_crowd_seed_batches, uniform_seed_batches
from repro.utils.timing import StageClock

FANOUTS = (3, 2)
BATCH = 64
KW = dict(total_cache_bytes=200_000, n_presample=2)


def _engine(dataset, policy="dci", **kw):
    eng = GNNInferenceEngine(dataset, fanouts=FANOUTS, batch_size=BATCH)
    eng.prepare(policy, **{**KW, **kw})
    return eng


# ------------------------------------------------------------------ telemetry


def test_telemetry_accumulates_and_windows():
    t = WorkloadTelemetry(num_nodes=10, num_edges=6)
    nodes = np.array([1, 2, 2, 5])
    hit = np.array([True, False, False, True])
    t.observe_batch(nodes, hit, [np.array([[0, 1]]), np.array([[5]])])
    assert t.batches == 1
    assert t.node_counts[2] == 2 and t.node_counts[1] == 1
    assert t.node_miss_counts[2] == 2 and t.node_miss_counts[1] == 0
    assert t.edge_counts[5] == 1 and t.edge_counts[0] == 1
    win = t.snapshot()
    assert win.feat_lookups == 4 and win.feat_misses == 2 and win.miss_rate == 0.5
    t.reset()
    assert t.batches == 0 and t.node_counts.sum() == 0
    # snapshot is a copy — later accumulation must not mutate it
    t.observe_batch(nodes, hit, [])
    assert win.node_counts[2] == 2


def test_telemetry_drops_out_of_bounds_edge_slots():
    """A zero-degree node at the CSC tail emits slot == num_edges; the
    presample path's JAX scatter drops it silently — telemetry must too,
    not crash the serve loop (np.add.at raises on OOB)."""
    t = WorkloadTelemetry(num_nodes=4, num_edges=4)
    t.observe_batch(np.array([0]), np.array([True]), [np.array([[3, 4]])])
    assert t.edge_counts[3] == 1 and t.edge_counts.sum() == 1


def test_telemetry_pull_times_uses_cursors():
    t = WorkloadTelemetry(num_nodes=4, num_edges=2)
    clock = StageClock(overlap=True)
    for _ in range(3):
        with clock.stage("sample"):
            pass
        with clock.stage("feature"):
            pass
    t.pull_times(clock)
    assert len(t.sample_times) == len(t.feature_times) == 3
    t.pull_times(clock)  # no new laps -> nothing double-counted
    assert len(t.sample_times) == 3
    with clock.stage("sample"):
        pass
    t.pull_times(clock)
    assert len(t.sample_times) == 4
    t.reset()  # window resets, cursors persist
    t.pull_times(clock)
    assert len(t.sample_times) == 0


# ------------------------------------------------------------- feature delta


def _counts(rng, n):
    return rng.integers(0, 50, n).astype(np.int64)


def test_feature_refresh_matches_fresh_build_selection(rng):
    feats = rng.standard_normal((200, 8)).astype(np.float32)
    store = build_feature_cache(feats, _counts(rng, 200), 40 * 32)
    new_counts = _counts(rng, 200)
    refreshed, stats = refresh_feature_cache(store, new_counts, 40 * 32)
    fresh = build_feature_cache(feats, new_counts, 40 * 32)
    old_pos = np.asarray(store.position_map)
    new_pos = np.asarray(refreshed.position_map)
    # identical hot SET to a from-scratch fill (slot layout may differ)
    np.testing.assert_array_equal(np.nonzero(new_pos >= 0)[0],
                                  np.nonzero(np.asarray(fresh.position_map) >= 0)[0])
    # kept rows stayed in their slots; every cached slot holds its row's bits
    kept = (old_pos >= 0) & (new_pos >= 0)
    np.testing.assert_array_equal(old_pos[kept], new_pos[kept])
    cached_nodes = np.nonzero(new_pos >= 0)[0]
    np.testing.assert_array_equal(
        np.asarray(refreshed.hot_table)[new_pos[cached_nodes]], feats[cached_nodes]
    )
    assert stats.rows_kept == int(kept.sum())
    assert stats.rows_inserted == int(((old_pos < 0) & (new_pos >= 0)).sum())
    assert stats.rows_evicted == int(((old_pos >= 0) & (new_pos < 0)).sum())
    # host mirror carried forward matches the device map
    np.testing.assert_array_equal(refreshed.position_np(), new_pos)


def test_feature_refresh_same_counts_is_noop(rng):
    feats = rng.standard_normal((100, 4)).astype(np.float32)
    counts = _counts(rng, 100)
    store = build_feature_cache(feats, counts, 20 * 16)
    refreshed, stats = refresh_feature_cache(store, counts, 20 * 16)
    assert not stats.changed
    assert refreshed.hot_table is store.hot_table  # no device writes at all
    assert refreshed.position_map is store.position_map


def test_feature_refresh_grow_and_shrink(rng):
    feats = rng.standard_normal((100, 4)).astype(np.float32)
    store = build_feature_cache(feats, _counts(rng, 100), 10 * 16)
    grown, stats = refresh_feature_cache(store, _counts(rng, 100), 40 * 16)
    assert grown.num_cached == 40 and stats.budget_rows == 40
    assert stats.physical_rows >= 40
    # shrink: physical table is reused (no reshape), logical occupancy drops
    shrunk, sstats = refresh_feature_cache(grown, _counts(rng, 100), 5 * 16)
    assert shrunk.num_cached == 5
    assert shrunk.hot_table.shape[0] == grown.hot_table.shape[0]
    assert sstats.rows_evicted >= 35


def test_select_hot_rows_matches_build_semantics(rng):
    counts = _counts(rng, 64)
    hot = select_hot_rows(counts, 16)
    assert len(set(hot.tolist())) == 16
    # top above-mean nodes are always selected
    mean = counts.mean()
    above = np.nonzero(counts > mean)[0]
    top = above[np.argsort(-counts[above], kind="stable")[:16]]
    assert set(top.tolist()) <= set(hot.tolist())


# ----------------------------------------------------------- adjacency delta


def test_adj_refresh_prefix_invariant_and_delta(small_dataset, rng):
    g = small_dataset.graph
    ec0 = rng.integers(0, 9, g.num_edges).astype(np.int64)
    sorted_row, totals0 = two_level_sort(g, ec0)
    old = build_adj_cache(g, sorted_row, totals0, 4 * 1500)
    # updated counts re-rank the nodes; the sorted order stays frozen
    ec1 = rng.integers(0, 9, g.num_edges).astype(np.int64)
    _, totals1 = two_level_sort(g, ec1)
    new, stats = refresh_adj_cache(g, sorted_row, old, totals1, 4 * 1500)
    fresh = build_adj_cache(g, sorted_row, totals1, 4 * 1500)
    # the delta re-fill lands exactly where a fresh Alg. 1 fill would
    np.testing.assert_array_equal(new.cached_len, fresh.cached_len)
    np.testing.assert_array_equal(new.cache_ptr, fresh.cache_ptr)
    np.testing.assert_array_equal(new.cache_row_index, fresh.cache_row_index)
    assert new.num_cached_elements * 4 <= 4 * 1500
    assert stats.elements_kept + stats.elements_regathered == new.num_cached_elements
    changed = new.cached_len.astype(int) != old.cached_len.astype(int)
    assert stats.nodes_changed == int(changed.sum())


def test_adj_refresh_same_totals_is_noop(small_dataset, rng):
    g = small_dataset.graph
    ec = rng.integers(0, 9, g.num_edges).astype(np.int64)
    sorted_row, totals = two_level_sort(g, ec)
    old = build_adj_cache(g, sorted_row, totals, 4 * 1000)
    new, stats = refresh_adj_cache(g, sorted_row, old, totals, 4 * 1000)
    assert not stats.changed and stats.elements_regathered == 0
    np.testing.assert_array_equal(new.cache_row_index, old.cache_row_index)


# ------------------------------------------------------------ DualCache epochs


def test_dual_cache_refresh_bumps_epoch_and_applies_delta(small_dataset, rng):
    ds = small_dataset
    alloc = CacheAllocation(
        total_bytes=100_000, adj_bytes=50_000, feat_bytes=50_000, sample_fraction=0.5
    )
    dc = DualCache.build(
        ds,
        node_counts=rng.integers(0, 9, ds.num_nodes),
        edge_counts=rng.integers(0, 9, ds.graph.num_edges),
        allocation=alloc,
    )
    assert dc.epoch == 0 and dc.refreshable
    new_alloc = dataclasses.replace(alloc, adj_bytes=30_000, feat_bytes=70_000)
    delta = dc.refresh(
        allocation=new_alloc,
        node_counts=rng.integers(0, 9, ds.num_nodes),
        edge_counts=rng.integers(0, 9, ds.graph.num_edges),
    )
    assert dc.epoch == 1 and delta.epoch == 1
    assert dc.allocation is new_alloc
    assert dc.feat_cached_rows * ds.feature_nbytes_per_row() <= new_alloc.feat_bytes
    assert dc.adj_cached_elements * 4 <= new_alloc.adj_bytes
    # the device holds the new hit lengths, and samples from the resident
    # sorted CSC exactly as the two-gather sampler over the new cache did
    np.testing.assert_array_equal(np.asarray(dc.dgraph.cached_len), dc._adj_cache.cached_len)
    assert_blocks_match_reference(
        jax.random.PRNGKey(5), dc.dgraph, reference_graph(ds.graph, dc._sorted_row, dc._adj_cache),
        jnp.asarray(ds.test_idx[:64], jnp.int32), (4, 3), dedup=True,
    )


def test_cacheless_dual_cache_rejects_refresh(small_dataset):
    dc = DualCache.none(small_dataset)
    assert not dc.refreshable
    with pytest.raises(ValueError):
        dc.refresh(
            allocation=CacheAllocation(
                total_bytes=0, adj_bytes=0, feat_bytes=0, sample_fraction=0.5
            ),
            node_counts=np.zeros(small_dataset.num_nodes),
            edge_counts=np.zeros(small_dataset.graph.num_edges),
        )


# -------------------------------------------------------------- config errors


def test_refresh_config_validation():
    with pytest.raises(ValueError):
        RefreshConfig(mode="sometimes")
    with pytest.raises(ValueError):
        RefreshConfig(mode="interval")  # needs interval_batches >= 1
    with pytest.raises(ValueError):
        RefreshConfig(mode="events", history_decay=1.5)
    with pytest.raises(ValueError):
        RefreshConfig(mode="events", max_split_step=0.0)
    assert not RefreshConfig().enabled
    assert RefreshConfig(mode="all", interval_batches=2).on_interval


def test_manager_rejects_disabled_config_and_cacheless_policy(small_dataset):
    eng = _engine(small_dataset)
    with pytest.raises(ValueError):
        CacheRefreshManager(
            eng.pipeline, small_dataset, fanouts=FANOUTS, batch_size=BATCH,
            config=RefreshConfig(),
        )
    dgl = _engine(small_dataset, policy="dgl")
    with pytest.raises(ValueError):
        CacheRefreshManager(
            dgl.pipeline, small_dataset, fanouts=FANOUTS, batch_size=BATCH,
            config=RefreshConfig(mode="events"),
        )


# ---------------------------------------------------------- engine refresh


def test_engine_refresh_outputs_bit_identical_and_epochs_partition(small_dataset):
    ref = _engine(small_dataset)
    r0 = ref.run(max_batches=6, pipeline_depth=1, collect_outputs=True)
    o0 = ref.last_outputs

    eng = GNNInferenceEngine(small_dataset, fanouts=FANOUTS, batch_size=BATCH,
                             params=ref.params)
    eng.pipeline = ref.pipeline  # same prepared pipeline, epoch 0
    r1 = eng.run(
        max_batches=6,
        pipeline_depth=2,
        collect_outputs=True,
        refresh=RefreshConfig(mode="interval", interval_batches=2),
    )
    assert eng.pipeline.caches.epoch >= 1
    assert len(r1.refresh_events) >= 1
    for e in r1.refresh_events:
        # every re-fill is a delta: no full rebuild — something stayed put
        assert e.delta.feat.rows_kept > 0 or e.delta.adj.elements_kept > 0
        assert e.pause_seconds >= 0
    # refresh moves bytes, never values
    for a, b in zip(o0, eng.last_outputs):
        np.testing.assert_array_equal(a, b)
    # per-epoch counters partition the lifetime counters exactly
    assert r1.epoch_hits is not None and len(r1.epoch_hits) >= 2
    assert sum(v["batches"] for v in r1.epoch_hits.values()) == r1.num_batches


def test_refresh_recovers_feature_hits_after_flash_crowd_shift(small_dataset):
    """Prepare on uniform traffic, then shift to a flash crowd (every batch
    one small seed pool): the interval refresh re-ranks the feature cache
    toward the new hot set and hits more than the frozen cache, by delta
    re-fills alone.  SCI and a sub-byte split step hold the allocation, so
    the wall-clock Eq. 1 re-run cannot move budget; the hits are counts."""
    bs = 64
    eng = GNNInferenceEngine(small_dataset, fanouts=(8,), batch_size=bs)
    uniform = uniform_seed_batches(small_dataset, n_batches=8, batch_size=bs, seed=0)
    crowd = flash_crowd_seed_batches(small_dataset, n_batches=8, batch_size=bs, seed=1)
    eng.prepare("sci", total_cache_bytes=50_000, n_presample=4)
    refresh = RefreshConfig(
        mode="interval", interval_batches=2, history_decay=0.3, max_split_step=1e-9
    )
    serial = EngineConfig(pipeline_depth=1)
    post = {}
    # Static first: a refreshed pass mutates the shared caches in place.
    for label, cfg in (("static", None), ("refreshed", refresh)):
        eng.run(batches=uniform, config=serial, refresh=cfg)
        post[label] = eng.run(batches=crowd, config=serial, refresh=cfg)
    static, refreshed = post["static"], post["refreshed"]
    assert static.feat_lookups == refreshed.feat_lookups
    assert refreshed.feat_hits > static.feat_hits
    assert refreshed.refresh_events
    for e in refreshed.refresh_events:
        assert e.delta.allocation.adj_bytes == 0
        assert e.delta.feat.rows_kept > 0


def test_engine_refresh_off_is_default_path(small_dataset):
    eng = _engine(small_dataset)
    r_off = eng.run(max_batches=4, pipeline_depth=1, refresh=RefreshConfig(mode="off"))
    assert r_off.refresh_events == [] and r_off.epoch_hits is None
    assert eng.pipeline.caches.epoch == 0
    assert "refresh_events" not in r_off.summary()


# ----------------------------------------------------------- serve join/leave


def test_serve_join_leave_trigger_incremental_refresh(small_dataset):
    eng = _engine(small_dataset, n_presample=4, stream_seeds=[100, 101])
    queues = make_stream_batches(
        small_dataset, num_streams=3, batches_per_stream=3, batch_size=BATCH, seed=7
    )
    server = MultiStreamServer(eng, depth=2, refresh=RefreshConfig(mode="events"))
    s0 = server.add_stream(queues[0], seed=100, collect_outputs=True)
    s1 = server.add_stream(queues[1], seed=101, collect_outputs=True)
    server.run()
    assert eng.pipeline.caches.epoch == 0  # pre-run adds are not join events

    s2 = server.add_stream(queues[2], seed=102, collect_outputs=True)
    assert eng.pipeline.caches.epoch == 1  # serve-time join refreshed
    events = server.refresh_manager.events
    assert [e.reason for e in events] == ["stream-join"]
    assert events[0].delta.feat.rows_kept > 0 or events[0].delta.adj.elements_kept > 0
    server.run()

    server.remove_stream(s2.stream_id)
    assert eng.pipeline.caches.epoch == 2
    assert [e.reason for e in server.refresh_manager.events] == [
        "stream-join",
        "stream-leave",
    ]

    # unchanged streams: per-stream results stay serial-equivalent
    for state, queue, seed in ((s0, queues[0], 100), (s1, queues[1], 101)):
        ref = GNNInferenceEngine(
            small_dataset, fanouts=FANOUTS, batch_size=BATCH, seed=seed, params=eng.params
        )
        ref.pipeline = eng.pipeline
        ref.run(batches=list(queue), pipeline_depth=1, collect_outputs=True)
        assert len(ref.last_outputs) == len(state.runtime.outputs)
        for a, b in zip(ref.last_outputs, state.runtime.outputs):
            np.testing.assert_array_equal(a, b)


def test_serve_interval_refresh_reports_per_epoch(small_dataset):
    eng = _engine(small_dataset, stream_seeds=[100, 101])
    queues = make_stream_batches(
        small_dataset, num_streams=2, batches_per_stream=4, batch_size=BATCH, seed=3
    )
    server = MultiStreamServer(
        eng, depth=2, refresh=RefreshConfig(mode="interval", interval_batches=3)
    )
    for i, q in enumerate(queues):
        server.add_stream(q, seed=100 + i)
    rep = server.run()
    assert rep.epochs is not None and len(rep.refresh_events) >= 1
    # aggregate per-epoch batches partition the total
    assert sum(v["batches"] for v in rep.epochs.values()) == rep.total_batches
    # per-stream epoch splits sum to the aggregate
    for epoch, agg in rep.epochs.items():
        per_stream = sum(
            s.epoch_hits[epoch]["batches"] for s in rep.streams
            if s.epoch_hits and epoch in s.epoch_hits
        )
        assert per_stream == agg["batches"]
    assert "per_epoch" in rep.summary()


def test_serve_refresh_off_report_unchanged(small_dataset):
    eng = _engine(small_dataset)
    (queue,) = make_stream_batches(
        small_dataset, num_streams=1, batches_per_stream=2, batch_size=BATCH, seed=3
    )
    server = MultiStreamServer(eng, depth=1)
    server.add_stream(queue, seed=100)
    rep = server.run()
    assert rep.epochs is None and rep.refresh_events == []
    assert "per_epoch" not in rep.summary()
    assert "per_epoch" not in rep.streams[0].summary()


# ------------------------------------------------------------ adaptive depth


def test_auto_pipeline_depth_heuristic():
    # A ~zero prep lap means the probe measured nothing overlappable —
    # depth 1 (serial), NOT prep/compute → 0 → "pin at 2" from noise.
    assert auto_pipeline_depth(0.0, 1.0) == 1
    assert auto_pipeline_depth(5e-7, 1.0) == 1  # below the degenerate-lap floor
    assert auto_pipeline_depth(1.0, 1.0) == 2
    assert auto_pipeline_depth(3.0, 1.0) == 4
    assert auto_pipeline_depth(100.0, 1.0) == 4  # saturates at max_depth
    assert auto_pipeline_depth(100.0, 1.0, max_depth=6) == 6
    # Degenerate COMPUTE probe with real prep: double-buffer, never a
    # divide-by-~0 ratio pinning the window at the cap.
    assert auto_pipeline_depth(1.0, 0.0) == 2
    assert auto_pipeline_depth(1.0, 1e-9) == 2


def test_engine_does_not_cache_degenerate_auto_probe(small_dataset, monkeypatch):
    """A zero-measured prep lap resolves to depth 1 for THIS run but is
    not cached — the next resolve re-probes and can recover a real
    window."""
    eng = _engine(small_dataset)
    monkeypatch.setattr(
        eng, "_probe_stage_seconds", lambda seeds: (0.0, 0.0, 1.0)
    )
    assert eng.resolve_pipeline_depth("auto") == 1
    monkeypatch.undo()
    depth = eng.resolve_pipeline_depth("auto")  # re-probed, now cached
    assert 2 <= depth <= 4
    assert eng.resolve_pipeline_depth("auto") == depth


def test_engine_resolves_auto_depth(small_dataset):
    eng = _engine(small_dataset)
    depth = eng.resolve_pipeline_depth("auto")
    assert isinstance(depth, int) and 2 <= depth <= 4
    assert eng.resolve_pipeline_depth("auto") == depth  # cached
    rep = eng.run(max_batches=2, pipeline_depth="auto")
    assert rep.pipeline_depth == depth
    # plain ints pass through untouched, without a probe
    assert eng.resolve_pipeline_depth(3) == 3


def test_run_with_empty_batch_list_still_returns(small_dataset):
    """An explicit empty batch list is a no-op run, not an IndexError from
    the depth-resolution probe's eager seeds lookup."""
    eng = _engine(small_dataset)
    rep = eng.run(batches=[], warmup=False, pipeline_depth=2)
    assert rep.num_batches == 0 and rep.feat_lookups == 0


def test_prepare_accepts_auto_depth(small_dataset):
    eng = GNNInferenceEngine(
        small_dataset, fanouts=FANOUTS, batch_size=BATCH, pipeline_depth="auto"
    )
    pipe = eng.prepare("dci", pipeline_depth="auto", **KW)
    assert pipe.presample is not None  # presampling ran (serially) fine


# ------------------------------------------------------------- threaded pack


def test_prefetch_pack_thread_bit_identical(small_dataset):
    eng = _engine(small_dataset)
    store = eng.pipeline.caches.store
    rng = np.random.default_rng(0)
    nodes = rng.integers(0, small_dataset.num_nodes, 257).astype(np.int32)
    a = store.prefetch_misses(nodes, pack_in_thread=True)
    b = store.prefetch_misses(nodes, pack_in_thread=False)
    np.testing.assert_array_equal(np.asarray(a.rows), np.asarray(b.rows))
    assert a.num_miss == b.num_miss
    if a.idx is not None:
        np.testing.assert_array_equal(np.asarray(a.idx), np.asarray(b.idx))
        np.testing.assert_array_equal(np.asarray(a.pack_pos), np.asarray(b.pack_pos))


# --------------------------------------------------- SLO miss-rate trigger


def test_refresh_config_validates_miss_threshold():
    with pytest.raises(ValueError):
        RefreshConfig(mode="events", miss_threshold=0.0)
    with pytest.raises(ValueError):
        RefreshConfig(mode="events", miss_threshold=1.5)
    cfg = RefreshConfig(mode="events", miss_threshold=0.3)
    assert cfg.enabled and not cfg.on_interval


def test_miss_threshold_fires_before_interval(small_dataset):
    """A high-miss window must refresh on the SLO trigger without waiting
    out the interval (here: events mode, so no interval trigger at all)."""
    eng = _engine(small_dataset, total_cache_bytes=40_000)
    rep = eng.run(
        max_batches=4,
        pipeline_depth=1,
        refresh=RefreshConfig(mode="events", miss_threshold=0.05),
    )
    assert rep.refresh_events, "threshold never fired"
    assert all(e.reason == "miss-threshold" for e in rep.refresh_events)
    assert all(e.window_miss_rate >= 0.05 for e in rep.refresh_events)


def test_miss_threshold_composes_with_interval(small_dataset):
    """interval mode + threshold: the quality trigger may pre-empt the
    schedule, and the schedule still guarantees a refresh cadence."""
    eng = _engine(small_dataset, total_cache_bytes=40_000)
    rep = eng.run(
        max_batches=6,
        pipeline_depth=1,
        refresh=RefreshConfig(
            mode="interval", interval_batches=3, miss_threshold=0.05
        ),
    )
    reasons = {e.reason for e in rep.refresh_events}
    assert reasons <= {"miss-threshold", "interval"} and reasons


def test_low_threshold_never_fires_below_it(small_dataset):
    """A threshold above the actual miss rate must never fire — only the
    interval trigger remains."""
    eng = _engine(small_dataset)  # ample cache → low miss rate
    rep = eng.run(
        max_batches=6,
        pipeline_depth=1,
        refresh=RefreshConfig(mode="interval", interval_batches=3, miss_threshold=0.999),
    )
    assert all(e.reason == "interval" for e in rep.refresh_events)


# ------------------------------------------------ refresh-aware auto depth


def test_refresh_rederives_auto_depth(small_dataset):
    """With pipeline_depth='auto' and refresh enabled, each refresh derives
    a window from the measured serve-time prep:compute laps and applies it
    to the live executor; outputs stay bit-identical to serial."""
    eng = _engine(small_dataset)
    r1 = eng.run(max_batches=6, pipeline_depth=1, collect_outputs=True)
    o1 = eng.last_outputs
    eng2 = GNNInferenceEngine(
        small_dataset, fanouts=FANOUTS, batch_size=BATCH, params=eng.params
    )
    eng2.pipeline = eng.pipeline
    r2 = eng2.run(
        max_batches=6,
        pipeline_depth="auto",
        collect_outputs=True,
        refresh=RefreshConfig(mode="interval", interval_batches=2),
    )
    depths = [e.suggested_depth for e in r2.refresh_events]
    assert depths and all(d is None or 2 <= d <= 4 for d in depths)
    # telemetry recorded compute laps, so at least the LAST refresh (after
    # a full window of retired batches) must carry a derived depth
    assert any(d is not None for d in depths)
    for a, b in zip(o1, eng2.last_outputs):
        np.testing.assert_array_equal(a, b)


def test_serve_refresh_rederives_auto_depth(small_dataset):
    """The multi-stream server applies the re-derived window to its live
    executor (depth='auto' + interval refresh)."""
    eng = _engine(small_dataset, stream_seeds=[0, 1])
    server = MultiStreamServer(
        eng,
        depth="auto",
        refresh=RefreshConfig(mode="interval", interval_batches=3),
    )
    queues = make_stream_batches(
        small_dataset, num_streams=2, batches_per_stream=3, batch_size=BATCH, seed=0
    )
    for sid, q in enumerate(queues):
        server.add_stream(q, seed=sid)
    rep = server.run()
    events = server.refresh_manager.events
    assert events, "interval refresh never fired"
    derived = [e.suggested_depth for e in events if e.suggested_depth is not None]
    if derived:  # once compute laps exist, the server follows the new window
        assert rep.depth == derived[-1]
        # the defaulted backpressure cap follows the window — a deeper
        # window admission can actually fill (an explicit cap would stay)
        assert server.max_inflight == derived[-1]


# -------------------------------------------- weighted per-stream telemetry


def test_merge_windows_weights_counts_not_laps():
    a = WorkloadTelemetry(num_nodes=6, num_edges=4)
    b = WorkloadTelemetry(num_nodes=6, num_edges=4)
    a.observe_batch(np.array([0, 1]), np.array([True, False]), [np.array([[0]])])
    b.observe_batch(np.array([1, 2]), np.array([False, True]), [np.array([[1]])])
    a.sample_times.append(0.5)
    b.sample_times.append(0.25)
    merged = merge_windows([a.snapshot(), b.snapshot()], [1.0, 3.0])
    # counts weighted: node 1 visited once in each window -> 1*1 + 3*1
    assert merged.node_counts[1] == 4.0 and merged.node_counts[0] == 1.0
    assert merged.node_miss_counts[1] == 4.0
    assert merged.edge_counts[1] == 3.0
    # laps concatenated UNweighted, batches summed
    assert merged.sample_times == [0.5, 0.25] and merged.batches == 2
    # weights=None == all-ones == plain sum
    plain = merge_windows([a.snapshot(), b.snapshot()])
    np.testing.assert_array_equal(
        plain.node_counts, a.snapshot().node_counts + b.snapshot().node_counts
    )
    # negative weights clamp to zero (a merge can't subtract a stream)
    clamped = merge_windows([a.snapshot(), b.snapshot()], [1.0, -5.0])
    np.testing.assert_array_equal(clamped.node_counts, a.snapshot().node_counts)
    with pytest.raises(ValueError):
        merge_windows([])
    with pytest.raises(ValueError):
        merge_windows([a.snapshot()], [1.0, 2.0])


def test_refresh_config_validates_stream_weighting():
    with pytest.raises(ValueError):
        RefreshConfig(mode="interval", interval_batches=2, stream_weighting="bogus")
    cfg = RefreshConfig(mode="interval", interval_batches=2, stream_weighting="queue-depth")
    assert cfg.enabled


def test_manager_telemetry_for_routes_by_weighting(small_dataset):
    eng = _engine(small_dataset)
    shared = CacheRefreshManager(
        eng.pipeline, small_dataset, fanouts=FANOUTS, batch_size=BATCH,
        config=RefreshConfig(mode="interval", interval_batches=2),
    )
    assert shared.telemetry_for(0) is shared.telemetry  # "none": shared sink
    weighted = CacheRefreshManager(
        eng.pipeline, small_dataset, fanouts=FANOUTS, batch_size=BATCH,
        config=RefreshConfig(
            mode="interval", interval_batches=2, stream_weighting="queue-depth"
        ),
    )
    s0, s1 = weighted.telemetry_for(0), weighted.telemetry_for(1)
    assert s0 is not weighted.telemetry and s0 is not s1
    assert weighted.telemetry_for(0) is s0  # stable per key


def test_serve_weighted_telemetry_refreshes_and_stays_equivalent(small_dataset):
    """stream_weighting='queue-depth': per-stream sinks feed a weighted
    merge at each refresh; refreshes still fire, their windows still
    count every stream's batches, and outputs stay serial-equivalent
    (weights change the ranking, never values)."""
    eng = _engine(small_dataset, stream_seeds=[100, 101])
    queues = make_stream_batches(
        small_dataset, num_streams=2, batches_per_stream=4, batch_size=BATCH, seed=3
    )
    server = MultiStreamServer(
        eng,
        depth=2,
        refresh=RefreshConfig(
            mode="interval", interval_batches=3, stream_weighting="queue-depth"
        ),
    )
    states = [
        server.add_stream(q, seed=100 + i, collect_outputs=True)
        for i, q in enumerate(queues)
    ]
    rep = server.run()
    mgr = server.refresh_manager
    assert rep.refresh_events, "interval refresh never fired"
    assert set(mgr._stream_telemetry) == {0, 1}  # one sink per stream
    assert rep.refresh_events[0].window_batches >= 3  # both streams counted
    for i, q in enumerate(queues):
        ref = GNNInferenceEngine(
            small_dataset, fanouts=FANOUTS, batch_size=BATCH, seed=100 + i,
            params=eng.params,
        )
        ref.pipeline = eng.pipeline
        ref.run(batches=list(q), pipeline_depth=1, collect_outputs=True)
        for a, b in zip(ref.last_outputs, states[i].runtime.outputs):
            np.testing.assert_array_equal(a, b)


# ------------------------------------------- leave-path history invariants


def test_join_serve_leave_history_never_negative(small_dataset):
    """join → serve (decays the remnant in lockstep) → leave: the decayed
    subtraction must leave every history count >= 0 — float-rounding
    asymmetry between the summed history decay and the remnant's solo
    decay must be absorbed by the clamp, not leak anti-visits into the
    next Eq. 1 re-allocation."""
    eng = _engine(small_dataset, n_presample=4, stream_seeds=[100, 101])
    queues = make_stream_batches(
        small_dataset, num_streams=3, batches_per_stream=3, batch_size=BATCH, seed=7
    )
    server = MultiStreamServer(
        eng, depth=2, refresh=RefreshConfig(mode="all", interval_batches=2)
    )
    server.add_stream(queues[0], seed=100)
    server.add_stream(queues[1], seed=101)
    server.run()
    s2 = server.add_stream(queues[2], seed=102)  # join: refresh + remnant stored
    mgr = server.refresh_manager
    assert 102 in mgr._stream_stats
    server.run()  # interval refreshes decay history AND remnant in lockstep
    assert any(e.reason == "interval" for e in mgr.events)
    server.remove_stream(s2.stream_id)  # leave: subtract the decayed remnant
    assert 102 not in mgr._stream_stats
    assert (mgr._node_counts >= 0.0).all()
    assert (mgr._edge_counts >= 0.0).all()
    assert mgr._sample_s >= 0.0 and mgr._feature_s >= 0.0
    # and the post-leave history still supports a refresh
    event = mgr.refresh("manual")
    assert event.delta.epoch == eng.pipeline.caches.epoch


# ------------------------------------------------------------- mesh path


def test_telemetry_shard_slice_partitions_the_window():
    t = WorkloadTelemetry(num_nodes=10, num_edges=6)
    t.observe_batch(
        np.array([1, 2, 2, 7, 9]),
        np.array([True, False, False, True, False]),
        [np.array([[0, 1]]), np.array([[5]])],
    )
    win = t.snapshot()
    slices = [win.shard_slice(0, 4), win.shard_slice(4, 7), win.shard_slice(7, 10)]
    # node traffic partitions exactly across the ranges
    np.testing.assert_array_equal(
        np.concatenate([s.node_counts for s in slices]), win.node_counts
    )
    np.testing.assert_array_equal(
        np.concatenate([s.node_miss_counts for s in slices]), win.node_miss_counts
    )
    for s in slices:
        # adjacency is replicated per shard; stage laps are whole-pipeline
        # facts — both pass through unsliced
        np.testing.assert_array_equal(s.edge_counts, win.edge_counts)
        assert s.sample_times == win.sample_times
        assert s.batches == win.batches


def test_sharded_serve_refresh_outputs_bit_identical(small_dataset):
    """Refresh on the mesh path moves bytes, never values: the sharded
    server's epoch-versioned outputs are bit-identical with refresh on or
    off, and its per-epoch counters partition the lifetime counters —
    the single-device invariants, carried across the shard exchange."""
    from repro.runtime.sharded_serve import ShardedServer

    eng = _engine(small_dataset, stream_seeds=[100, 101])
    queues = make_stream_batches(
        small_dataset, num_streams=2, batches_per_stream=4, batch_size=BATCH, seed=7
    )

    off = ShardedServer(eng, num_shards=4, dedup=True)
    for sid, q in enumerate(queues):
        off.add_stream(q, seed=100 + sid, collect_outputs=True)
    r_off = off.run()
    assert r_off.refresh_events == []

    on = ShardedServer(
        eng,
        num_shards=4,
        dedup=True,
        refresh=RefreshConfig(mode="interval", interval_batches=2),
    )
    for sid, q in enumerate(queues):
        on.add_stream(q, seed=100 + sid, collect_outputs=True)
    r_on = on.run()
    assert len(r_on.refresh_events) >= 1
    assert eng.pipeline.caches.epoch >= 1
    # the shards repartitioned on every refresh epoch; the latest
    # repartition mirrors the base fill exactly (earlier epochs' row
    # totals tracked their OWN epoch's allocation)
    assert len(on.repartition_log) == len(r_on.refresh_events)
    assert sum(on.repartition_log[-1]["rows_after"]) == (
        eng.pipeline.caches.store.num_cached
    )
    assert [e["epoch"] for e in on.repartition_log] == [
        e.epoch for e in r_on.refresh_events
    ]
    for a, b in zip(off.streams, on.streams):
        assert len(a.runtime.outputs) == len(b.runtime.outputs)
        for x, y in zip(a.runtime.outputs, b.runtime.outputs):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    assert r_on.epochs is not None
    assert sum(v["batches"] for v in r_on.epochs.values()) == r_on.total_batches


def test_refresh_manager_shard_allocations_partition_the_global(small_dataset):
    """After serve-time refreshes, the manager's per-shard Eq. 1 on the
    decayed partitioned history sums to the global budget with every
    shard at the global split fraction."""
    from repro.graph.shard import make_shard_plan
    from repro.runtime.sharded_serve import ShardedServer

    eng = _engine(small_dataset, stream_seeds=[100, 101])
    queues = make_stream_batches(
        small_dataset, num_streams=2, batches_per_stream=4, batch_size=BATCH, seed=7
    )
    server = ShardedServer(
        eng,
        num_shards=4,
        refresh=RefreshConfig(mode="interval", interval_batches=2),
    )
    for sid, q in enumerate(queues):
        server.add_stream(q, seed=100 + sid)
    server.run()
    mgr = server.refresh_manager
    assert mgr.events, "serve must have refreshed"
    base = eng.pipeline.caches.allocation
    for k in (1, 3, 4):
        allocs = mgr.shard_allocations(make_shard_plan(small_dataset.num_nodes, k))
        assert len(allocs) == k
        assert sum(a.total_bytes for a in allocs) == base.total_bytes
        for a in allocs:
            if a.total_bytes:
                assert a.sample_fraction == pytest.approx(
                    base.sample_fraction, abs=1e-9
                )
    # the server recorded the same per-shard allocations at the last epoch
    assert sum(a.total_bytes for a in server.shard_allocations) == base.total_bytes
