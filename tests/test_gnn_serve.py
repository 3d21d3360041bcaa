"""Multi-stream serving over a shared DualCache (runtime/gnn_serve.py).

The load-bearing guarantees:

  * per-stream serial equivalence — N interleaved streams produce, per
    stream, bit-identical logits and hit counters to running that stream's
    batches alone through the single-stream engine (per-stream RNG, reuse
    state, and the immutability of the shared caches);
  * shared-cache accounting — the aggregate report is exactly the sum of
    the per-stream reports;
  * admission — round-robin over streams with work, per-stream in-flight
    cap (backpressure), and no starvation under uneven queues.
"""

import numpy as np
import pytest
from conftest import miss_bytes

from repro.core.presample import merge_stats, run_presampling
from repro.runtime.gnn_engine import GNNInferenceEngine
from repro.runtime.gnn_serve import MultiStreamServer, make_stream_batches

FANOUTS = (3, 2)
BATCH = 64
KW = dict(total_cache_bytes=200_000, n_presample=2)
STREAM_SEEDS = [100, 101, 102]


def _shared_engine(dataset, policy="dci"):
    eng = GNNInferenceEngine(dataset, fanouts=FANOUTS, batch_size=BATCH)
    eng.prepare(policy, stream_seeds=STREAM_SEEDS, **KW)
    return eng


def _queues(dataset, n=3, batches=3):
    return make_stream_batches(
        dataset, num_streams=n, batches_per_stream=batches, batch_size=BATCH, seed=7
    )


def _reference_run(engine, queue, seed):
    """The stream's batches alone, serially, same params + shared pipeline."""
    ref = GNNInferenceEngine(
        engine.dataset, fanouts=FANOUTS, batch_size=BATCH, seed=seed, params=engine.params
    )
    ref.pipeline = engine.pipeline
    rep = ref.run(batches=list(queue), pipeline_depth=1, collect_outputs=True)
    return rep, ref.last_outputs


# --------------------------------------------------------------- equivalence


@pytest.mark.parametrize("policy", ["dci", "rain", "dgl"])
@pytest.mark.parametrize("depth", [1, 3])
def test_per_stream_serial_equivalence(small_dataset, policy, depth):
    """Interleaving N streams changes nothing a stream can observe — RAIN's
    cross-batch reuse included, because reuse state is per-stream."""
    engine = _shared_engine(small_dataset, policy)
    queues = _queues(small_dataset)
    server = MultiStreamServer(engine, depth=depth)
    states = [
        server.add_stream(q, seed=STREAM_SEEDS[i], collect_outputs=True)
        for i, q in enumerate(queues)
    ]
    report = server.run()
    assert report.num_streams == len(queues)
    for i, q in enumerate(queues):
        ref_rep, ref_out = _reference_run(engine, q, STREAM_SEEDS[i])
        rt = states[i].runtime
        assert (ref_rep.adj_hits, ref_rep.adj_lookups) == (rt.adj_hits, rt.adj_lookups)
        assert (ref_rep.feat_hits, ref_rep.feat_lookups) == (rt.feat_hits, rt.feat_lookups)
        assert len(ref_out) == len(rt.outputs) == len(q)
        for a, b in zip(ref_out, rt.outputs):
            np.testing.assert_array_equal(a, b)


def test_serve_prefetch_bit_identical_and_capped(small_dataset):
    """Prefetch on the shared schedule: outputs and hit accounting are
    bit-identical to the prefetch-off serve over the SAME prepared
    pipeline, prefetched rows equal the aggregate misses, and per-stream
    staging respects the backpressure cap (staged buffers only exist
    inside admitted in-flight batches)."""
    engine = _shared_engine(small_dataset)
    queues = _queues(small_dataset)

    def serve(prefetch):
        server = MultiStreamServer(
            engine, depth=2, max_inflight_per_stream=2, prefetch=prefetch
        )
        states = [
            server.add_stream(q, seed=STREAM_SEEDS[i], collect_outputs=True)
            for i, q in enumerate(queues)
        ]
        return server.run(), states

    rep_off, _ = serve(False)
    rep_on, states = serve(True)
    assert rep_on.prefetch and not rep_off.prefetch
    assert (rep_off.feat_hits, rep_off.feat_lookups) == (rep_on.feat_hits, rep_on.feat_lookups)
    assert (rep_off.adj_hits, rep_off.adj_lookups) == (rep_on.adj_hits, rep_on.adj_lookups)
    for s_off, s_on in zip(rep_off.streams, rep_on.streams):
        assert (s_off.feat_hits, s_off.adj_hits) == (s_on.feat_hits, s_on.adj_hits)
    total_prefetched = sum(s.prefetched_rows for s in rep_on.streams)
    assert total_prefetched == rep_on.feat_lookups - rep_on.feat_hits
    for st in states:
        assert st.max_inflight_seen <= 2  # staged buffers bounded by the cap
    # and stream outputs match a prefetch-off solo reference run exactly
    for i, q in enumerate(queues):
        _, ref_out = _reference_run(engine, q, STREAM_SEEDS[i])
        for a, b in zip(ref_out, states[i].runtime.outputs):
            np.testing.assert_array_equal(a, b)


def test_single_stream_server_matches_engine(small_dataset):
    engine = _shared_engine(small_dataset)
    (queue,) = _queues(small_dataset, n=1, batches=4)
    server = MultiStreamServer(engine, depth=1)
    server.add_stream(queue, seed=STREAM_SEEDS[0], collect_outputs=True)
    report = server.run()
    ref_rep, ref_out = _reference_run(engine, queue, STREAM_SEEDS[0])
    s = report.streams[0]
    assert (s.adj_hits, s.feat_hits) == (ref_rep.adj_hits, ref_rep.feat_hits)
    assert report.total_batches == ref_rep.num_batches
    for a, b in zip(ref_out, server.streams[0].runtime.outputs):
        np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------- accounting


def test_aggregate_accounting_sums_streams(small_dataset):
    engine = _shared_engine(small_dataset)
    queues = _queues(small_dataset)
    server = MultiStreamServer(engine, depth=2)
    for i, q in enumerate(queues):
        server.add_stream(q, seed=STREAM_SEEDS[i])
    rep = server.run()
    assert rep.adj_hits == sum(s.adj_hits for s in rep.streams)
    assert rep.adj_lookups == sum(s.adj_lookups for s in rep.streams)
    assert rep.feat_hits == sum(s.feat_hits for s in rep.streams)
    assert rep.feat_lookups == sum(s.feat_lookups for s in rep.streams)
    assert rep.total_batches == sum(len(q) for q in queues)
    assert rep.total_seeds == rep.total_batches * BATCH
    assert 0 < rep.feat_hit_rate <= 1
    assert rep.throughput_seeds_per_s > 0
    assert rep.feat_row_bytes == small_dataset.feature_nbytes_per_row()
    assert miss_bytes(rep) > 0
    summary = rep.summary()
    assert summary["streams"] == 3 and len(summary["per_stream"]) == 3


def test_per_stream_clocks_and_latencies(small_dataset):
    engine = _shared_engine(small_dataset)
    queues = _queues(small_dataset, batches=2)
    server = MultiStreamServer(engine, depth=2)
    for i, q in enumerate(queues):
        server.add_stream(q, seed=STREAM_SEEDS[i])
    rep = server.run()
    for s in rep.streams:
        assert s.num_batches == 2
        # every stage booked time on the STREAM's own clock
        assert s.sample_seconds > 0 and s.feature_seconds > 0 and s.compute_seconds > 0
        assert s.mean_latency_s > 0 and s.max_latency_s >= s.mean_latency_s


# ----------------------------------------------------------------- admission


def test_round_robin_admission_with_backpressure(small_dataset):
    """Uneven queues (6/2/1), cap 1: round-robin while everyone has work;
    short streams finish without ever waiting behind the deep queue; the
    lone remaining stream is allowed past its cap (documented fallback —
    admission must make progress) but only once others drained."""
    engine = _shared_engine(small_dataset)
    all_batches = _queues(small_dataset, n=1, batches=9)[0]
    queues = [all_batches[:6], all_batches[6:8], all_batches[8:9]]
    server = MultiStreamServer(engine, depth=2, max_inflight_per_stream=1)
    for i, q in enumerate(queues):
        server.add_stream(q, seed=STREAM_SEEDS[i])
    rep = server.run()
    assert server.admission_log == [
        (0, 0), (1, 0), (2, 0), (0, 1), (1, 1), (0, 2), (0, 3), (0, 4), (0, 5),
    ]
    # every stream fully served, in its own batch order
    assert [s.num_batches for s in rep.streams] == [6, 2, 1]
    # cap respected whenever another stream could be picked instead
    assert server.streams[1].max_inflight_seen == 1
    assert server.streams[2].max_inflight_seen == 1
    assert server.streams[0].max_inflight_seen == 2  # solo-tail fallback


def test_no_starvation_first_round_covers_every_stream(small_dataset):
    engine = _shared_engine(small_dataset)
    queues = _queues(small_dataset, n=3, batches=2)
    server = MultiStreamServer(engine, depth=3)
    for i, q in enumerate(queues):
        server.add_stream(q, seed=STREAM_SEEDS[i])
    server.run()
    first_round = {sid for sid, _ in server.admission_log[:3]}
    assert first_round == {0, 1, 2}


# ------------------------------------------------------------ shared presample


def test_merge_stats_sums_counts_and_concats_times(small_dataset):
    per_stream = [
        run_presampling(
            small_dataset, fanouts=FANOUTS, batch_size=BATCH, n_batches=1, seed=s
        )
        for s in STREAM_SEEDS
    ]
    merged = merge_stats(per_stream)
    np.testing.assert_array_equal(
        merged.node_counts, np.sum([s.node_counts for s in per_stream], axis=0)
    )
    np.testing.assert_array_equal(
        merged.edge_counts, np.sum([s.edge_counts for s in per_stream], axis=0)
    )
    assert merged.n_batches == 3
    assert len(merged.sample_times) == len(merged.feature_times) == 3
    assert merged.peak_workload_bytes == max(s.peak_workload_bytes for s in per_stream)
    with pytest.raises(ValueError):
        merge_stats([])


def test_shared_prepare_splits_presample_budget(small_dataset):
    eng = GNNInferenceEngine(small_dataset, fanouts=FANOUTS, batch_size=BATCH)
    pipe = eng.prepare(
        "dci", total_cache_bytes=200_000, n_presample=8, stream_seeds=STREAM_SEEDS
    )
    # total presample budget split across streams EXACTLY (8 = 3 + 3 + 2),
    # not multiplied by them and not truncated by integer division
    assert pipe.presample.n_batches == 8
    assert pipe.caches.allocation.total_bytes == 200_000


def test_shared_cache_outhits_private_slices(small_dataset):
    """One budget-B cache prepared from the union presample hits more of
    the streams' feature lookups than N private B/N caches, each prepared
    from its own stream's presample and serving that stream alone.  ``sci``
    gives the whole budget to features, so no wall-clock Eq. 1 split
    enters the comparison and the counts are exact."""
    queues = _queues(small_dataset)
    server = MultiStreamServer(_shared_engine(small_dataset, "sci"), depth=1)
    for i, q in enumerate(queues):
        server.add_stream(q, seed=STREAM_SEEDS[i])
    shared = server.run()
    hits = lookups = 0
    for i, q in enumerate(queues):
        eng = GNNInferenceEngine(
            small_dataset, fanouts=FANOUTS, batch_size=BATCH, seed=STREAM_SEEDS[i]
        )
        eng.prepare(
            "sci",
            total_cache_bytes=KW["total_cache_bytes"] // len(queues),
            n_presample=KW["n_presample"],
        )
        rep = eng.run(batches=list(q), pipeline_depth=1)
        hits, lookups = hits + rep.feat_hits, lookups + rep.feat_lookups
    assert lookups == shared.feat_lookups
    assert shared.feat_hits > hits


# -------------------------------------------------------------------- errors


def test_server_rejects_bad_config(small_dataset):
    engine = _shared_engine(small_dataset)
    with pytest.raises(ValueError):
        MultiStreamServer(engine, depth=0)
    with pytest.raises(ValueError):
        MultiStreamServer(engine, depth=2, max_inflight_per_stream=0)
    with pytest.raises(RuntimeError):
        MultiStreamServer(engine, depth=1).run()
    unprepared = GNNInferenceEngine(small_dataset, fanouts=FANOUTS, batch_size=BATCH)
    with pytest.raises(RuntimeError):
        MultiStreamServer(unprepared)


def test_make_stream_batches_shapes_and_determinism(small_dataset):
    q1 = make_stream_batches(
        small_dataset, num_streams=2, batches_per_stream=3, batch_size=32, seed=5
    )
    q2 = make_stream_batches(
        small_dataset, num_streams=2, batches_per_stream=3, batch_size=32, seed=5
    )
    assert len(q1) == 2 and all(len(q) == 3 for q in q1)
    assert all(b.shape == (32,) for q in q1 for b in q)
    for a, b in zip(q1[0], q2[0]):
        np.testing.assert_array_equal(a, b)
    # different streams draw different orderings of the same test set
    assert not all(np.array_equal(a, b) for a, b in zip(q1[0], q1[1]))
