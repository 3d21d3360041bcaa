"""Tracing & metrics layer (core/trace.py + the runtime wiring).

The load-bearing guarantees:

  * schema — every emitted event passes :func:`validate_trace` (Chrome
    trace-event fields present, X spans carry a non-negative ``dur``,
    every flow id pairs exactly one start with one end), so Perfetto /
    chrome://tracing always load the export;
  * agreement — per-stage span totals agree with the StageClock's stage
    seconds on a serial run (the span wraps the clock's lap, so span
    time is a tight upper bound);
  * overlap — the slot-lane model makes pipeline overlap a property of
    the trace: exactly 0.0 at depth 1, > 0 at depth > 1;
  * non-interference — tracing (and metrics) on vs off is bit-for-bit
    identical in outputs and hit accounting across the dedup x prefetch
    x refresh grid, and the NullTracer path allocates no events.
"""

import concurrent.futures
import json
import pathlib
import subprocess
import sys
import time

import jax
import numpy as np
import pytest

from repro.core.config import EngineConfig
from repro.core.trace import (
    NULL_TRACER,
    MetricsRegistry,
    NullTracer,
    Tracer,
    resolve_tracer,
    summarize_trace,
    validate_trace,
)
from repro.graph.features import PACK_LANE, build_feature_cache, plain_feature_store
from repro.graph.sampling import pow2_bucket
from repro.runtime.cache_refresh import RefreshConfig
from repro.runtime.gnn_engine import GNNInferenceEngine
from repro.runtime.gnn_serve import MultiStreamServer, make_stream_batches
from repro.runtime.request_queue import Request, RequestQueueServer
from repro.runtime.sharded_serve import ShardedServer

FANOUTS = (3, 2)
BATCH = 64
KW = dict(total_cache_bytes=200_000, n_presample=2)
STREAM_SEEDS = [100, 101, 102]


def _engine(dataset, *, streams=False):
    eng = GNNInferenceEngine(dataset, fanouts=FANOUTS, batch_size=BATCH)
    eng.prepare("dci", stream_seeds=STREAM_SEEDS if streams else None, **KW)
    return eng


def _queues(dataset, n=2, batches=3):
    return make_stream_batches(
        dataset, num_streams=n, batches_per_stream=batches, batch_size=BATCH, seed=7
    )


def _serve(dataset, tracer=None, metrics=None, *, depth=2, refresh=None, server_cls=MultiStreamServer, **kw):
    eng = _engine(dataset, streams=True)
    srv = server_cls(eng, depth=depth, refresh=refresh, tracer=tracer, metrics=metrics, **kw)
    queues = _queues(dataset)
    states = [
        srv.add_stream(q, seed=STREAM_SEEDS[i], collect_outputs=True)
        for i, q in enumerate(queues)
    ]
    rep = srv.run()
    outs = [[np.asarray(o) for o in s.runtime.outputs] for s in states]
    return rep, outs


# ------------------------------------------------------------ tracer unit


def test_tracer_schema_and_lanes():
    tr = Tracer()
    with tr.span("a", lane="slot 0", args={"batch": 0}):
        with tr.span("b", lane="slot 1"):
            pass
    tr.instant("tick", lane="slot 0")
    tr.counter("depth", {"q": 3.0})
    fid = tr.next_flow_id()
    tr.flow_start(fid, "req", lane="slot 0")
    tr.flow_end(fid, "req", lane="slot 1")
    assert validate_trace(tr.events) == []
    doc = tr.to_chrome()
    assert doc["displayTimeUnit"] == "ms"
    # metadata first, then timestamp order
    phs = [e["ph"] for e in doc["traceEvents"]]
    assert phs[: phs.count("M")] == ["M"] * phs.count("M")
    # lanes are dense tids in creation order, counters on tid 0
    names = {e["args"]["name"] for e in tr.events if e.get("name") == "thread_name"}
    assert {"slot 0", "slot 1"} <= names
    assert all(e["tid"] == 0 for e in tr.events if e["ph"] == "C")
    # spans nest: "b" closed before "a", both non-negative
    spans = {e["name"]: e for e in tr.events if e["ph"] == "X"}
    assert spans["b"]["dur"] >= 0 and spans["a"]["dur"] >= spans["b"]["dur"]


def test_validate_trace_catches_violations():
    bad = [
        {"ph": "X", "ts": 0.0, "pid": 1, "tid": 1, "name": "no-dur"},
        {"ph": "s", "ts": 0.0, "pid": 1, "tid": 1, "name": "f", "cat": "flow", "id": 9},
        {"ph": "i", "ts": 0.0, "pid": 1, "tid": 1, "name": "scope", "s": "zzz"},
    ]
    errs = validate_trace(bad)
    assert any("dur" in e for e in errs)
    assert any("flow" in e for e in errs)  # id 9 has a start but no finish
    assert any("scope" in e or "s" in e for e in errs)


def test_summarize_overlap_on_synthetic_spans():
    tr = Tracer()
    tr.complete("batch", lane="slot 0", ts_us=0.0, dur_us=100.0)
    tr.complete("batch", lane="slot 1", ts_us=50.0, dur_us=100.0)
    s = summarize_trace(tr.events)
    # busy wall-clock union is [0, 150] us; 50 us of it has both lanes busy
    assert s["overlap_fraction"] == pytest.approx(50.0 / 150.0)
    assert s["lanes"]["slot 0"]["spans"] == 1
    serial = Tracer()
    serial.complete("batch", lane="slot 0", ts_us=0.0, dur_us=100.0)
    serial.complete("batch", lane="slot 0", ts_us=100.0, dur_us=100.0)
    assert summarize_trace(serial.events)["overlap_fraction"] == 0.0


def _lane_tids(events) -> dict[str, int]:
    return {e["args"]["name"]: e["tid"] for e in events if e["ph"] == "M" and e["name"] == "thread_name"}


def test_tracer_lanes_stay_unique_under_concurrent_creation():
    """Spans open on worker threads too (the miss-pack worker), so lane
    creation must not hand two lanes one tid or name one lane twice when
    threads race to create the same lanes."""
    tr = Tracer()
    lanes, threads = 2000, 32
    seen: dict[int, list[int]] = {}

    def create(k):
        seen[k] = [tr.lane(f"lane {i}") for i in range(lanes)]

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        with concurrent.futures.ThreadPoolExecutor(threads) as pool:
            for f in [pool.submit(create, k) for k in range(threads)]:
                f.result(timeout=60)
    finally:
        sys.setswitchinterval(old)
    named = _lane_tids(tr.events)
    assert sorted(named.values()) == list(range(1, lanes + 1))
    assert sum(e["ph"] == "M" and e["name"] == "thread_name" for e in tr.events) == lanes
    assert all(tids == [named[f"lane {i}"] for i in range(lanes)] for tids in seen.values())


def test_null_tracer_is_free_and_shared():
    assert resolve_tracer(None) is NULL_TRACER
    tr = Tracer()
    assert resolve_tracer(tr) is tr
    null = resolve_tracer(None)
    assert isinstance(null, NullTracer) and not null.enabled
    with null.span("x", lane="anything", args={"k": 1}):
        pass
    null.instant("i")
    null.counter("c", {"v": 1.0})
    null.complete("x", lane="l", ts_us=0.0, dur_us=1.0)
    null.flow_start(null.next_flow_id(), "f", lane="l")
    assert tuple(null.events) == ()


# ------------------------------------------------------------ metrics unit


def test_metrics_registry_kinds_and_labels():
    m = MetricsRegistry()
    m.counter("reqs", stream=0).inc()
    m.counter("reqs", stream=0).inc(2)
    m.counter("reqs", stream=1).inc()
    m.gauge("rate", policy="dci").set(0.5)
    h = m.histogram("lat_ms")
    for v in (1.0, 3.0, 200.0, 900.0):
        h.observe(v)
    snap = m.snapshot()
    assert snap["counters"]['reqs{stream="0"}'] == 3.0
    assert snap["counters"]['reqs{stream="1"}'] == 1.0
    assert snap["gauges"]['rate{policy="dci"}'] == 0.5
    hs = snap["histograms"]["lat_ms"]
    assert hs["count"] == 4 and hs["min"] == 1.0 and hs["max"] == 900.0
    assert hs["p50"] <= hs["p95"] <= hs["p99"] <= 900.0
    # one name = one kind
    with pytest.raises(ValueError):
        m.gauge("reqs")
    with pytest.raises(ValueError):
        m.counter("reqs").inc(-1.0)
    # same labels in any kwarg order resolve to the same series
    assert m.counter("pair", a=1, b=2) is m.counter("pair", b=2, a=1)
    assert json.loads(m.to_json()) == m.snapshot()


def test_metrics_prometheus_text():
    m = MetricsRegistry()
    m.counter("served_total", stream=0).inc(5)
    m.gauge("hit_rate").set(0.25)
    m.histogram("lat_ms", buckets=(1.0, 10.0)).observe(2.0)
    text = m.to_prometheus()
    assert "# TYPE served_total counter" in text
    assert 'served_total{stream="0"} 5' in text
    assert "hit_rate 0.25" in text
    assert 'lat_ms_bucket{le="10"} 1' in text or 'lat_ms_bucket{le="10.0"} 1' in text
    assert 'lat_ms_bucket{le="+Inf"} 1' in text
    assert "lat_ms_count 1" in text


# --------------------------------------------------- engine / serve wiring


def test_engine_serial_spans_agree_with_stage_clock(small_dataset, jit_warm):
    eng = _engine(small_dataset)
    tr = Tracer()
    rep = eng.run(max_batches=3, pipeline_depth=1, tracer=tr)
    assert validate_trace(tr.events) == []
    s = summarize_trace(tr.events)
    # serial: one slot lane, zero overlap
    assert s["overlap_fraction"] == 0.0
    assert [n for n in s["lanes"] if n.startswith("slot")] == ["slot 0"]
    clock_s = {
        "sample": rep.sample_seconds,
        "feature": rep.feature_seconds,
        "compute": rep.compute_seconds,
    }
    for stage, total in clock_s.items():
        span_s = s["stages"][stage]["total_ms"] / 1e3
        # the span wraps the clock lap (plus ~us of tracer overhead)
        assert span_s >= total * 0.98
        assert span_s <= total + 0.05 * max(total, 1.0)


def test_serve_trace_flows_overlap_and_refresh(small_dataset, jit_warm):
    tr = Tracer()
    metrics = MetricsRegistry()
    rep, _ = _serve(
        small_dataset,
        tr,
        metrics,
        depth=2,
        refresh=RefreshConfig(mode="interval", interval_batches=3),
    )
    assert validate_trace(tr.events) == []
    s = summarize_trace(tr.events)
    assert s["overlap_fraction"] > 0.0
    # one complete enqueue->retire flow per retired batch
    retired = sum(st.num_batches for st in rep.streams)
    assert s["n_flows"] == retired
    names = {e.get("name") for e in tr.events if e["ph"] == "X"}
    assert {"queued", "service", "batch", "refresh"} <= names
    assert "epoch" in {e.get("name") for e in tr.events if e["ph"] == "i"}
    assert {"queue_depth", "inflight", "allocation_bytes"} <= set(s["counters"])
    # metrics landed in the report snapshot
    assert rep.metrics
    lat = [v for k, v in rep.metrics["histograms"].items() if k.startswith("request_latency_ms")]
    assert sum(h["count"] for h in lat) == retired
    assert "metrics" in rep.summary()


def test_request_queue_trace_uses_arrival_clock(small_dataset, jit_warm):
    eng = _engine(small_dataset, streams=True)
    tr = Tracer()
    rq = RequestQueueServer(eng, depth=2, admission="round-robin", tracer=tr)
    queues = _queues(small_dataset)
    for sid, q in enumerate(queues):
        reqs = [
            Request(request_id=i, stream_id=sid, seeds=b, arrival_s=0.0, deadline_s=None)
            for i, b in enumerate(q)
        ]
        rq.add_request_stream(reqs, seed=STREAM_SEEDS[sid])
    rep = rq.run()
    assert validate_trace(tr.events) == []
    s = summarize_trace(tr.events)
    assert s["n_flows"] == sum(st.num_batches for st in rep.streams)
    queued = [e for e in tr.events if e["ph"] == "X" and e["name"] == "queued"]
    assert queued and all(e["dur"] >= 0 for e in queued)


def test_sharded_serve_emits_exchange_spans(small_dataset, jit_warm):
    tr = Tracer()
    _serve(small_dataset, tr, depth=2, server_cls=ShardedServer, num_shards=2)
    assert validate_trace(tr.events) == []
    exch = [e for e in tr.events if e["ph"] == "X" and e["name"] == "exchange"]
    assert exch
    lanes = {e["tid"] for e in exch}
    assert len(lanes) == 2  # one lane per shard


def test_layerwise_trace_layer_spans(small_dataset, jit_warm):
    eng = _engine(small_dataset)
    tr = Tracer()
    metrics = MetricsRegistry()
    rep = eng.run(
        config=EngineConfig(mode="layerwise", chunk_size=4096),
        tracer=tr,
        metrics=metrics,
    )
    assert validate_trace(tr.events) == []
    layer_spans = [
        e for e in tr.events if e["ph"] == "X" and str(e["name"]).startswith("layer ")
    ]
    assert len(layer_spans) == rep.num_layers  # one span per model layer
    assert rep.metrics is not None
    assert any(k.startswith("chunks_total") for k in rep.metrics["counters"])


# ------------------------------------------- prefetch and host-sync spans


def _spans_by_batch(events, name) -> dict[int, list[dict]]:
    out: dict[int, list[dict]] = {}
    for e in events:
        if e["ph"] == "X" and e["name"] == name:
            out.setdefault(e["args"]["batch"], []).append(e)
    return out


def _inside(inner: dict, outer: dict) -> bool:
    return outer["ts"] <= inner["ts"] and inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"]


@pytest.mark.parametrize("dedup,refresh_on", [(True, False), (True, True), (False, False)])
def test_prefetch_and_sync_spans_link_to_their_batch(small_dataset, jit_warm, dedup, refresh_on):
    """Every staging step and host pull of a batch is a span carrying its
    index: the caller's steps nest in the batch's ``prefetch`` stage span
    on its slot lane, the pack and put run on the worker's lane inside the
    same stage, and the retire-time pulls nest in the batch's span."""
    eng = _engine(small_dataset)
    tr = Tracer()
    n = 4
    rep = eng.run(
        max_batches=n,
        config=EngineConfig(pipeline_depth=2, dedup=dedup, prefetch=True),
        refresh=RefreshConfig(mode="interval", interval_batches=2) if refresh_on else None,
        collect_outputs=True,
        tracer=tr,
    )
    ev = tr.events
    assert validate_trace(ev) == []
    worker = _lane_tids(ev)[PACK_LANE]
    stage = _spans_by_batch(ev, "prefetch")
    batch = _spans_by_batch(ev, "batch")
    sample = _spans_by_batch(ev, "sample")
    assert sorted(stage) == sorted(batch) == list(range(n))
    for name in ("prefetch:pull", "prefetch:scan", "prefetch:index", "prefetch:join"):
        spans = _spans_by_batch(ev, name)
        assert sorted(spans) == list(range(n)), name
        for b, (sp,) in spans.items():
            assert sp["tid"] == stage[b][0]["tid"] != worker and _inside(sp, stage[b][0]), name
    for name in ("prefetch:pack", "prefetch:put"):
        spans = _spans_by_batch(ev, name)
        assert sorted(spans) == list(range(n)), name
        for b, (sp,) in spans.items():
            assert sp["tid"] == worker and _inside(sp, stage[b][0]), name
    puts = _spans_by_batch(ev, "prefetch:put")
    assert sum(sp["args"]["rows"] for (sp,) in puts.values()) == rep.staged_rows
    assert rep.staged_rows >= rep.prefetched_rows > 0
    retire = ["sync:stats", "sync:logits"] + (["sync:telemetry"] if refresh_on else [])
    for name in retire:
        spans = _spans_by_batch(ev, name)
        assert sorted(spans) == list(range(n)), name
        for b, (sp,) in spans.items():
            assert sp["tid"] == batch[b][0]["tid"] and _inside(sp, batch[b][0]), name
    if not refresh_on:
        assert not _spans_by_batch(ev, "sync:telemetry")
    unique = _spans_by_batch(ev, "sync:num_unique")
    assert sorted(unique) == (list(range(n)) if dedup else [])
    for b, (sp,) in unique.items():
        assert _inside(sp, sample[b][0])


@pytest.mark.parametrize("pack_in_thread", [True, False])
def test_prefetch_misses_spans_and_staged_rows(small_dataset, rng, pack_in_thread):
    """The store's staging spans land on the caller's lane, or on the
    worker's for the pack and its put; ``staged_rows`` is the pow2 pack
    (the whole row set when every row missed), and tracing changes no bit."""
    ds = small_dataset
    counts = rng.integers(0, 6, ds.num_nodes).astype(np.int64)
    store = build_feature_cache(ds.features, counts, capacity_bytes=200_000)
    nodes = rng.integers(0, ds.num_nodes, 257).astype(np.int32)
    off = store.prefetch_misses(nodes, pack_in_thread=pack_in_thread)
    tr = Tracer()
    on = store.prefetch_misses(
        nodes, pack_in_thread=pack_in_thread, tracer=tr, lane="caller", args={"batch": 7}
    )
    np.testing.assert_array_equal(np.asarray(on.rows), np.asarray(off.rows))
    np.testing.assert_array_equal(np.asarray(on.idx), np.asarray(off.idx))
    assert 0 < on.num_miss == off.num_miss < nodes.size
    assert on.staged_rows == off.staged_rows == pow2_bucket(on.num_miss, nodes.size)
    lanes = {tid: name for name, tid in _lane_tids(tr.events).items()}
    seen = [(e["name"], lanes[e["tid"]]) for e in tr.events if e["ph"] == "X"]
    pack_lane = PACK_LANE if pack_in_thread else "caller"
    caller = [("prefetch:scan", "caller"), ("prefetch:index", "caller")]
    if pack_in_thread:
        caller.append(("prefetch:join", "caller"))
    assert sorted(seen) == sorted(caller + [("prefetch:pack", pack_lane), ("prefetch:put", pack_lane)])
    assert all(e["args"]["batch"] == 7 for e in tr.events if e["ph"] == "X")
    (put,) = [e for e in tr.events if e["name"] == "prefetch:put"]
    assert put["args"]["rows"] == on.staged_rows

    plain = plain_feature_store(ds.features)  # every row misses: no pack, no worker
    tr = Tracer()
    staged = plain.prefetch_misses(nodes, tracer=tr, lane="caller", args={"batch": 7})
    assert staged.idx is None and staged.staged_rows == staged.num_miss == nodes.size
    names = [e["name"] for e in tr.events if e["ph"] == "X"]
    assert names == ["prefetch:scan", "prefetch:pack", "prefetch:put"]
    assert set(_lane_tids(tr.events)) == {"caller"}


def test_worker_spans_reach_the_profiler_on_their_own_thread(small_dataset, jit_warm, tmp_path):
    """Under ``jax_annotations`` each span becomes a profiler annotation with
    its args as metadata: the worker's pack and put sit on a host thread of
    their own, and each names its batch."""
    from jax.profiler import ProfileData

    eng = _engine(small_dataset)
    kw = dict(max_batches=2, config=EngineConfig(pipeline_depth=2, dedup=True, prefetch=True))
    eng.run(**kw)  # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.run(**kw, warmup=False, tracer=Tracer(jax_annotations=True))
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.rglob("*.xplane.pb")
    found: dict[str, set] = {}  # span name -> {(host line, batch)}
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):
                for e in line.events:
                    if e.name.startswith("prefetch"):
                        found.setdefault(e.name, set()).add((i, dict(e.stats).get("batch")))
    stage_lines = {i for i, _ in found["prefetch"]}
    for name in ("prefetch:pack", "prefetch:put"):
        assert {b for _, b in found[name]} == {0, 1}, name
        assert stage_lines.isdisjoint(i for i, _ in found[name]), name
    for name in ("prefetch:pull", "prefetch:scan", "prefetch:index", "prefetch:join"):
        assert found[name] == {(i, b) for i in stage_lines for b in (0, 1)}, name


@pytest.mark.parametrize("model,per_batch", [("gat", 2 * (192 * 4 + 64 * 3)), ("graphsage", 0)])
def test_attn_edges_counts_the_forward_edge_scores(small_dataset, model, per_batch):
    """``attn_edges``: Σ over layers of S_dst × (fanout + 1) × heads per
    batch, from static shapes.  At fan-outs (3, 2) and 64 seeds the layers
    map 768 → 192 rows (fan-out 3) and 192 → 64 (fan-out 2); GAT here has
    2 heads, GraphSAGE no per-edge scores."""
    from repro.models import gnn as gnn_models

    params = gnn_models.init_params(
        jax.random.PRNGKey(0), model, small_dataset.spec.feat_dim,
        small_dataset.spec.num_classes, hidden=8, n_layers=2, heads=2,
    )
    eng = GNNInferenceEngine(small_dataset, model=model, fanouts=FANOUTS, batch_size=BATCH,
                             params=params)
    eng.prepare("dci", **KW)
    rep = eng.run(max_batches=3, config=EngineConfig(pipeline_depth=2, dedup=True, prefetch=True),
                  tracer=Tracer())
    assert rep.num_batches == 3
    assert rep.attn_edges == 3 * per_batch


# ------------------------------------------------- bit-for-bit equivalence


@pytest.mark.parametrize("dedup", [False, True])
@pytest.mark.parametrize("prefetch", [False, True])
@pytest.mark.parametrize("refresh_on", [False, True])
def test_tracing_is_bit_for_bit_invisible(small_dataset, jit_warm, dedup, prefetch, refresh_on):
    """Outputs (and, with immutable caches, hit counters) are identical
    with tracing+metrics on vs off across the knob grid."""
    eng = _engine(small_dataset)
    refresh = RefreshConfig(mode="interval", interval_batches=2) if refresh_on else None
    kw = dict(
        max_batches=4,
        pipeline_depth=2,
        dedup=dedup,
        prefetch=prefetch,
        refresh=refresh,
        collect_outputs=True,
    )
    r_off = eng.run(**kw)
    out_off = [np.asarray(o) for o in eng.last_outputs]
    tr = Tracer()
    r_on = eng.run(**kw, tracer=tr, metrics=MetricsRegistry())
    out_on = [np.asarray(o) for o in eng.last_outputs]
    assert len(out_off) == len(out_on)
    for a, b in zip(out_off, out_on):
        np.testing.assert_array_equal(a, b)
    if not refresh_on:
        # immutable caches: the accounting must match bit-for-bit too
        assert (r_off.feat_hits, r_off.feat_lookups) == (r_on.feat_hits, r_on.feat_lookups)
        assert (r_off.adj_hits, r_off.adj_lookups) == (r_on.adj_hits, r_on.adj_lookups)
        assert r_off.gathered_rows == r_on.gathered_rows
        assert (r_off.prefetched_rows, r_off.staged_rows) == (r_on.prefetched_rows, r_on.staged_rows)
    assert r_on.staged_rows >= r_on.prefetched_rows
    assert (r_on.staged_rows > 0) == prefetch
    assert validate_trace(tr.events) == []
    assert r_on.metrics is not None and r_off.metrics is None


# ------------------------------------------------------------- CLI summary


def test_trace_summary_cli_gates(small_dataset, jit_warm, tmp_path):
    tr = Tracer()
    _serve(small_dataset, tr, depth=2)
    path = tmp_path / "trace.json"
    tr.export(str(path))
    repo = str(pathlib.Path(__file__).resolve().parents[1])
    cmd = [sys.executable, "scripts/trace_summary.py", str(path)]
    ok = subprocess.run(
        cmd + ["--strict", "--min-overlap", "0.0", "--require-flows"],
        capture_output=True,
        text=True,
        cwd=repo,
    )
    assert ok.returncode == 0, ok.stderr
    assert "overlap fraction" in ok.stdout
    bad = subprocess.run(
        cmd + ["--require-span", "no-such-span"],
        capture_output=True,
        text=True,
        cwd=repo,
    )
    assert bad.returncode == 1
    assert "no-such-span" in bad.stderr


def test_tracer_timestamps_are_relative_and_monotonic():
    tr = Tracer()
    t0 = tr.now_us()
    time.sleep(0.001)
    t1 = tr.now_us()
    assert 0.0 <= t0 < t1
    # ts_from maps a perf_counter stamp into the same clock
    assert tr.ts_from(time.perf_counter()) >= t1
