"""On-chip smoke run of the DCI GNN serving path.

Drives the system once through the objects ``repro.launch.infer_gnn`` uses
(``load_dataset``, ``GNNInferenceEngine.prepare/run``, the serving front
ends), on Table II-sized synthetic graphs built from ``--seed``, and checks
what comes out:

  (a) sampled serving on ogbn-products (2,449,029 nodes, F=100), GraphSAGE,
      fan-outs 15,10,5, batch 1024, policy dci, a cache of a fifth of
      the feature + adjacency bytes: serial, then depth 2 + dedup +
      prefetch.  Gathered rows equal the numpy table bit for bit; logits
      match a plain float32 numpy forward; hit rates lie in (0, 1).
  (c) the Pallas gather kernel on the same prepared pipeline, with and
      without dedup: logits and hit counts bit-identical to (a), and the
      kernel compiled (not interpret mode, ``tpu_custom_call`` in the HLO).
  (b) request serving: ``RequestQueueServer``, 2 Poisson streams, 8
      requests, with interval cache refresh; every request answered.
  (d) one layer-wise full-graph pass over Reddit (232,965 nodes, F=602).

``--chips 4`` runs only the sharded path: ``ShardedServer`` over a 4-device
mesh against ``MultiStreamServer`` on one chip, same streams, identical
logits and counters.

Usage (from the repository root, on a machine with a TPU):

    python chip_smoke.py             # phases (a)-(d), one chip
    python chip_smoke.py --chips 4   # sharded serving on four chips

The last line of standard output is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
It is printed only when every phase passed; without a TPU, or outside a
checkout of the repository, the script exits nonzero and prints no result.
"""

from __future__ import annotations

import argparse
import gc
import json
import pathlib
import sys
import time

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent
FANOUTS = (15, 10, 5)
BATCH = 1024

# TPU matmuls take f32 operands at the default precision, which rounds them
# to bfloat16 (8-bit mantissa, relative rounding 2^-9) before an f32
# accumulation; three stacked layers compound it to about a percent of the
# logit scale.  5e-2 of the largest reference logit leaves room for that
# and still fails on a wrong row, a wrong neighbour block or a wrong layer,
# each of which moves logits by the order of the logit scale itself.
LOGIT_TOL = 5e-2


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(msg, flush=True)


# ------------------------------------------------------------ reference


def reference_forward(params, feats: np.ndarray, fanouts) -> np.ndarray:
    """Plain float32 numpy GraphSAGE over one ``[self | neighbours]`` block
    (independent of ``repro.models``)."""
    rev = tuple(reversed(fanouts))
    mult = int(np.prod([1 + f for f in rev]))
    sizes = [feats.shape[0] // mult]
    for f in rev:
        sizes.append(sizes[-1] * (1 + f))
    h = feats
    for li, level in enumerate(range(len(fanouts) - 1, -1, -1)):
        p = {k: np.asarray(v, np.float32) for k, v in params[li].items()}
        n_dst, fo = sizes[level], rev[level]
        nbr = h[n_dst : n_dst * (1 + fo)].reshape(n_dst, fo, h.shape[1]).sum(axis=1)
        h = h[:n_dst] @ p["w_self"] + nbr @ p["w_nbr"] + p["b"]
        if li < len(fanouts) - 1:
            h = np.maximum(h, 0.0)
    return h


def sampled_blocks(eng, batches, *, dedup: bool):
    """The blocks a run of ``eng`` samples for ``batches``: the run's RNG
    stream (``PRNGKey(seed + 1)``, one split per batch) replayed."""
    import jax
    import jax.numpy as jnp

    from repro.graph.sampling import sample_blocks

    pipe = eng.pipeline
    key = jax.random.PRNGKey(eng.seed + 1)
    for seeds in batches:
        key, sub = jax.random.split(key)
        yield sample_blocks(
            sub,
            pipe.caches.dgraph,
            jnp.asarray(seeds),
            eng.fanouts,
            dedup=dedup,
            dedup_pad_id=pipe.caches.store.pad_node_id() if dedup else None,
        )


def check_clean(rep, what: str) -> None:
    """No fault-tolerance path may have been taken on a healthy run."""
    check(rep.kernel_fallbacks == 0, f"{what}: kernel_fallbacks={rep.kernel_fallbacks}")
    degraded = getattr(rep, "degraded_batches", getattr(rep, "requests_degraded", 0))
    check(degraded == 0, f"{what}: {degraded} degraded batches")
    check(getattr(rep, "error", None) is None, f"{what}: error {getattr(rep, 'error', None)}")


def hit_counts(rep) -> tuple[int, int, int, int]:
    return (rep.adj_hits, rep.adj_lookups, rep.feat_hits, rep.feat_lookups)


# --------------------------------------------------------------- phases


def phase_sampled(ds, eng, batches):
    """(a) serial and pipelined sampled serving on the table route."""
    from repro.core.config import EngineConfig

    runs = {}
    for name, cfg in (
        ("serial", EngineConfig(pipeline_depth=1)),
        ("pipelined", EngineConfig(pipeline_depth=2, dedup=True, prefetch=True)),
    ):
        t0 = time.perf_counter()
        rep = eng.run(config=cfg, batches=batches, collect_outputs=True)
        wall = time.perf_counter() - t0
        outs = [np.asarray(o) for o in eng.last_outputs]
        check_clean(rep, f"(a) {name}")
        check(0.0 < rep.feat_hit_rate < 1.0, f"(a) {name}: feat hit rate {rep.feat_hit_rate}")
        check(0.0 < rep.adj_hit_rate < 1.0, f"(a) {name}: adj hit rate {rep.adj_hit_rate}")
        check(len(outs) == len(batches), f"(a) {name}: {len(outs)} outputs")
        log(
            f"(a) {name}: {len(batches)} batches, feat_hit_rate={rep.feat_hit_rate} "
            f"adj_hit_rate={rep.adj_hit_rate} wall_s={wall} (host clock, compile included)"
        )
        runs[name] = (rep, outs)
    check(
        hit_counts(runs["serial"][0]) == hit_counts(runs["pipelined"][0]),
        "(a) hit counts differ between serial and pipelined runs",
    )

    store = eng.pipeline.caches.store
    worst = 0.0
    for i, block in enumerate(sampled_blocks(eng, batches, dedup=False)):
        ids = np.asarray(block.input_nodes)
        frontier = BATCH * int(np.prod([1 + f for f in FANOUTS]))
        check(ids.shape[0] == frontier, f"(a) frontier of {ids.shape[0]} rows, not {frontier}")
        feats, _ = store.gather(block.input_nodes)
        feats = np.asarray(feats)
        check(np.array_equal(feats, ds.features[ids]), f"(a) batch {i}: gathered rows differ")
        ref = reference_forward(eng.params, feats, eng.fanouts)
        scale = float(np.abs(ref).max())
        for name, (_, outs) in runs.items():
            err = float(np.abs(outs[i] - ref).max())
            worst = max(worst, err / scale)
            check(np.isfinite(outs[i]).all(), f"(a) {name} batch {i}: non-finite logits")
            check(err <= LOGIT_TOL * scale, f"(a) {name} batch {i}: max|err|={err} scale={scale}")
    log(f"(a) gathered rows bit-exact; logits vs float32 numpy: max|err|/max|ref| = {worst}")
    return runs


def assert_kernel_compiled(store, idx) -> None:
    """The gather kernel runs compiled: not in interpret mode, and the
    compiled program holds the Mosaic custom call."""
    import jax

    from repro.kernels.cached_gather.kernel import cached_gather, default_interpret

    check(not default_interpret(), "(c) the kernel would run in interpret mode")
    hot, host = store.kernel_tables()
    pos = store.position_map[idx]
    hlo = (
        jax.jit(lambda a, b, i, p: cached_gather(a, b, i, p, feat_dim=store.feat_dim))
        .lower(hot, host, idx, pos)
        .compile()
        .as_text()
    )
    check("tpu_custom_call" in hlo, "(c) no tpu_custom_call in the compiled gather")
    log("(c) kernel compiled: default_interpret()=False, tpu_custom_call in HLO")


def phase_kernel(ds, eng, batches, table_runs):
    """(c) the Pallas gather kernel on the same prepared pipeline."""
    from repro.core.config import EngineConfig
    from repro.kernels.cached_gather.kernel import ROW_BLOCK

    store = eng.pipeline.caches.store
    assert_kernel_compiled(store, next(sampled_blocks(eng, batches[:1], dedup=False)).input_nodes)

    for name, cfg in (
        ("serial", EngineConfig(pipeline_depth=1, use_kernel=True)),
        ("pipelined", EngineConfig(pipeline_depth=2, dedup=True, prefetch=True, use_kernel=True)),
    ):
        rep = eng.run(config=cfg, batches=batches, collect_outputs=True)
        check_clean(rep, f"(c) {name}")
        outs = [np.asarray(o) for o in eng.last_outputs]
        ref_rep, ref_outs = table_runs[name]
        check(hit_counts(rep) == hit_counts(ref_rep), f"(c) {name}: hit counts differ from (a)")
        for i, (a, b) in enumerate(zip(outs, ref_outs)):
            check(np.array_equal(a, b), f"(c) {name} batch {i}: logits differ from (a)")
        log(
            f"(c) kernel {name} (dedup={bool(cfg.dedup)}): logits and hit counts "
            "bit-identical to (a)"
        )

    for i, block in enumerate(sampled_blocks(eng, batches, dedup=True)):
        ids = np.asarray(block.input_nodes)
        feats, _ = store.gather(block.input_nodes, use_kernel=True)
        check(np.array_equal(np.asarray(feats), ds.features[ids]), f"(c) batch {i}: kernel rows")
        nu = int(block.dedup.num_unique)
        uids = block.dedup.unique_ids[:nu]
        feats_u, _ = store.gather(uids, use_kernel=True, row_block=ROW_BLOCK)
        check(
            np.array_equal(np.asarray(feats_u), ds.features[np.asarray(uids)]),
            f"(c) batch {i}: row-block kernel rows",
        )
    log("(c) kernel gathers (per-row and row-block) equal the numpy table bit for bit")


def phase_requests(ds, eng):
    """(b) request serving with arrivals, admission and interval refresh."""
    from repro.core.config import EngineConfig, ServeConfig
    from repro.runtime.request_queue import RequestQueueServer, poisson_trace

    cfg = ServeConfig(
        engine=EngineConfig(pipeline_depth=2, refresh_mode="interval", refresh_interval=4),
        arrival="poisson",
        mean_interarrival_ms=50.0,
    )
    trace = poisson_trace(
        ds,
        num_streams=2,
        requests_per_stream=4,
        batch_size=BATCH,
        mean_interarrival_s=cfg.mean_interarrival_ms / 1e3,
        seed=eng.seed,
    )
    server = RequestQueueServer(eng, config=cfg)
    for sid, requests in enumerate(trace):
        server.add_request_stream(requests, seed=eng.seed + sid)
    rep = server.run()
    offered = sum(len(t) for t in trace)
    check_clean(rep, "(b)")
    check(rep.total_batches == offered, f"(b) {rep.total_batches}/{offered} requests answered")
    check(rep.requests_shed == 0 and rep.unserved == 0, "(b) requests shed or left unserved")
    check(rep.availability == 1.0, f"(b) availability {rep.availability}")
    check(server.refresh_manager.failures == [], "(b) refresh failures")
    check(len(rep.refresh_events) >= 1, "(b) no cache refresh ran")
    log(
        f"(b) {rep.total_batches}/{offered} requests answered, shed=0, "
        f"refreshes={len(rep.refresh_events)}, p50_latency_s={rep.p50_latency_s} "
        f"p99_latency_s={rep.p99_latency_s} (host wall clock, informational)"
    )


def phase_layerwise(ds, seed: int):
    """(d) one layer-wise full-graph pass over the Reddit stand-in."""
    from repro.core.config import EngineConfig
    from repro.runtime.gnn_engine import GNNInferenceEngine

    eng = GNNInferenceEngine(ds, model="graphsage", fanouts=FANOUTS, batch_size=BATCH, seed=seed)
    eng.prepare("dci", config=EngineConfig(), total_cache_bytes=cache_budget(ds))
    rep = eng.run(config=EngineConfig(mode="layerwise"))
    out = rep.outputs
    check(out.shape == (ds.num_nodes, ds.spec.num_classes), f"(d) outputs {out.shape}")
    check(bool(np.isfinite(out).all()), "(d) non-finite logits")
    log(
        f"(d) layer-wise: {out.shape[0]} nodes scored over {rep.num_chunks} chunks, "
        f"all finite; feat_hit_rate={rep.feat_hit_rate} embed_hit_rate={rep.embed_hit_rate}"
    )


def phase_sharded(ds, eng):
    """--chips 4: ShardedServer on a 4-device mesh vs one chip."""
    import jax

    from repro.core.config import EngineConfig, ServeConfig
    from repro.runtime.gnn_serve import MultiStreamServer, make_stream_batches
    from repro.runtime.sharded_serve import ShardedServer

    cfg = ServeConfig(engine=EngineConfig(pipeline_depth=2, dedup=True, prefetch=True))
    queues = make_stream_batches(
        ds, num_streams=2, batches_per_stream=2, batch_size=BATCH, seed=eng.seed
    )

    def serve(server):
        states = [
            server.add_stream(q, seed=eng.seed + sid, collect_outputs=True)
            for sid, q in enumerate(queues)
        ]
        rep = server.run()
        check_clean(rep, type(server).__name__)
        return rep, [[np.asarray(o) for o in s.runtime.outputs] for s in states]

    one_rep, one_outs = serve(MultiStreamServer(eng, config=cfg))
    server = ShardedServer(eng, config=cfg.replace(mesh=4))
    check(server.mesh.size == 4, f"sharded mesh has {server.mesh.size} devices, asked for 4")
    shard_devs = [s.host_table.devices() for s in server.sharded.store.shards]
    check(all(len(d) == 1 for d in shard_devs), "a shard store spans several devices")
    shard_devs = [next(iter(d)) for d in shard_devs]
    check(
        len(set(shard_devs)) == 4
        and all(d.platform == jax.devices()[0].platform for d in shard_devs),
        f"shard stores on {shard_devs}",
    )
    rep, outs = serve(server)
    log(f"(e) shard stores on {[str(d) for d in shard_devs]}")
    log(
        f"(e) assembly device {server.sharded.store.assemble_device}; the forward runs "
        f"there too (the default device {jax.devices()[0]})"
    )
    for sid, (a_list, b_list) in enumerate(zip(one_outs, outs)):
        check(len(a_list) == len(b_list) == len(queues[sid]), f"stream {sid}: output count")
        for i, (a, b) in enumerate(zip(a_list, b_list)):
            check(np.array_equal(a, b), f"stream {sid} batch {i}: sharded logits differ")
    fields = ("adj_hits", "adj_lookups", "feat_hits", "feat_lookups", "unique_rows",
              "gathered_rows", "prefetched_rows", "num_batches", "num_seeds")
    for a, b in zip(one_rep.streams, rep.streams):
        for f in fields:
            check(getattr(a, f) == getattr(b, f), f"stream {a.stream_id} {f}: "
                  f"{getattr(a, f)} vs {getattr(b, f)}")
    for key, total in (
        ("feat_hits", rep.feat_hits),
        ("feat_lookups", rep.feat_lookups),
        ("adj_hits", rep.adj_hits),
        ("adj_lookups", rep.adj_lookups),
        ("prefetched_rows", sum(s.prefetched_rows for s in rep.streams)),
    ):
        shard_sum = sum(s[key] for s in rep.shards)
        check(shard_sum == total, f"per-shard {key} sum {shard_sum} != total {total}")
    log(
        f"(e) sharded x4 == one chip: {rep.total_batches} batches, logits and every hit "
        f"counter identical, per-shard sums tile the totals "
        f"(feat_hit_rate={rep.feat_hit_rate} adj_hit_rate={rep.adj_hit_rate})"
    )


def cache_budget(ds) -> int:
    """A fifth of the feature + adjacency bytes.  On the ogbn-products
    widths (400 B of features and ~100 B of adjacency per node) that is no
    more than the adjacency alone, so whatever split Eq. 1 measures,
    neither cache holds its whole table and both hit rates stay below 1."""
    return (ds.features.nbytes + ds.graph.num_edges * 4) // 5


# ----------------------------------------------------------------- main


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: no repro package under {ROOT / 'src'}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: first device is {dev.platform}, not a TPU", file=sys.stderr)
        return 3
    if len(devices) < args.chips:
        print(f"chip_smoke: {len(devices)} devices, --chips {args.chips}", file=sys.stderr)
        return 3

    from repro.graph import load_dataset
    from repro.runtime.gnn_engine import GNNInferenceEngine
    from repro.runtime.gnn_serve import make_stream_batches
    from repro.utils.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    compile_s = [0.0]
    cache_hits = [0]

    def on_duration(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compile_s[0] += duration

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            cache_hits[0] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    log(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; compile cache {cache_dir}")

    t0 = time.perf_counter()
    ds = load_dataset("ogbn-products", scale=1.0, seed=args.seed)
    check(ds.num_nodes == 2_449_029, f"ogbn-products stand-in has {ds.num_nodes} nodes")
    eng = GNNInferenceEngine(
        ds, model="graphsage", fanouts=FANOUTS, batch_size=BATCH, seed=args.seed
    )
    from repro.core.config import EngineConfig

    eng.prepare("dci", config=EngineConfig(), total_cache_bytes=cache_budget(ds))
    alloc = eng.pipeline.caches.allocation
    log(
        f"ogbn-products: {ds.num_nodes} nodes, {ds.graph.num_edges} edges, F={ds.spec.feat_dim}; "
        f"cache {alloc.total_bytes} B (adj {alloc.adj_bytes}, feat {alloc.feat_bytes}); "
        f"set-up {time.perf_counter() - t0} s"
    )

    if args.chips == 4:
        phase_sharded(ds, eng)
    else:
        batches = make_stream_batches(
            ds, num_streams=1, batches_per_stream=3, batch_size=BATCH, seed=args.seed
        )[0]
        table_runs = phase_sampled(ds, eng, batches)
        phase_kernel(ds, eng, batches, table_runs)
        phase_requests(ds, eng)
        # Phase (d) needs the chip's memory (its presampling gathers
        # 1,081,344 x 602 floats): release the ogbn-products engine first.
        del ds, eng, table_runs
        gc.collect()
        live = sum(a.nbytes for a in jax.live_arrays())
        log(f"device bytes live before (d): {live}")
        reddit = load_dataset("reddit", scale=1.0, seed=args.seed)
        check(reddit.num_nodes == 232_965, f"reddit stand-in has {reddit.num_nodes} nodes")
        phase_layerwise(reddit, args.seed)

    log(f"compile seconds: {compile_s[0]} (persistent-cache hits: {cache_hits[0]})")
    log(f"total seconds: {time.perf_counter() - t0}")
    print(
        json.dumps(
            {
                "ok": True,
                "device": {
                    "platform": dev.platform,
                    "kind": dev.device_kind,
                    "count": len(devices),
                },
            }
        ),
        flush=True,
    )
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as err:
        print(f"chip_smoke: FAILED: {err}", file=sys.stderr)
        sys.exit(1)
