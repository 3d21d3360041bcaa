"""GNN inference driver — the paper's system as a CLI.

Single stream (the paper's setup):

    PYTHONPATH=src python -m repro.launch.infer_gnn \
        --dataset ogbn-products --policy dci --fanouts 15,10,5 \
        --batch-size 1024 --cache-mb 2

Multi-stream serving (N request streams sharing one DualCache, batches
interleaved through one pipelined executor — runtime/gnn_serve.py):

    PYTHONPATH=src python -m repro.launch.infer_gnn \
        --policy dci --streams 4 --batches-per-stream 8 --pipeline-depth 2
"""

from __future__ import annotations

import argparse
import json

from repro.core.config import INFERENCE_MODES, ServeConfig
from repro.core.policies import ADMISSION_POLICIES, POLICIES
from repro.core.trace import MetricsRegistry, Tracer
from repro.graph import load_dataset
from repro.models.gnn import MODELS
from repro.runtime.cache_refresh import MODES as REFRESH_MODES
from repro.runtime.gnn_engine import GNNInferenceEngine
from repro.runtime.gnn_serve import MultiStreamServer, make_stream_batches
from repro.runtime.request_queue import (
    RequestQueueServer,
    burst_trace,
    flash_crowd_trace,
    poisson_trace,
    uniform_seed_batches,
)
from repro.utils.compile_cache import enable_compile_cache


def _depth(value: str):
    """--pipeline-depth accepts an int or 'auto' (measured compute:prep)."""
    return "auto" if value == "auto" else int(value)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="ogbn-products")
    ap.add_argument("--policy", default="dci", choices=sorted(POLICIES))
    ap.add_argument("--model", default="graphsage", choices=MODELS)
    ap.add_argument("--fanouts", default="15,10,5")
    ap.add_argument("--batch-size", type=int, default=1024)
    ap.add_argument("--cache-mb", type=float, default=2.0)
    ap.add_argument("--scale", type=float, default=0.004)
    ap.add_argument("--presample", type=int, default=8)
    ap.add_argument("--max-batches", type=int, default=None)
    ap.add_argument(
        "--mode",
        default="sampling",
        choices=INFERENCE_MODES,
        help="'sampling' (default) = mini-batch neighborhood-sampled inference "
        "over the test seeds; 'layerwise' = full-graph layer-wise scoring — "
        "every layer over ALL nodes in node-range chunks, the DualCache "
        "serving layer-0 features and an embedding cache serving "
        "intermediate layer outputs (runtime/layerwise.py)",
    )
    ap.add_argument(
        "--chunk-size",
        type=int,
        default=None,
        help="node-range chunk for --mode layerwise (default 4096, clamped "
        "to the graph)",
    )
    ap.add_argument(
        "--pipeline-depth",
        type=_depth,
        default=1,
        help="batches kept in flight: 1 = serial (per-stage sync, the paper's "
        "timing), 2+ = overlap batch i+1's sample/gather with batch i's compute, "
        "'auto' = derive the window from a measured compute:prep probe",
    )
    ap.add_argument(
        "--refresh-mode",
        default="off",
        choices=REFRESH_MODES,
        help="online cache refresh: 'interval' re-allocates (Eq. 1 on the "
        "measured serve-time stage ratio) and delta re-fills every "
        "--refresh-interval retired batches; 'events' refreshes on stream "
        "join/leave; 'all' does both.  Off (default) keeps the caches "
        "immutable — bit-for-bit the pre-refresh system",
    )
    ap.add_argument(
        "--refresh-interval",
        type=int,
        default=8,
        help="retired batches between interval refreshes (interval/all modes)",
    )
    ap.add_argument(
        "--refresh-miss-threshold",
        type=float,
        default=None,
        help="SLO-aware refresh trigger: fire a refresh as soon as the live "
        "telemetry window's feature miss rate crosses this value, composing "
        "with the interval/event triggers (needs --refresh-mode != off)",
    )
    ap.add_argument(
        "--dedup",
        action="store_true",
        help="sort-and-unique each input frontier on device and "
        "gather/prefetch/model one row per DISTINCT node, expanding through "
        "the inverse map; outputs and hit accounting are identical, only "
        "the gathered-row count (and wall clock) changes",
    )
    ap.add_argument(
        "--prefetch",
        action="store_true",
        help="stage batch i+1's MISSED host feature rows onto the device "
        "(jax.device_put) while batch i's forward runs; outputs and hit "
        "accounting are identical, only where the miss bytes move changes",
    )
    ap.add_argument(
        "--use-kernel",
        action="store_true",
        help="route feature gathers through the Pallas cached_gather kernel "
        "(compiled on a TPU, interpret mode on the CPU)",
    )
    ap.add_argument(
        "--gather-buffers",
        type=int,
        default=2,
        help="kernel row copies kept in flight: 1 = serial copies, 2 = double "
        "buffering (only meaningful with --use-kernel)",
    )
    ap.add_argument(
        "--streams",
        type=int,
        default=1,
        help="number of independent request streams served against ONE shared "
        "cache (1 = the single-stream engine; >1 = runtime/gnn_serve.py, with "
        "the presample budget split across stream seeds)",
    )
    ap.add_argument(
        "--batches-per-stream",
        type=int,
        default=8,
        help="queue length per stream in multi-stream mode "
        "(--max-batches caps it too, so the flag means the same in both modes)",
    )
    ap.add_argument(
        "--max-inflight",
        type=int,
        default=None,
        help="backpressure cap: window slots one stream may occupy (default: depth)",
    )
    ap.add_argument(
        "--arrival",
        default="none",
        choices=("none", "poisson", "burst", "flash-crowd"),
        help="request-level serving (runtime/request_queue.py): put each "
        "stream's batches on an arrival clock instead of an always-ready "
        "queue.  'poisson' = steady traffic with exponential gaps, 'burst' "
        "= a flash crowd at t=0 colliding with a service-paced steady "
        "stream (always 2 streams), 'flash-crowd' = every stream dumps its "
        "whole queue at t=0.  'none' (default) serves plain queues",
    )
    ap.add_argument(
        "--slo-ms",
        type=float,
        default=None,
        help="relative deadline attached to every request (arrival modes); "
        "reported as deadline hit rate, and enforced by --admission slo",
    )
    ap.add_argument(
        "--admission",
        default="round-robin",
        choices=sorted(ADMISSION_POLICIES),
        help="admission policy for --arrival modes: 'round-robin' (the "
        "bit-for-bit baseline), 'edf' (earliest deadline first), 'slo' "
        "(EDF + shed requests whose deadline already passed)",
    )
    ap.add_argument(
        "--mean-interarrival-ms",
        type=float,
        default=50.0,
        help="mean request gap per stream for --arrival poisson",
    )
    ap.add_argument(
        "--mesh",
        type=int,
        default=0,
        help="shard the feature table + feature cache across this many mesh "
        "devices (runtime/sharded_serve.py); clamps to the devices present "
        "(set XLA_FLAGS=--xla_force_host_platform_device_count=N for a CPU "
        "mesh).  0 (default) keeps the single-device servers; outputs and "
        "hit accounting are bit-identical at any mesh size",
    )
    ap.add_argument(
        "--trace",
        default=None,
        metavar="OUT.json",
        help="record a span/event timeline of the run (core/trace.py) and "
        "write it as Chrome trace-event JSON — load it in Perfetto "
        "(ui.perfetto.dev) or chrome://tracing, or summarize it with "
        "scripts/trace_summary.py.  Off (default) = the NullTracer no-op "
        "path; outputs are bit-for-bit identical either way",
    )
    ap.add_argument(
        "--trace-jax",
        action="store_true",
        help="also bridge every span into jax.profiler.TraceAnnotation so "
        "spans show up inside a JAX/XLA profiler capture (needs --trace)",
    )
    ap.add_argument(
        "--faults",
        default=None,
        metavar="PLAN.json",
        help="inject deterministic faults from a FaultPlan JSON file "
        "(core/faults.py): named serve-path sites (adj_fetch, host_fetch, "
        "prefetch, kernel_gather, shard_exchange, refresh_fill) fail or "
        "delay on seeded per-site schedules.  Replay is a pure function of "
        "the plan — the same plan + same run produces the same faults.  "
        "Off (default) = no injector, the bit-for-bit baseline",
    )
    ap.add_argument(
        "--fault-policy",
        default=None,
        choices=("fail", "retry", "shed"),
        help="what a guarded-site failure does: 'fail' fails fast (default), "
        "'retry' retries with bounded exponential backoff then fails, 'shed' "
        "retries then sheds just the failing request and keeps serving",
    )
    ap.add_argument(
        "--retry",
        action="store_true",
        help="shorthand for --fault-policy retry",
    )
    ap.add_argument(
        "--retry-attempts",
        type=int,
        default=3,
        help="attempts per guarded call including the first (fault policies "
        "retry/shed)",
    )
    ap.add_argument(
        "--retry-backoff-ms",
        type=float,
        default=1.0,
        help="base backoff before attempt 2; doubles per attempt with "
        "deterministic seeded jitter, capped (core/retry.py RetryPolicy)",
    )
    ap.add_argument(
        "--retry-timeout-ms",
        type=float,
        default=None,
        help="per-attempt wall-clock budget; an attempt over budget raises "
        "StageTimeout, which retries like a fault (default: no timeout)",
    )
    ap.add_argument(
        "--degraded-mode",
        action="store_true",
        help="serve degraded instead of failing when the miss path is down: "
        "cache-only feature service (hit rows real, miss rows zero, requests "
        "marked degraded) and prefetch skipping.  Composes with --fault-policy",
    )
    ap.add_argument(
        "--metrics",
        default=None,
        metavar="OUT",
        help="collect a structured metrics snapshot (counters/gauges/"
        "histograms, core/trace.py MetricsRegistry) and write it to OUT: "
        "Prometheus text exposition when OUT ends in .prom/.txt, JSON "
        "otherwise.  The snapshot is also embedded in the printed report "
        "under the 'metrics' key",
    )
    args = ap.parse_args()
    enable_compile_cache()

    if args.trace_jax and args.trace is None:
        ap.error("--trace-jax requires --trace")
    tracer = Tracer(jax_annotations=args.trace_jax) if args.trace is not None else None
    metrics = MetricsRegistry() if args.metrics is not None else None

    def finish(rep) -> None:
        print(json.dumps(rep.summary(), indent=1))
        if tracer is not None:
            tracer.export(args.trace)
        if metrics is not None:
            text = (
                metrics.to_prometheus()
                if args.metrics.endswith((".prom", ".txt"))
                else metrics.to_json()
            )
            with open(args.metrics, "w", encoding="utf-8") as fh:
                fh.write(text)

    fanouts = tuple(int(x) for x in args.fanouts.split(","))
    if args.arrival == "burst":
        args.streams = 2  # the burst trace is one flash-crowd + one steady stream
    # One typed config object carries every execution knob from here down —
    # the engine, the servers, and the report echoes all read it.
    cfg = ServeConfig.from_args(args)
    ds = load_dataset(args.dataset, scale=args.scale)
    eng = GNNInferenceEngine(
        ds,
        model=args.model,
        fanouts=fanouts,
        batch_size=args.batch_size,
        pipeline_depth=args.pipeline_depth,
    )
    stream_seeds = [eng.seed + s for s in range(args.streams)] if args.streams > 1 else None
    eng.prepare(
        args.policy,
        config=cfg.engine,
        total_cache_bytes=int(args.cache_mb * 1e6),
        n_presample=args.presample,
        stream_seeds=stream_seeds,
    )
    if args.mode == "layerwise":
        # Full-graph scoring is a whole-dataset pass — the serving
        # front-ends (streams/arrival/mesh) are sampling-mode machinery.
        rep = eng.run(config=cfg.engine, tracer=tracer, metrics=metrics)
        finish(rep)
        return
    if args.arrival != "none":
        per_stream = args.batches_per_stream
        if args.max_batches is not None:
            per_stream = min(per_stream, args.max_batches)
        slo_s = args.slo_ms / 1e3 if args.slo_ms is not None else None
        if args.arrival == "poisson":
            trace = poisson_trace(
                ds,
                num_streams=args.streams,
                requests_per_stream=per_stream,
                batch_size=args.batch_size,
                mean_interarrival_s=args.mean_interarrival_ms / 1e3,
                slo_s=slo_s,
                seed=eng.seed,
            )
        elif args.arrival == "flash-crowd":
            trace = flash_crowd_trace(
                ds,
                num_streams=args.streams,
                requests_per_stream=per_stream,
                batch_size=args.batch_size,
                slo_s=slo_s,
                seed=eng.seed,
            )
        else:  # burst: pace the steady stream at the measured service time
            probe = uniform_seed_batches(
                ds, n_batches=1, batch_size=args.batch_size, seed=eng.seed
            )[0]
            eng.warmup(probe)
            service_s = float(sum(eng._probe_stage_seconds(probe)))
            trace = burst_trace(
                ds,
                burst_requests=per_stream,
                steady_requests=2 * per_stream,
                batch_size=args.batch_size,
                service_estimate_s=service_s,
                slo_s=slo_s,
                seed=eng.seed,
            )
        server = RequestQueueServer(eng, config=cfg, tracer=tracer, metrics=metrics)
        for sid, requests in enumerate(trace):
            server.add_request_stream(requests, seed=eng.seed + sid)
        # Under a fault plan, a fail-fast abort still prints the partial
        # report (with the 'error' field) instead of a traceback.
        rep = server.run(raise_on_error=args.faults is None)
        finish(rep)
    elif args.streams > 1 or args.mesh > 0:
        if args.mesh > 0:
            from repro.runtime.sharded_serve import ShardedServer

            server = ShardedServer(eng, config=cfg, tracer=tracer, metrics=metrics)
        else:
            server = MultiStreamServer(eng, config=cfg, tracer=tracer, metrics=metrics)
        per_stream = args.batches_per_stream
        if args.max_batches is not None:
            per_stream = min(per_stream, args.max_batches)
        queues = make_stream_batches(
            ds,
            num_streams=args.streams,
            batches_per_stream=per_stream,
            batch_size=args.batch_size,
            seed=eng.seed,
        )
        seeds = stream_seeds if stream_seeds is not None else [eng.seed]
        for sid, queue in enumerate(queues):
            server.add_stream(queue, seed=seeds[sid])
        rep = server.run(raise_on_error=args.faults is None)
        finish(rep)
    else:
        # The servers above resolve the injector from cfg.faults; the
        # single-stream engine takes live handles.
        injector = None
        if args.faults is not None:
            from repro.core.faults import FaultInjector, FaultPlan

            injector = FaultInjector(FaultPlan.load(args.faults), tracer=tracer)
        rep = eng.run(
            config=cfg.engine,
            max_batches=args.max_batches,
            tracer=tracer,
            metrics=metrics,
            injector=injector,
            retry_policy=cfg.retry_policy(),
            degraded_mode=cfg.degraded_mode,
        )
        finish(rep)


if __name__ == "__main__":
    main()
