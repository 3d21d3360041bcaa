"""The chip benchmark: one cell, one seed, one process.

``run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>`` lands
in :func:`main`, which

1. refuses to run (exit 3, no result line) without a TPU, or with fewer
   chips than the cell asks for, or on a device kind ``peaks.json`` lacks;
2. turns JAX's persistent compilation cache on (``.jax_cache/`` in the
   checkout unless ``JAX_COMPILATION_CACHE_DIR`` is set);
3. builds the cell from its files: ``BENCHMARK.json`` names the
   configuration file and the traffic mix (``traffic/<mix>.json``, whose
   ``kind`` names the generator ``traffic/<kind>.py``); the configuration's
   ``model.name`` names the model plug-in ``models/<name>.py`` (its widths,
   weights, reference layer and FLOP count; ``models/gcn.py`` lists what a
   plug-in defines); each per-layer metric is read by ``metrics/<name>.py``,
   which may list in ``COUNTERS`` the report fields it needs beyond
   :data:`COUNTERS`;
4. makes the graph and the weights from the seed, prepares the engine
   (presampling, the Eq. 1 split, the cache fill) and warms every shape the
   window will use: that is ``setup_s``;
5. measures for ``--seconds`` (``--trace 1``: a shorter traced window);
6. checks, on batches the window served, what ``check.py`` compares;
7. prints the result as the last line of standard output.

The cells are offline (closed loop): the window drives
``GNNInferenceEngine.run`` in whole chunks of batches until it has passed.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import importlib.util
import itertools
import json
import pathlib
import shutil
import sys
import tempfile
import time

import numpy as np

import check
import graphdata
import trace_reduce
import work

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
TRACE_SECONDS = 5.0  # length of the traced window (--trace 1)
PROBE_BATCHES = 8  # window batches whose unique-frontier buckets set-up warms
EDGE_MARGIN = 0.02  # a probe this close to a bucket edge also warms the next bucket


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_module(path: pathlib.Path, tag: str):
    """Import one plug-in file (a traffic generator, a model or a metric
    reader)."""
    spec = importlib.util.spec_from_file_location(f"chipbench_{tag}_{path.stem}", path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ------------------------------------------------------------------ cells
@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    generator: object  # the traffic module
    model: object  # the model plug-in module
    end_to_end: list[dict]
    per_layer: list[dict]
    readers: dict  # per-layer metric name -> reader module
    counters: tuple  # report fields summed over the window


def resolve_cell(name: str, root: pathlib.Path = ROOT, here: pathlib.Path = HERE) -> Cell:
    """Everything a cell needs, found by the names in ``BENCHMARK.json``
    (``root``) and the benchmark's directory (``here``)."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json (have {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    traffic = json.loads((here / "traffic" / f"{w['traffic']}.json").read_text())
    generator = load_module(here / "traffic" / f"{traffic['kind']}.py", "traffic")

    def applies(metric):
        return "workloads" not in metric or name in metric["workloads"]

    per_layer = [m for m in bench["per_layer"] if applies(m)]
    readers = {m["name"]: load_module(here / "metrics" / f"{m['name']}.py", "metric")
               for m in per_layer}
    counters = COUNTERS + tuple(n for r in readers.values() for n in getattr(r, "COUNTERS", ()))
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config=config,
        traffic=traffic,
        generator=generator,
        model=load_module(here / "models" / f"{config['model']['name']}.py", "model"),
        end_to_end=[m for m in bench["end_to_end"] if applies(m)],
        per_layer=per_layer,
        readers=readers,
        counters=tuple(dict.fromkeys(counters)),
    )


# --------------------------------------------------------------- compiles
class CompileCounter:
    """Programs built, in all and while armed (the window): JAX's
    backend-compile event marks each one, whether XLA compiled it or it was
    loaded from the persistent cache (the cache-hit event marks those), and
    either means a shape met for the first time in the process."""

    def __init__(self):
        import jax

        self.armed = False
        self.compiles = 0
        self.cache_loads = 0
        self.all_compiles = 0
        self.all_cache_loads = 0
        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += duration
            self.all_compiles += 1
            if self.armed:
                self.compiles += 1

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.all_cache_loads += 1
            self.cache_loads += self.armed


# ------------------------------------------------------------------ set-up
@dataclasses.dataclass
class System:
    graph: graphdata.GraphArrays
    dataset: object
    engine: object
    ecfg: object
    params_np: list
    dims: list[int]  # layer widths: features, hidden..., classes
    parts: dict  # set-up seconds by part


def build(config: dict, model, seed: int) -> System:
    """The graph, the weights (from the model plug-in ``model``) and the
    prepared engine of one run."""
    from repro.core.config import EngineConfig
    from repro.runtime.gnn_engine import GNNInferenceEngine

    parts = {}
    t = time.perf_counter()
    dspec = config["dataset"]
    graph = graphdata.make_graph(dspec, seed)
    dataset = graphdata.as_program_dataset(dspec, graph)
    parts["data_s"] = time.perf_counter() - t

    t = time.perf_counter()
    m = config["model"]
    dims = model.widths(m, dspec)
    params = model.make_weights(dims, seed, m)
    params_np = [{k: np.asarray(v) for k, v in layer.items()} for layer in params]
    engine = GNNInferenceEngine(
        dataset,
        **model.engine_args(m),
        fanouts=tuple(config["fanouts"]),
        batch_size=int(config["batch_size"]),
        seed=seed,
        params=params,
    )
    ecfg = EngineConfig(**config["engine"])
    budget = int((graph.features.nbytes + graph.row_index.shape[0] * 4) * config["cache_fraction"])
    engine.prepare(
        config["policy"],
        config=ecfg,
        total_cache_bytes=budget,
        n_presample=int(config["n_presample"]),
    )
    parts["prepare_s"] = time.perf_counter() - t
    return System(graph, dataset, engine, ecfg, params_np, dims, parts)


def unique_count(system: System, key, seeds) -> tuple[int, int]:
    """``(num_unique, frontier)`` of ``seeds`` sampled with ``key``."""
    import jax.numpy as jnp

    from repro.graph.sampling import sample_blocks

    pipe = system.engine.pipeline
    block = sample_blocks(
        key, pipe.caches.dgraph, jnp.asarray(seeds), system.engine.fanouts,
        dedup=True, dedup_pad_id=pipe.caches.store.pad_node_id(),
    )
    return int(block.dedup.num_unique), int(block.input_nodes.shape[0])


def craft(system: System, key, base: np.ndarray, bucket: int) -> np.ndarray | None:
    """A batch whose unique frontier falls in ``bucket`` under ``key``, or
    None.  Smaller buckets: ``base``'s first ``k`` seeds, repeated (a
    search over ``k``); larger ones: other test-set batches, drawn from a
    fixed stream, up to 32 of them."""
    from repro.graph.sampling import pow2_bucket

    def bucket_of(seeds):
        nu, s = unique_count(system, key, seeds)
        return pow2_bucket(nu, s)

    lo, hi = 1, base.shape[0]
    while lo <= hi:
        k = (lo + hi) // 2
        seeds = np.resize(base[:k], base.shape[0])
        b = bucket_of(seeds)
        if b == bucket:
            return seeds
        lo, hi = (k + 1, hi) if b < bucket else (lo, k - 1)
    rng = np.random.default_rng(0xB0C)
    test = system.dataset.test_idx
    for _ in range(32):
        seeds = rng.choice(test, size=base.shape[0], replace=test.shape[0] < base.shape[0])
        seeds = seeds.astype(base.dtype)
        if bucket_of(seeds) == bucket:
            return seeds
    return None


def warm(system: System, probes: list[tuple[np.ndarray, int, int]]) -> list[int]:
    """Compile every program the window will run.

    ``probes`` are window batches as ``(seeds, key_seed, index)``.  Without
    dedup one run of two of them covers every shape.  Under dedup the
    gather and forward specialise on the pow2 bucket of each batch's unique
    count, so every bucket the probes land in is warmed, and the next
    bucket too where a probe lies within ``EDGE_MARGIN`` of an edge.  A
    bucket is warmed by the engine's own ``warmup`` (which also builds every
    prefetch-pack size for it; it samples with ``PRNGKey(seed + 1)``) and by
    one batch through ``run`` (a run's first batch samples with the first
    split of that key)."""
    import jax

    from repro.graph.sampling import pow2_bucket

    eng, ecfg = system.engine, system.ecfg
    if not ecfg.dedup:
        eng.run(config=ecfg, batches=[p[0] for p in probes[:2]], warmup=True)
        return []
    base = jax.random.PRNGKey(eng.seed + 1)
    first_split = jax.random.split(base)[1]
    found: dict[int, np.ndarray] = {}
    nus = []
    for seeds, key_seed, index in probes:
        key = jax.random.PRNGKey(key_seed + 1)
        for _ in range(index + 1):
            key, sub = jax.random.split(key)
        nu, s = unique_count(system, sub, seeds)
        nus.append(nu)
        b = pow2_bucket(nu, s)
        found.setdefault(b, seeds)
        if nu > (1 - EDGE_MARGIN) * b and b < s:
            found.setdefault(pow2_bucket(b + 1, s), seeds)
        if nu < (1 + EDGE_MARGIN) * (b // 2) and b > 1:
            found.setdefault(b // 2, seeds)
    log(f"warm-up: probe unique counts {nus} of {s}; buckets {sorted(found)}")
    warmed = []
    for b, seeds in sorted(found.items()):
        for key, use in ((base, "warmup"), (first_split, "run")):
            nu, s = unique_count(system, key, seeds)
            batch = seeds if pow2_bucket(nu, s) == b else craft(system, key, seeds, b)
            if batch is None:
                log(f"warm-up: no batch reaches bucket {b} for the {use} key; not warmed")
                break
            if use == "warmup":
                eng.warmup(batch)
            else:
                eng.run(config=ecfg, batches=[batch], warmup=False)
        else:
            warmed.append(b)
    return warmed


# ------------------------------------------------------------------ window
@dataclasses.dataclass
class Window:
    elapsed_s: float
    served: list  # check.Served
    attempted: int
    failed: int
    counters: dict
    nodes: int


COUNTERS = ("gathered_rows", "prefetched_rows", "unique_rows", "feat_hits", "feat_lookups",
            "adj_hits", "adj_lookups", "kernel_fallbacks", "degraded_batches")


def check_counters(names) -> None:
    """Refuse a counter that ``InferenceReport`` has no field for."""
    from repro.runtime.gnn_engine import InferenceReport

    fields = {f.name for f in dataclasses.fields(InferenceReport)}
    missing = [n for n in names if n not in fields]
    if missing:
        raise KeyError(f"counters {missing} are not fields of InferenceReport")


def run_closed(system: System, gen, chunk: int, seconds: float, tracer,
               counter_names: tuple) -> Window:
    eng, ecfg = system.engine, system.ecfg
    served, counters = [], dict.fromkeys(counter_names, 0)
    batches = 0
    t0 = time.perf_counter()
    while True:
        seeds = [next(gen) for _ in range(chunk)]
        rep = eng.run(config=ecfg, batches=seeds, warmup=False, collect_outputs=True,
                      tracer=tracer)
        for name in counter_names:
            counters[name] += getattr(rep, name)
        chain = len(served)
        served.extend(
            check.Served(s, o, eng.seed, chain, i) for i, (s, o) in enumerate(zip(seeds, eng.last_outputs))
        )
        batches += len(seeds)
        if time.perf_counter() - t0 >= seconds:
            break
    elapsed = time.perf_counter() - t0
    failed = sum(1 for s in served if not np.isfinite(s.logits).all()) + counters["degraded_batches"]
    nodes = sum(int(s.seeds.shape[0]) for s in served)
    return Window(elapsed, served, batches, failed, counters, nodes=nodes)


# ------------------------------------------------------------------ result
def end_to_end(cell: Cell, win: Window, setup_s: float) -> dict:
    values = {"setup_s": setup_s, "nodes_per_s": win.nodes / win.elapsed_s}
    return {m["name"]: {"value": values[m["name"]], "unit": m["unit"]} for m in cell.end_to_end}


def device_info(chips: int) -> dict:
    import jax

    devs = jax.devices()
    peak = 0
    for d in devs[:chips]:
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs),
            "memory_peak_bytes": peak}


def measure(
    cell: Cell,
    seed: int,
    seconds: float,
    trace: bool,
    *,
    peaks: dict,
    t_start: float | None = None,
    matmul_precision: str | None = None,
    counter: CompileCounter | None = None,
) -> dict:
    """One run of ``cell``; returns the result object (see the module
    docstring).  ``matmul_precision`` overrides the configuration's (the
    control runs the program one step below it)."""
    import jax

    t_start = time.perf_counter() if t_start is None else t_start
    counter = counter or CompileCounter()
    cfg = cell.config
    precision = matmul_precision or cfg["precision"]["matmul"]
    with jax.default_matmul_precision(precision):
        return _measure(cell, seed, seconds, trace, peaks, t_start, counter)


def _measure(cell, seed, seconds, trace, peaks, t_start, counter) -> dict:
    import jax

    from repro.core.trace import Tracer
    from repro.graph.sampling import pow2_bucket, sample_blocks

    cfg, traffic, gen_mod = cell.config, cell.traffic, cell.generator
    batch = int(cfg["batch_size"])
    check_counters(cell.counters)
    system = build(cfg, cell.model, seed)
    eng = system.engine

    # The window's traffic, and the batches set-up warms.
    window_s = min(float(seconds), TRACE_SECONDS) if trace else float(seconds)
    gen = gen_mod.batches(system.dataset.test_idx, batch_size=batch, seed=seed, params=traffic)
    head = [next(gen) for _ in range(PROBE_BATCHES)]
    gen = itertools.chain(head, gen)
    probes = [(s, eng.seed, i) for i, s in enumerate(head)]
    t = time.perf_counter()
    warmed = warm(system, probes)
    system.parts["warm_s"] = time.perf_counter() - t
    setup_s = time.perf_counter() - t_start
    alloc = eng.pipeline.caches.allocation
    log(f"setup: {json.dumps(system.parts)} total_s={setup_s} warmed_buckets={warmed} "
        f"compile_s={counter.compile_s} programs_built={counter.all_compiles} "
        f"loaded_from_cache={counter.all_cache_loads}")
    log(f"eq1_split: adj_bytes={alloc.adj_bytes} feat_bytes={alloc.feat_bytes} "
        f"total_bytes={alloc.total_bytes} sample_fraction={alloc.sample_fraction}")

    # The window.
    tracer, trace_dir = None, None
    if trace:
        tracer = Tracer(jax_annotations=True)
        trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    counter.armed = True
    try:
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
            win = run_closed(system, gen, int(traffic["chunk_batches"]), window_s, tracer,
                             cell.counters)
    finally:
        counter.armed = False
        if trace:
            jax.profiler.stop_trace()
    c = win.counters
    log(f"window: elapsed_s={win.elapsed_s} batches={len(win.served)} attempted={win.attempted} "
        f"failed={win.failed} window_compiles={counter.compiles} "
        f"window_cache_loads={counter.cache_loads}")
    log(f"hit_rates: feat={c['feat_hits'] / max(c['feat_lookups'], 1)} "
        f"adj={c['adj_hits'] / max(c['adj_lookups'], 1)}; rows: gathered={c['gathered_rows']} "
        f"unique={c['unique_rows']} prefetched={c['prefetched_rows']}")
    device = device_info(cell.chips)
    log(f"peak_bytes_in_use={device['memory_peak_bytes']}")

    # Per-layer metrics (traced runs) or end-to-end metrics.
    result = {"correct": False, "attempted": win.attempted, "failed": win.failed}
    if trace:
        profile = trace_reduce.load_xplane(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        layer_map = json.loads((HERE / "layers.json").read_text())
        layers = {k: v / 1e9 for k, v in trace_reduce.layer_ns(profile, layer_map).items()}
        tr = {"busy_s": trace_reduce.busy_ns(profile) / 1e9,
              "window_s": trace_reduce.window_ns(profile) / 1e9, "layer_s": layers}
        log(f"trace: busy_s={tr['busy_s']} window_s={tr['window_s']} layer_s={json.dumps(layers)}")
        ctx = {
            "trace": tr,
            "batches": len(win.served),
            "counters": c,
            "row_bytes": int(cfg["dataset"]["feat_dim"]) * 4,
            "flops_per_batch": cell.model.forward_flops(
                system.dims, cfg["fanouts"], batch, cfg["model"]
            ),
            "peaks": peaks,
            # what a new model's readers need: every op's device seconds by
            # "program:op", the program's spans on every host thread
            # (trace_reduce's ``threads``), the busy intervals and window in
            # ns, and the shapes to count a kernel's work from
            "ops": {k: v / 1e9 for k, v in trace_reduce.top_ops(profile, None)},
            "spans": profile["threads"],
            "busy": trace_reduce.busy_union(profile),
            "window": profile["window"],
            "model": cell.model,
            "config": cfg,
            "dims": system.dims,
            "batch": batch,
        }
        log(f"trace: flops_per_batch={ctx['flops_per_batch']} spans={len(ctx['spans'])} "
            f"counters={json.dumps(c)}")
        metrics = {}
        for meta in cell.per_layer:
            value = cell.readers[meta["name"]].read(ctx)
            if value is None:
                log(f"metric {meta['name']}: nothing to read, left out")
            else:
                metrics[meta["name"]] = {"value": value, "unit": meta["unit"]}
        device["busy_s"] = tr["busy_s"]
        device["window_s"] = tr["window_s"]
        result["metrics"] = metrics
        result["device"] = device
        result["breakdown"] = {
            "device_ops": [[k, v] for k, v in list(ctx["ops"].items())[:10]],
            "idle_gaps": [[k, v / 1e9] for k, v in trace_reduce.idle_gaps(profile)[:10]],
        }
    else:
        result["metrics"] = end_to_end(cell, win, setup_s)
        result["device"] = device

    # The check, on batches the window served, drawn from the seed.
    t = time.perf_counter()
    rng = np.random.default_rng([int(seed), 0xC4EC])
    k = min(int(cfg["check"]["batches"]), len(win.served))
    picked = [win.served[i] for i in sorted(rng.choice(len(win.served), size=k, replace=False))]
    del win
    gc.collect()
    pipe = eng.pipeline
    readings = check.check_batches(
        picked,
        model=cell.model,
        params_np=system.params_np,
        graph=system.graph,
        fanouts=tuple(cfg["fanouts"]),
        store=pipe.caches.store,
        sample_blocks=sample_blocks,
        pow2_bucket=pow2_bucket,
        dgraph=pipe.caches.dgraph,
        dedup=bool(system.ecfg.dedup),
    )
    ok, numbers = check.verdict(readings, cfg["check"]["limits"])
    log(f"check_s={time.perf_counter() - t}")
    result["correct"] = ok
    result["checks"] = numbers
    return result


# -------------------------------------------------------------------- main
def print_result(result: dict) -> None:
    for name, n in result["checks"].items():
        log(f"check {name}: {n['value']} (limit {n['limit']})")
    ordered = {k: result[k] for k in ("correct", "attempted", "failed", "metrics", "device")}
    if "breakdown" in result:
        ordered["breakdown"] = result["breakdown"]
    ordered["checks"] = result["checks"]
    print(json.dumps(ordered), flush=True)


def parse_args(argv):
    ap = argparse.ArgumentParser(description="DCI chip benchmark: one cell, one seed.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, *, t_start: float | None = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    args = parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        log(f"chipbench: no repro package under {ROOT / 'src'}")
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    cell = resolve_cell(args.workload)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        log(f"chipbench: first device is {devices[0].platform}, not a TPU; no result")
        return 3
    if len(devices) < cell.chips:
        log(f"chipbench: {len(devices)} devices, the cell needs {cell.chips}; no result")
        return 3
    try:
        peaks = work.peaks(devices[0].device_kind)
    except work.UnknownDevice as err:
        log(f"chipbench: {err}; no result")
        return 3

    from repro.utils.compile_cache import enable_compile_cache

    log(f"device: {devices[0].device_kind} x{len(devices)}; compile cache {enable_compile_cache()}")
    counter = CompileCounter()
    result = measure(cell, args.seed, args.seconds, bool(args.trace), peaks=peaks,
                     t_start=t_start, counter=counter)
    print_result(result)
    return 0
