"""The trace reduction and the metric arithmetic, on small profiles (CPU)."""

import collections
import json
import pathlib
import sys

import pytest

CHIP = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(CHIP))

import harness  # noqa: E402
import trace_reduce  # noqa: E402
import work  # noqa: E402

LAYERS = json.loads((CHIP / "layers.json").read_text())
DATA = CHIP / "tests" / "data"


def profile(ops, host=(), window=(0, 100)):
    return {"window": list(window), "ops": [list(o) for o in ops], "host": [list(h) for h in host]}


TPU = "/device:TPU:0"
SMALL = profile(
    ops=[
        (TPU, "jit_sample_blocks(3)", "fusion.1", 10, 10),  # 10..20
        (TPU, "jit_sample_blocks(3)", "sort.2", 15, 10),  # overlaps: union 10..25
        (TPU, "jit_gather(9)", "gather.1", 30, 5),  # 30..35
        (TPU, "jit_forward(7)", "fusion.4", 50, 20),  # 50..70
        (TPU, "jit_forward(7)", "fusion.5", 95, 10),  # clipped to 95..100
        (TPU, "jit_forward(7)", "fusion.6", 150, 10),  # outside the window
    ],
    host=[
        ("feature", 25, 5),  # covers the gap 25..30
        ("prefetch", 35, 15),  # covers the gap 35..50
        ("batch", 0, 100),  # encloses everything: never the innermost
    ],
)


def test_busy_is_the_union_of_ops_inside_the_window():
    # 10..25, 30..35, 50..70, 95..100
    assert trace_reduce.busy_ns(SMALL) == pytest.approx(15 + 5 + 20 + 5)
    assert trace_reduce.window_ns(SMALL) == 100


def test_busy_is_averaged_over_devices():
    two = profile(ops=[(TPU, "m", "a", 0, 40), ("/device:TPU:1", "m", "a", 0, 20)])
    assert trace_reduce.busy_ns(two) == pytest.approx(30)


def test_idle_share_reader_matches_the_busy_union():
    reader = harness.load_module(CHIP / "metrics" / "device_idle_share.offline.py", "metric")
    ctx = {"trace": {"busy_s": trace_reduce.busy_ns(SMALL) / 1e9,
                     "window_s": trace_reduce.window_ns(SMALL) / 1e9, "layer_s": {}}}
    assert reader.read(ctx) == pytest.approx(55.0)
    assert reader.read({"trace": {"busy_s": 0.0, "window_s": 1.0, "layer_s": {}}}) is None


def test_layers_are_bucketed_by_program_name():
    layers = trace_reduce.layer_ns(SMALL, LAYERS)
    assert layers["sampling"] == pytest.approx(20)  # op time, not union: 10 + 10
    assert layers["feature"] == pytest.approx(5)
    assert layers["forward"] == pytest.approx(25)


def test_a_missing_layer_fails_rather_than_reading_zero():
    no_forward = profile(ops=[o for o in SMALL["ops"] if "forward" not in o[1]])
    with pytest.raises(trace_reduce.MissingLayer, match="forward"):
        trace_reduce.layer_ns(no_forward, LAYERS)


def test_idle_gaps_are_labelled_by_the_innermost_host_span():
    gaps = dict(trace_reduce.idle_gaps(SMALL))
    assert gaps["prefetch"] == pytest.approx(15)  # 35..50
    assert gaps["feature"] == pytest.approx(5)  # 25..30
    assert gaps["batch"] == pytest.approx(10 + 25)  # 0..10 and 70..95
    assert sum(gaps.values()) == pytest.approx(100 - trace_reduce.busy_ns(SMALL))


def test_top_ops_rank_by_time_in_the_window():
    top = trace_reduce.top_ops(SMALL, 2)
    assert top[0] == ("jit_forward(7):fusion.4", pytest.approx(20))
    assert len(top) == 2


def test_step_mfu_and_gather_hbm_share_from_known_counts():
    peaks = {"flops_per_s": 200e12, "hbm_bytes_per_s": 800e9}
    ctx = {
        "trace": {"busy_s": 0.5, "window_s": 1.0,
                  "layer_s": {"feature": 0.002, "sampling": 0.1, "forward": 0.05}},
        "batches": 10,
        "counters": {"gathered_rows": 1000, "prefetched_rows": 600},
        "row_bytes": 400,
        "flops_per_batch": 4e9,
        "peaks": peaks,
    }
    mfu = harness.load_module(CHIP / "metrics" / "step_mfu.py", "metric")
    hbm = harness.load_module(CHIP / "metrics" / "gather_hbm_share.py", "metric")
    # 10 batches x 4 GFLOP in 1 s over 200 TFLOP/s
    assert mfu.read(ctx) == pytest.approx(100 * 40e9 / 200e12)
    # (1000 + 600) rows x 400 B, read and written, in 2 ms over 800 GB/s
    assert hbm.read(ctx) == pytest.approx(100 * 2 * 1600 * 400 / 0.002 / 800e9)
    per_batch = harness.load_module(CHIP / "metrics" / "sample_device_ms.py", "metric")
    assert per_batch.read(ctx) == pytest.approx(10.0)


def model(name):
    return harness.load_module(CHIP / "models" / f"{name}.py", "model")


def test_forward_flops_of_the_table_iii_shapes():
    # 1,081,344 -> 67,584 -> 6,144 -> 1,024 rows; GCN: one product a layer
    dims = [602, 128, 128, 41]
    flops = model("gcn").forward_flops(dims, (15, 10, 5), 1024, {})
    products = 2.0 * (67584 * 602 * 128 + 6144 * 128 * 128 + 1024 * 128 * 41)
    assert products < flops < 1.1 * products
    sage = model("graphsage").forward_flops([100, 128, 128, 47], (15, 10, 5), 1024, {})
    assert sage == pytest.approx(
        67584 * 14 * 100 + 4 * 67584 * 100 * 128 + 2 * 67584 * 128
        + 6144 * 9 * 128 + 4 * 6144 * 128 * 128 + 2 * 6144 * 128
        + 1024 * 4 * 128 + 4 * 1024 * 128 * 47 + 2 * 1024 * 47
    )


def test_forward_flops_are_the_floats_counted_before_the_plugins():
    # the harness's count before the models became plug-ins, float for float
    assert model("gcn").forward_flops([602, 128, 128, 41], (15, 10, 5), 1024, {}) == 11297465344.0
    sage = model("graphsage").forward_flops([100, 128, 128, 47], (15, 10, 5), 1024, {})
    assert sage == 4008785920.0


def test_peaks_refuse_an_unknown_device_kind():
    assert work.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(work.UnknownDevice):
        work.peaks("cpu")


def test_a_recorded_chip_trace_reduces():
    recorded = DATA / "products_trace_small.json"
    prof = json.loads(recorded.read_text())
    busy, window = trace_reduce.busy_ns(prof), trace_reduce.window_ns(prof)
    assert 0 < busy <= window
    layers = trace_reduce.layer_ns(prof, LAYERS)
    assert set(layers) >= {"sampling", "forward", "feature"}
    gaps = trace_reduce.idle_gaps(prof)
    assert sum(v for _, v in gaps) == pytest.approx(window - busy)
    # the program's spans on its two threads: the window's and the pack worker's
    line = trace_reduce.window_line(prof["threads"])
    packs = [t for t in prof["threads"] if t[1] == "prefetch:pack"]
    assert packs and all(t[0] != line and t[4] is not None for t in packs)
    ctx = {"spans": prof["threads"], "busy": trace_reduce.busy_union(prof),
           "window": prof["window"], "batches": 1}
    for name in ("prefetch_idle_ms", "miss_pack_ms", "miss_put_ms", "host_sync_ms"):
        assert 0 < reader(name).read(ctx) <= window / 1e6, name


# ----------------------------------------------------- the program's spans
M = 1e6  # ns in a ms
WINDOW_LINE, WORKER_LINE, OTHER_LINE = 0, 1, 2
SPANS = profile(
    ops=[(TPU, "jit_sample_blocks(3)", "fusion.1", 100 * M, 100 * M),  # busy 100..200
         (TPU, "jit_forward(7)", "fusion.4", 400 * M, 300 * M),  # busy 400..700
         (TPU, "jit_gather(9)", "gather.1", 900 * M, 50 * M)],  # busy 900..950
    window=(0, 1000 * M),
)
SPANS["threads"] = [
    [line, name, a * M, d * M, batch] for line, name, a, d, batch in [
        (WINDOW_LINE, trace_reduce.WINDOW_SPAN, 0, 1000, None),
        (WINDOW_LINE, "prefetch", 50, 250, 0),  # 50..300: 100 busy, 150 idle
        (WINDOW_LINE, "prefetch:pull", 50, 30, 0),
        (WINDOW_LINE, "prefetch:join", 200, 100, 0),
        (WINDOW_LINE, "sync:num_unique", 300, 150, 0),
        (WINDOW_LINE, "prefetch", 600, 200, 1),  # 600..800: 100 busy, 100 idle
        (WINDOW_LINE, "prefetch:scan", 600, 50, 1),
        (WINDOW_LINE, "sync:logits", 800, 20, 1),
        (WINDOW_LINE, "PjitFunction(forward)", 390, 5, None),
        (WORKER_LINE, "prefetch:pack", 60, 130, 0),
        (WORKER_LINE, "prefetch:put", 190, 10, 0),
        (WORKER_LINE, "prefetch:pack", 990, 20, 2),  # 10 of it inside the window
        (OTHER_LINE, "prefetch:index", 960, 30, None),  # not the window's thread
    ]
]


def span_ctx(prof, batches=2, counters=None):
    return {"spans": prof["threads"], "busy": trace_reduce.busy_union(prof),
            "window": prof["window"], "batches": batches, "counters": counters or {}}


def reader(name):
    return harness.load_module(CHIP / "metrics" / f"{name}.py", "metric")


def test_idle_within_spans_is_their_cover_less_the_busy_union():
    busy = trace_reduce.busy_union(SPANS)
    assert busy == [(100 * M, 200 * M), (400 * M, 700 * M), (900 * M, 950 * M)]
    idle = trace_reduce.idle_within(busy, SPANS["window"], [(50 * M, 300 * M), (250 * M, 420 * M)])
    assert idle == pytest.approx((370 - 100 - 20) * M)  # 50..420 less 100..200 and 400..420
    assert trace_reduce.idle_within(busy, SPANS["window"], [(950 * M, 1200 * M)]) == 50 * M
    assert trace_reduce.window_line(SPANS["threads"]) == WINDOW_LINE


@pytest.mark.parametrize("name,want", [
    ("prefetch_idle_ms", (150 + 100) / 2),  # window line only: not the other thread's index
    ("miss_pack_ms", (130 + 10) / 2),  # clipped to the window
    ("miss_put_ms", 10 / 2),
    ("host_sync_ms", (30 + 150 + 20) / 2),  # pull, num_unique, logits
])
def test_span_readers_on_known_spans(name, want):
    assert reader(name).read(span_ctx(SPANS)) == pytest.approx(want)
    assert reader(name).read(span_ctx(profile(ops=SPANS["ops"]) | {"threads": []})) is None


def test_staged_row_use_from_the_counters():
    r = reader("staged_row_use")
    assert set(r.COUNTERS) >= {"staged_rows", "prefetched_rows"}
    ctx = span_ctx(SPANS, counters={"prefetched_rows": 600, "staged_rows": 1000})
    assert r.read(ctx) == pytest.approx(60.0)
    assert r.read(span_ctx(SPANS, counters={"prefetched_rows": 0, "staged_rows": 0})) is None


def test_trim_keeps_the_program_threads_within_their_caps():
    big = dict(SPANS, threads=SPANS["threads"] + [
        [line, f"span{i}", (10 + i) * M, M, i if line != OTHER_LINE else None]
        for line in (WINDOW_LINE, WORKER_LINE, OTHER_LINE) for i in range(20)
    ])
    small = trace_reduce.trim(big, max_ops=2, max_host=5)
    end = small["window"][1]
    assert end == 700 * M  # the end of the first two ops
    lines = collections.Counter(t[0] for t in small["threads"])
    assert set(lines) == {WINDOW_LINE, WORKER_LINE}  # the other thread carries no batch
    assert max(lines.values()) <= 5
    assert all(t[2] < end for t in small["threads"])
    assert len(small["ops"]) <= 2 and len(small["host"]) <= 5


class _Event:
    def __init__(self, name, start, dur, **stats):
        self.name, self.start_ns, self.duration_ns = name, start, dur
        self.stats = list(stats.items())


class _Line:
    def __init__(self, name, events):
        self.name, self.events = name, events


class _Plane:
    def __init__(self, name, lines):
        self.name, self.lines = name, lines


def _fake_profile(worker: bool):
    window_line = _Line("python", [
        _Event(trace_reduce.WINDOW_SPAN, 0, 1000),
        _Event("prefetch", 10, 300, batch=0),
        _Event("prefetch:join", 200, 110, batch=0),
        _Event("$core.py:1 fn", 20, 5),
    ])
    lines = [window_line] + ([_Line("python", [_Event("prefetch:pack", 20, 150, batch=7)])]
                             if worker else [])
    device = _Plane("/device:TPU:0", [
        _Line("XLA Ops", [_Event("fusion.1 = f32[2] fusion()", 400, 100, hlo_module="jit_f")]),
    ])
    return type("Data", (), {"planes": [_Plane("/host:CPU", lines), device]})


@pytest.mark.parametrize("worker", [False, True])
def test_load_xplane_keeps_every_thread_apart_by_line(tmp_path, monkeypatch, worker):
    import jax.profiler

    (tmp_path / "t.xplane.pb").write_bytes(b"")

    def load(w):
        fake = type("ProfileData", (), {"from_file": staticmethod(lambda _p: _fake_profile(w))})
        monkeypatch.setattr(jax.profiler, "ProfileData", fake)
        return trace_reduce.load_xplane(str(tmp_path))

    base, prof = load(False), load(worker)
    for key in ("window", "ops", "host"):
        assert prof[key] == base[key]
    assert trace_reduce.idle_gaps(prof) == trace_reduce.idle_gaps(base)
    assert [h[0] for h in prof["host"]] == ["prefetch", "prefetch:join"]
    packs = [t for t in prof["threads"] if t[1] == "prefetch:pack"]
    if worker:
        assert packs == [[1, "prefetch:pack", 20, 150, 7]]
        assert trace_reduce.window_line(prof["threads"]) == 0
    else:
        assert not packs
    assert [0, "prefetch", 10, 300, 0] in prof["threads"]
    assert [0, trace_reduce.WINDOW_SPAN, 0, 1000, None] in prof["threads"]


def test_load_xplane_reads_a_worker_thread_from_a_real_trace(tmp_path):
    """A profile the CPU backend writes: the worker's span sits on a line of
    its own, named ``python`` like the window's, with its batch."""
    import threading

    import jax
    import jax.numpy as jnp

    def pack():
        with jax.profiler.TraceAnnotation("prefetch:pack", batch=3):
            jnp.ones(8).block_until_ready()

    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
            with jax.profiler.TraceAnnotation("prefetch", batch=3):
                worker = threading.Thread(target=pack, name="dci-miss-pack")
                worker.start()
                worker.join(timeout=60)
            with jax.profiler.TraceAnnotation("sync:num_unique", batch=4):
                jnp.zeros(8).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    assert not worker.is_alive()
    prof = trace_reduce.load_xplane(str(tmp_path))
    line = trace_reduce.window_line(prof["threads"])
    by_name = {t[1]: t for t in prof["threads"]}
    assert by_name["prefetch"][0] == line and by_name["prefetch"][4] == 3
    assert by_name["sync:num_unique"][4] == 4
    assert by_name["prefetch:pack"][0] != line and by_name["prefetch:pack"][4] == 3
    assert "prefetch:pack" not in [h[0] for h in prof["host"]]
