"""The trace reduction and the metric arithmetic, on small profiles (CPU)."""

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


def test_forward_flops_of_the_table_iii_shapes():
    # 1,081,344 -> 67,584 -> 6,144 -> 1,024 rows; GCN: one product a layer
    dims = [602, 128, 128, 41]
    flops = work.forward_flops("gcn", dims, (15, 10, 5), 1024)
    products = 2.0 * (67584 * 602 * 128 + 6144 * 128 * 128 + 1024 * 128 * 41)
    assert products < flops < 1.1 * products
    sage = work.forward_flops("graphsage", [100, 128, 128, 47], (15, 10, 5), 1024)
    assert sage == pytest.approx(
        67584 * 14 * 100 + 4 * 67584 * 100 * 128 + 2 * 67584 * 128
        + 6144 * 9 * 128 + 4 * 6144 * 128 * 128 + 2 * 6144 * 128
        + 1024 * 4 * 128 + 4 * 1024 * 128 * 47 + 2 * 1024 * 47
    )


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
