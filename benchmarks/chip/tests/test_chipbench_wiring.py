"""Every cell resolves from files found by name, and the contract's shape of
``BENCHMARK.json`` holds (CPU)."""

import json
import os
import pathlib
import re
import shutil
import subprocess
import sys

import pytest

CHIP = pathlib.Path(__file__).resolve().parents[1]
ROOT = CHIP.parents[1]
sys.path.insert(0, str(CHIP))

import harness  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def reports(cell: str, metric: dict) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_to_its_files(cell):
    c = harness.resolve_cell(cell)
    assert c.config["name"] == next(w["config"] for w in BENCH["workloads"] if w["name"] == cell)
    assert callable(c.generator.batches)
    assert {m["name"] for m in c.per_layer} == set(c.readers)
    for reader in c.readers.values():
        assert callable(reader.read) and UNIT.match(reader.UNIT)
    assert any(m["name"] == "setup_s" for m in c.end_to_end)
    assert len(c.end_to_end) >= 2 and c.per_layer


def test_names_and_units_use_the_allowed_characters():
    names = [c["name"] for c in BENCH["configs"]] + CELLS
    names += [w[k] for w in BENCH["workloads"] for k in ("config", "traffic")]
    names += [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    names += [k for c in BENCH["configs"] for k in c["reduced"]]
    assert all(NAME.match(n) for n in names), [n for n in names if not NAME.match(n)]
    units = [m["unit"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert all(UNIT.match(u) for u in units)
    metric_names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(set(metric_names)) == len(metric_names)


def test_each_layer_metric_moves_a_metric_its_cells_report():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        for cell in CELLS:
            if reports(cell, m):
                assert reports(cell, e2e[m["moves"]]), (m["name"], cell)


def test_every_config_is_used_and_its_file_is_its_own():
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}
    files = [c["file"] for c in BENCH["configs"]]
    assert len(set(files)) == len(files)
    assert all(f.startswith(BENCH["paths"][0] + "/") for f in files)


def test_a_new_cell_needs_only_new_files_and_entries(tmp_path):
    """Copy the benchmark, add a configuration, a traffic mix and a metric as
    files plus entries, and the new cell resolves with no edit elsewhere:
    the mix is a data file for an existing generator."""
    chip = tmp_path / "benchmarks" / "chip"
    shutil.copytree(CHIP, chip, ignore=shutil.ignore_patterns("tests", "__pycache__"))
    bench = json.loads(json.dumps(BENCH))
    cfg = json.loads((CHIP / "configs" / "gcn-reddit.json").read_text())
    cfg["name"] = "gcn-yelp"
    (chip / "configs" / "gcn-yelp.json").write_text(json.dumps(cfg))
    (chip / "traffic" / "long_runs.json").write_text(json.dumps(
        {"kind": "closed_loop", "chunk_batches": 128}
    ))
    (chip / "metrics" / "drain_device_ms.py").write_text(
        'UNIT = "ms"\n\n\ndef read(ctx):\n    return None\n'
    )
    bench["configs"].append({"name": "gcn-yelp", "source": "https://arxiv.org/abs/2503.01281",
                             "file": "benchmarks/chip/configs/gcn-yelp.json", "reduced": [],
                             "why": "test"})
    bench["workloads"].append({"name": "gcn-yelp.long_runs", "config": "gcn-yelp",
                               "traffic": "long_runs", "chips": 1, "why": "test"})
    for m in bench["end_to_end"]:
        if "workloads" in m:
            m["workloads"].append("gcn-yelp.long_runs")
    bench["per_layer"].append({"name": "drain_device_ms", "unit": "ms", "better": "lower",
                               "source": "device_trace", "layer": "executor",
                               "moves": "nodes_per_s", "workloads": ["gcn-yelp.long_runs"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = harness.resolve_cell("gcn-yelp.long_runs", root=tmp_path, here=chip)
    assert cell.config["name"] == "gcn-yelp"
    assert cell.traffic["chunk_batches"] == 128 and callable(cell.generator.batches)
    assert set(cell.readers) == {"drain_device_ms"}
    assert sorted(m["name"] for m in cell.end_to_end) == ["nodes_per_s", "setup_s"]


def test_run_without_a_tpu_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(CHIP / "run.py"), "--workload", CELLS[0], "--seed", "2147483653",
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    assert "correct" not in proc.stdout
    assert "not a TPU" in proc.stderr


def test_run_outside_a_checkout_exits_nonzero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(CHIP, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    proc = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload", CELLS[0], "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    assert "correct" not in proc.stdout
