"""The check that decides ``correct``, driven through a whole run at a tiny
size on the CPU: it passes on the program as it is, and comes out false
when the timed path is broken underneath (a neighbour, a gathered row or a
logit altered where it is produced, the bias add dropped, half of the
batch left out) and for the control (float32 products computed one
precision step lower, as three bfloat16 passes)."""

import dataclasses
import json
import pathlib
import sys

import numpy as np
import pytest

CHIP = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(CHIP))
sys.path.insert(0, str(CHIP.parents[1] / "src"))

import check  # noqa: E402
import harness  # noqa: E402
import reference  # noqa: E402

PEAKS = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}
SEED = 2147483905  # past 2**31, as the seeds of real runs are


@pytest.fixture(scope="module")
def cell():
    c = harness.resolve_cell("sage-products.offline")
    tiny = json.loads((CHIP / "tests" / "data" / "tiny-sage.json").read_text())
    # the limits are the real configuration's: the check under test is its
    tiny["check"] = c.config["check"]
    c.config = tiny
    return c


def run(cell, **kw):
    return harness.measure(cell, SEED, 0.5, False, peaks=PEAKS, **kw)


def test_the_program_as_it_is_is_correct(cell):
    res = run(cell)
    assert res["correct"], res["checks"]
    assert res["checks"]["logit_err"]["value"] < res["checks"]["logit_err"]["limit"]
    assert list(res)[-1] == "checks"
    assert res["metrics"]["nodes_per_s"]["value"] > 0


def test_an_altered_logit_is_caught(cell, monkeypatch):
    import repro.models.gnn as gnn

    real = gnn.forward

    def altered(*a, **kw):
        out = real(*a, **kw)
        return out.at[3, 1].add(0.5 * (1.0 + abs(out[3, 1])))

    monkeypatch.setattr(gnn, "forward", altered)
    res = run(cell)
    assert not res["correct"]
    assert res["checks"]["logit_err"]["value"] > res["checks"]["logit_err"]["limit"]


def _no_bias(params, feats, **kw):
    return [dict(layer, b=layer["b"] * 0.0) for layer in params], feats


def _half_the_batch(params, feats, **kw):
    return params, feats


FAULTS = {
    # the forward run without its bias add
    "bias-dropped": (_no_bias, None),
    # only the first half of the batch computed, its rows standing in for the rest
    "half-the-batch": (_half_the_batch, lambda out: out.at[out.shape[0] // 2 :].set(
        out[: out.shape[0] - out.shape[0] // 2])),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_faulty_forward_is_caught(cell, monkeypatch, fault):
    import repro.models.gnn as gnn

    real = gnn.forward
    before, after = FAULTS[fault]

    def faulty(params, feats, **kw):
        params, feats = before(params, feats, **kw)
        out = real(params, feats, **kw)
        return out if after is None else after(out)

    monkeypatch.setattr(gnn, "forward", faulty)
    res = run(cell)
    assert not res["correct"]
    assert res["checks"]["logit_err"]["value"] > res["checks"]["logit_err"]["limit"]


def test_an_altered_neighbour_is_caught(cell, monkeypatch):
    import repro.runtime.gnn_engine as ge

    real = ge.sample_blocks

    def altered(*a, **kw):
        block = real(*a, **kw)
        last = block.frontiers[-1]
        bad = last.at[-1].set((last[-1] + 1) % 3000)
        return type(block)(
            frontiers=block.frontiers[:-1] + (bad,), neighbor_hits=block.neighbor_hits,
            edge_slots=block.edge_slots, fanouts=block.fanouts, dedup=_dedup_of(bad, block),
        )

    def _dedup_of(frontier, block):
        from repro.graph.sampling import dedup_frontier

        return None if block.dedup is None else dedup_frontier(frontier, block.dedup.unique_ids[-1])

    monkeypatch.setattr(ge, "sample_blocks", altered)
    res = run(cell)
    assert not res["correct"]


def test_an_altered_gathered_row_is_caught(cell, monkeypatch):
    from repro.graph.features import FeatureStore

    real = FeatureStore.gather

    def altered(self, indices, **kw):
        feats, hit = real(self, indices, **kw)
        return feats.at[0].add(1.0), hit

    monkeypatch.setattr(FeatureStore, "gather", altered)
    res = run(cell)
    assert not res["correct"]
    assert res["checks"]["bad_rows"]["value"] > 0


def test_the_control_fails_the_logit_limit(cell, monkeypatch):
    """The reference computed with three-pass bfloat16 products, put in
    the program's place, reads above the limit.  At the published fan-outs
    (15,10,5), whose sums the error grows with; on the chip the program's
    own three-pass path reads 3.3e-5 to 3.7e-5 at full size."""
    cell = dataclasses.replace(cell, config=dict(cell.config, fanouts=[15, 10, 5], batch_size=16))
    real = check.check_batches

    def control(served, **kw):
        for s in served:
            block = next(check.replay(
                [s], sample_blocks=kw["sample_blocks"], dgraph=kw["dgraph"],
                fanouts=kw["fanouts"], dedup=kw["dedup"],
                pad_id=kw["store"].pad_node_id(),
            ))[1]
            s.logits = reference.forward(
                kw["model"], kw["params_np"], kw["graph"].features,
                np.asarray(block.input_nodes), s.seeds.shape[0], kw["fanouts"],
                matmul_precision="high",
            )
        return real(served, **kw)

    monkeypatch.setattr(check, "check_batches", control)
    res = run(cell)
    assert not res["correct"]
    assert res["checks"]["logit_err"]["value"] > res["checks"]["logit_err"]["limit"]
