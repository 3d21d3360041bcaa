"""The numpy references against the program's forward, at a tiny size, and
the model plug-ins against what they drew and computed before they were
plug-ins (CPU)."""

import hashlib
import pathlib
import sys

import numpy as np
import pytest

CHIP = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(CHIP))
sys.path.insert(0, str(CHIP.parents[1] / "src"))

import harness  # noqa: E402
import reference  # noqa: E402

FANOUTS = (4, 3, 2)
BATCH = 16
# Both sides compute in float32 on the CPU: the same products summed in a
# different order differ by a few units of the last place (2^-24 relative
# each) over sums of at most a few hundred terms, so a few 1e-6 of the
# logit scale.  A wrong row, neighbour block or layer moves logits by the
# order of the scale itself.
TOL = 1e-5


def plugin(model: str):
    return harness.load_module(CHIP / "models" / f"{model}.py", "model")


def sampled_frontier(num_nodes: int, rng) -> np.ndarray:
    """An input frontier in the sampler's layout, with duplicated ids."""
    size = reference.frontier_sizes(BATCH, FANOUTS)[-1]
    return rng.integers(0, num_nodes, size).astype(np.int32)


@pytest.mark.parametrize("model", ["graphsage", "gcn"])
def test_reference_matches_the_program_forward(model):
    import jax.numpy as jnp

    from repro.models.gnn import forward

    rng = np.random.default_rng(7)
    table = rng.standard_normal((300, 24)).astype(np.float32)
    params = plugin(model).make_weights([24, 32, 32, 5], 3, {})
    params_np = [{k: np.asarray(v) for k, v in p.items()} for p in params]
    ids = sampled_frontier(table.shape[0], rng)
    ref = reference.forward(plugin(model), params_np, table, ids, BATCH, FANOUTS, block=5)
    got = np.asarray(forward(params, jnp.asarray(table[ids]), model=model, fanouts=FANOUTS))
    assert got.shape == ref.shape == (BATCH, 5)
    scale = np.abs(ref).max()
    assert np.abs(got - ref).max() <= TOL * scale


def test_high_precision_control_is_coarser_than_float32():
    rng = np.random.default_rng(1)
    a = rng.standard_normal((64, 100)).astype(np.float32)
    b = rng.standard_normal((100, 32)).astype(np.float32)
    exact = a.astype(np.float64) @ b.astype(np.float64)
    err32 = np.abs(reference.matmul(a, b) - exact).max()
    err_high = np.abs(reference.matmul(a, b, "high") - exact).max()
    assert err_high > 5 * err32
    assert err_high < 1e-3 * np.abs(exact).max()  # still far closer than bfloat16


def test_bf16_rounding_is_to_nearest_even():
    x = np.array([1.0, 1.0 + 2**-8, 1.0 + 3 * 2**-8, -2.5, 1 + 2**-9], np.float32)
    assert reference.to_bf16(x).tolist() == [1.0, 1.0, 1.0 + 2**-6, -2.5, 1.0]


def sha256(arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


# sha256 of every weight array (layers in order, keys sorted) as the
# harness's weight maker drew them before the models became plug-ins
WEIGHTS = {
    ("graphsage", 3): "e73b50ca897322f9562bbedbb7b11b643fffba62a792bcc27a77c7107770521d",
    ("graphsage", 2147483905): "18759b15a776bb3379617afb882748b14e7fbd228ead850c5f3bf5d6e0ea848c",
    ("gcn", 3): "54506eab04f5b9b25b33659aed4f15351a36af7894b1c106ea89600696f3fb76",
    ("gcn", 2147483905): "8750b7ff9f824b159d761d593056f708dd4209f2dea34d32a07dc5b105c43d2a",
}
DIMS = {3: [24, 32, 32, 5], 2147483905: [100, 128, 128, 47]}


@pytest.mark.parametrize("model,seed", sorted(WEIGHTS))
def test_plugin_weights_are_the_ones_drawn_before(model, seed):
    params = plugin(model).make_weights(DIMS[seed], seed, {})
    assert sha256(np.asarray(p[k]) for p in params for k in sorted(p)) == WEIGHTS[model, seed]


# sha256 of the float32 reference logits at the tiny size, before the move
LOGITS = {
    ("graphsage", "highest"): "b8ca65227b0590763b8147f420a4ae3782e5ca4b808c1549c4abd44441f688d0",
    ("graphsage", "high"): "8248c76ea6cfa9ced857d7226abcfa90c11dd06cc5d506569db24fd7053a386d",
    ("gcn", "highest"): "cef4aa0b978f9f122bbd59774ef3da2adab3ac3f48c04bf72d3799fd78aaffa9",
    ("gcn", "high"): "d777057e95e24fac0fc8a61f6d65167a977a46dbead005aae818a5e6601df310",
}


@pytest.mark.parametrize("model,precision", sorted(LOGITS))
def test_reference_logits_are_the_ones_computed_before(model, precision):
    rng = np.random.default_rng(7)
    table = rng.standard_normal((300, 24)).astype(np.float32)
    params = plugin(model).make_weights([24, 32, 32, 5], 3, {})
    params_np = [{k: np.asarray(v) for k, v in p.items()} for p in params]
    ids = sampled_frontier(table.shape[0], rng)
    ref = reference.forward(plugin(model), params_np, table, ids, BATCH, FANOUTS, block=5,
                            matmul_precision=precision)
    assert ref.dtype == np.float32 and ref.shape == (BATCH, 5)
    assert sha256([ref]) == LOGITS[model, precision]


@pytest.mark.parametrize("model", ["graphsage", "gcn"])
def test_plugin_widths_and_engine_args(model):
    cfg = {"name": model, "num_layers": 3, "hidden": 128}
    dataset = {"feat_dim": 602, "num_classes": 41}
    assert plugin(model).widths(cfg, dataset) == [602, 128, 128, 41]
    assert plugin(model).engine_args(cfg) == {"model": model}
