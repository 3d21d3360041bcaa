"""The numpy references against the program's forward, at a tiny size (CPU)."""

import pathlib
import sys

import numpy as np
import pytest

CHIP = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(CHIP))
sys.path.insert(0, str(CHIP.parents[1] / "src"))

import graphdata  # noqa: E402
import reference  # noqa: E402

FANOUTS = (4, 3, 2)
BATCH = 16
# Both sides compute in float32 on the CPU: the same products summed in a
# different order differ by a few units of the last place (2^-24 relative
# each) over sums of at most a few hundred terms, so a few 1e-6 of the
# logit scale.  A wrong row, neighbour block or layer moves logits by the
# order of the scale itself.
TOL = 1e-5


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
    params = graphdata.make_weights(model, [24, 32, 32, 5], seed=3)
    params_np = [{k: np.asarray(v) for k, v in p.items()} for p in params]
    ids = sampled_frontier(table.shape[0], rng)
    ref = reference.forward(model, params_np, table, ids, BATCH, FANOUTS, block=5)
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
