"""Infrastructure tests: optimizer, checkpoint, token pipeline, HLO analysis,
sharding specs (including divisibility on the production mesh)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.hlo import analyze_hlo
from repro.checkpoint.io import load_checkpoint, save_checkpoint
from repro.configs import ARCH_IDS, get_config
from repro.data.tokens import TokenStream, batches
from repro.models.lm.model import abstract_params
from repro.models.lm.sharding import param_specs
from repro.optim.adamw import adamw_update, cosine_schedule, init_adamw

# ----------------------------------------------------------------- optim


def test_adamw_minimizes_quadratic():
    params = {"w": jnp.asarray([3.0, -2.0])}
    state = init_adamw(params)
    for _ in range(200):
        grads = jax.grad(lambda p: jnp.sum(p["w"] ** 2))(params)
        params, state = adamw_update(params, grads, state, lr=0.1, weight_decay=0.0)
    assert float(jnp.abs(params["w"]).max()) < 0.05
    assert int(state["step"]) == 200


def test_adamw_preserves_dtypes():
    params = {"w": jnp.ones((4, 4), jnp.bfloat16)}
    state = init_adamw(params)
    grads = {"w": jnp.ones((4, 4), jnp.bfloat16)}
    new_params, new_state = adamw_update(params, grads, state)
    assert new_params["w"].dtype == jnp.bfloat16
    assert new_state["m"]["w"].dtype == jnp.float32


def test_cosine_schedule_shape():
    lrs = [float(cosine_schedule(jnp.int32(s), base_lr=1.0, warmup=10, total=100)) for s in range(100)]
    assert lrs[0] < lrs[9]  # warmup rises
    assert lrs[20] > lrs[90]  # decays after


# ------------------------------------------------------------ checkpoint


def test_checkpoint_roundtrip(tmp_path):
    tree = {
        "a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
        "b": {"c": jnp.ones((4,), jnp.bfloat16), "d": jnp.int32(7)},
        "blocks": (jnp.zeros((2, 2)), jnp.ones((3,))),
    }
    path = str(tmp_path / "ckpt.npz")
    save_checkpoint(path, tree)
    restored = load_checkpoint(path, jax.tree.map(jnp.zeros_like, tree))
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(restored)):
        np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b, np.float32))
    assert restored["b"]["c"].dtype == jnp.bfloat16


# ------------------------------------------------------------ token data


def test_token_stream_bounds_and_determinism():
    stream = TokenStream(vocab=128, seed=3)
    b1 = list(batches(stream, batch=2, seq=16, steps=3, seed=1))
    b2 = list(batches(stream, batch=2, seq=16, steps=3, seed=1))
    for x, y in zip(b1, b2):
        np.testing.assert_array_equal(x["tokens"], y["tokens"])
    assert all((b["tokens"] >= 0).all() and (b["tokens"] < 128).all() for b in b1)
    assert b1[0]["tokens"].shape == (2, 16)
    # labels are the shifted stream
    np.testing.assert_array_equal(b1[0]["labels"][:, :-1], b1[0]["tokens"][:, 1:])


# ----------------------------------------------------------- HLO analysis


def test_hlo_flops_recovers_scan_trip_count():
    n, k, m, trips = 64, 32, 16, 10
    w = jnp.ones((k, m), jnp.float32)

    def f(x):
        def body(c, _):
            return c, x @ w

        _, ys = jax.lax.scan(body, 0, jnp.arange(trips))
        return ys.sum()

    x = jnp.ones((n, k), jnp.float32)
    hlo = jax.jit(f).lower(x).compile().as_text()
    s = analyze_hlo(hlo)
    expected = 2 * n * k * m * trips
    # XLA may hoist the loop-invariant matmul; accept either exact scan
    # accounting or the hoisted single execution.
    assert s.flops in (expected, expected / trips)
    assert s.unresolved_trip_counts == 0


def test_hlo_flops_counts_dependent_scan():
    n, trips = 32, 7
    w = jnp.eye(n, dtype=jnp.float32) * 0.5

    def f(x):
        def body(c, _):
            return c @ w, ()

        c, _ = jax.lax.scan(body, x, jnp.arange(trips))
        return c.sum()

    x = jnp.ones((n, n), jnp.float32)
    s = analyze_hlo(jax.jit(f).lower(x).compile().as_text())
    assert s.flops == 2 * n * n * n * trips


# --------------------------------------------------------- sharding specs


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_param_specs_structure_and_divisibility(arch):
    """Every sharded dim must divide by the model-axis size (16) — this is
    the static check that keeps new configs dry-run-compatible."""
    cfg = get_config(arch)
    params = abstract_params(cfg)
    specs = param_specs(params)
    leaves = jax.tree_util.tree_leaves_with_path(params)
    spec_leaves = {
        jax.tree_util.keystr(p): s
        for p, s in jax.tree_util.tree_leaves_with_path(
            specs, is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec)
        )
    }
    for path, leaf in leaves:
        spec = spec_leaves[jax.tree_util.keystr(path)]
        assert len(spec) <= leaf.ndim, (path, spec, leaf.shape)
        for dim, axis in zip(leaf.shape, tuple(spec) + (None,) * leaf.ndim):
            if axis == "model":
                assert dim % 16 == 0, f"{jax.tree_util.keystr(path)}: {dim} % 16 != 0"


# ------------------------------------------------------- determinism, cache


def _dataset_digest(hash_seed: str) -> str:
    import os
    import subprocess
    import sys

    code = (
        "import hashlib; from repro.graph.datasets import load_dataset; "
        "ds = load_dataset('reddit', scale=0.001, seed=3); "
        "print(hashlib.sha256(ds.graph.row_index.tobytes() + ds.features.tobytes()).hexdigest())"
    )
    env = dict(os.environ, PYTHONHASHSEED=hash_seed, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-c", code],
        env=env,
        capture_output=True,
        text=True,
        timeout=120,
        check=True,
    )
    return out.stdout.strip()


def test_dataset_is_a_function_of_the_seed_across_processes():
    """``load_dataset`` derives its generator from a stable digest of the
    name, not from ``hash(name)`` (salted per process): two processes with
    different hash salts build the same graph from the same seed."""
    assert _dataset_digest("1") == _dataset_digest("2")


def test_compile_cache_placement(monkeypatch):
    """The env var wins and nothing else is set; otherwise the cache sits
    at one fixed directory inside the checkout."""
    from repro.utils import compile_cache

    before = jax.config.jax_compilation_cache_dir
    min_time = jax.config.jax_persistent_cache_min_compile_time_secs
    try:
        monkeypatch.setenv(compile_cache.CACHE_ENV, "/elsewhere/jax-cache")
        assert compile_cache.enable_compile_cache() == "/elsewhere/jax-cache"
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv(compile_cache.CACHE_ENV)
        path = compile_cache.enable_compile_cache()
        repo = compile_cache.DEFAULT_CACHE_DIR.parent
        assert path == str(repo / ".jax_cache") and (repo / "src" / "repro").is_dir()
        assert jax.config.jax_compilation_cache_dir == path
        assert ".jax_cache/" in (repo / ".gitignore").read_text().splitlines()
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", min_time)
