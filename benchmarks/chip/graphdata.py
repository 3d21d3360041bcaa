"""The benchmark's graph data, made from ``--seed``.

A configuration's ``dataset`` block describes a synthetic stand-in for one
of DCI's Table II datasets: node count, average in-degree, feature width,
class count and train/val/test split, with power-law in-degrees (Pareto
``pareto_alpha``) and Zipf-skewed endpoint popularity (``popularity_gamma``)
spread over the id space by a random permutation.  It is the statistical
model of ``repro.graph.datasets.load_dataset``, made here so that a change
to the program cannot move the inputs it is measured on, and made in bulk:

* the in-degrees are one fixed draw, permuted by the seed (``base_degrees``);
* endpoint draws use the closed-form inverse of the continuous Zipf CDF
  instead of a search over the discrete one (seconds, not tens of seconds,
  for the 61M edges of ogbn-products);
* the feature table is drawn on the device in one jitted call and copied
  to the host once.

The result is the program's own ``SyntheticGraphDataset`` type, so the
system under test receives it as it would receive any dataset.
"""

from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class GraphArrays:
    """Host arrays of one stand-in graph (what the reference reads)."""

    col_ptr: np.ndarray  # int64[N+1]
    row_index: np.ndarray  # int32[E]: in-neighbours of v are row_index[col_ptr[v]:col_ptr[v+1]]
    features: np.ndarray  # float32[N, F]
    labels: np.ndarray  # int32[N]
    train_idx: np.ndarray
    val_idx: np.ndarray
    test_idx: np.ndarray


def zipf_ranks(u: np.ndarray, n: int, gamma: float) -> np.ndarray:
    """0-based ranks in ``[0, n)`` for uniforms ``u``, with P(rank k) close
    to ``(k+1)**-gamma`` (the continuous inverse CDF, floored)."""
    a = 1.0 - gamma
    x = (1.0 + u * ((n + 1.0) ** a - 1.0)) ** (1.0 / a)
    return np.minimum(np.floor(x).astype(np.int64) - 1, n - 1)


def base_degrees(spec: dict) -> np.ndarray:
    """The in-degree multiset of the stand-in: one fixed Pareto draw,
    scaled to the average degree.  Every seed uses these same degrees in
    another order, so every seed offers the same edges and array shapes; a
    fresh Pareto(1.3) draw per seed would not (its mean is ruled by a few
    extreme draws, which moved the unique frontier by 10% and the rate by
    25% between seeds)."""
    n = int(spec["num_nodes"])
    rng = np.random.default_rng([n, 0xDE6])
    raw = rng.pareto(float(spec["pareto_alpha"]), n) + 1.0
    deg = np.clip(np.round(raw * (float(spec["avg_degree"]) / raw.mean())), 1, max(2, n - 1))
    return deg.astype(np.int64)


def make_graph(spec: dict, seed: int) -> GraphArrays:
    """Every host array of the stand-in, from ``seed`` alone."""
    import jax
    import jax.numpy as jnp

    n = int(spec["num_nodes"])
    rng = np.random.default_rng([int(seed), 0x6D])
    deg = rng.permutation(base_degrees(spec))
    col_ptr = np.zeros(n + 1, np.int64)
    np.cumsum(deg, out=col_ptr[1:])
    perm = rng.permutation(n).astype(np.int32)
    ranks = zipf_ranks(rng.random(int(col_ptr[-1])), n, float(spec["popularity_gamma"]))
    row_index = perm[ranks]

    key = jax.random.PRNGKey(int(seed) % (1 << 32))
    feats = jax.jit(
        lambda k: jax.random.normal(k, (n, int(spec["feat_dim"])), jnp.float32),
    )(key)
    features = np.asarray(feats)
    del feats

    labels = rng.integers(0, int(spec["num_classes"]), n).astype(np.int32)
    order = rng.permutation(n)
    split = spec["split"]
    n_train, n_val = int(n * split[0]), int(n * split[1])
    return GraphArrays(
        col_ptr=col_ptr,
        row_index=row_index,
        features=features,
        labels=labels,
        train_idx=np.sort(order[:n_train]).astype(np.int32),
        val_idx=np.sort(order[n_train : n_train + n_val]).astype(np.int32),
        test_idx=np.sort(order[n_train + n_val :]).astype(np.int32),
    )


def as_program_dataset(spec: dict, g: GraphArrays):
    """Wrap the arrays in the program's dataset type."""
    from repro.graph.csc import CSCGraph
    from repro.graph.datasets import DatasetSpec, SyntheticGraphDataset

    dspec = DatasetSpec(
        name=spec["name"],
        num_nodes=int(spec["num_nodes"]),
        avg_degree=float(spec["avg_degree"]),
        feat_dim=int(spec["feat_dim"]),
        num_classes=int(spec["num_classes"]),
        split=tuple(spec["split"]),
        pareto_alpha=float(spec["pareto_alpha"]),
        popularity_gamma=float(spec["popularity_gamma"]),
    )
    return SyntheticGraphDataset(
        spec=dspec,
        graph=CSCGraph(col_ptr=g.col_ptr, row_index=g.row_index),
        features=g.features,
        labels=g.labels,
        train_idx=g.train_idx,
        val_idx=g.val_idx,
        test_idx=g.test_idx,
    )


def make_weights(model: str, dims: list[int], seed: int):
    """Seeded weights in the program's parameter layout (a list of layer
    dicts: ``w_self``, ``b`` and, for GraphSAGE, ``w_nbr``), float32, made
    on the device in one jitted call.  The biases are drawn too, not zero,
    so that a forward which drops or misplaces the bias add fails the logit
    comparison."""
    import jax
    import jax.numpy as jnp

    def init(key):
        layers = []
        for i in range(len(dims) - 1):
            key, k1, k2, k3 = jax.random.split(key, 4)
            scale = 1.0 / jnp.sqrt(jnp.float32(dims[i]))
            layer = {
                "w_self": jax.random.normal(k1, (dims[i], dims[i + 1]), jnp.float32) * scale,
                "b": jax.random.normal(k3, (dims[i + 1],), jnp.float32) * 0.1,
            }
            if model == "graphsage":
                layer["w_nbr"] = jax.random.normal(k2, (dims[i], dims[i + 1]), jnp.float32) * scale
            layers.append(layer)
        return layers

    return jax.jit(init)(jax.random.PRNGKey((int(seed) + 0x5EED) % (1 << 32)))
