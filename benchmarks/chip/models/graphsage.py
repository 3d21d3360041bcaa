"""GraphSAGE with the sum aggregator (DCI Table III).

A layer maps destination rows ``h_self`` and their ``fanout`` sampled
neighbour rows to ``h' = h_self @ W_self + (sum of the neighbour rows) @
W_nbr + b``, with ReLU after every layer but the last.

A model plug-in of the benchmark, loaded by the configuration's
``model.name``; ``gcn.py`` documents the functions every plug-in defines.
"""

from __future__ import annotations

import numpy as np

import reference


def widths(model_cfg: dict, dataset: dict) -> list[int]:
    """Layer widths: features, hidden..., classes."""
    hidden = [int(model_cfg["hidden"])] * (int(model_cfg["num_layers"]) - 1)
    return [int(dataset["feat_dim"])] + hidden + [int(dataset["num_classes"])]


def engine_args(model_cfg: dict) -> dict:
    return {"model": "graphsage"}


def make_weights(dims: list[int], seed: int, model_cfg: dict):
    """Seeded float32 ``w_self``, ``w_nbr`` and ``b`` per layer, made on the
    device in one jitted call.  The biases are drawn, not zero, so that a
    forward which drops or misplaces the bias add fails the comparison."""
    import jax
    import jax.numpy as jnp

    def init(key):
        layers = []
        for i in range(len(dims) - 1):
            key, k1, k2, k3 = jax.random.split(key, 4)
            scale = 1.0 / jnp.sqrt(jnp.float32(dims[i]))
            layers.append({
                "w_self": jax.random.normal(k1, (dims[i], dims[i + 1]), jnp.float32) * scale,
                "b": jax.random.normal(k3, (dims[i + 1],), jnp.float32) * 0.1,
                "w_nbr": jax.random.normal(k2, (dims[i], dims[i + 1]), jnp.float32) * scale,
            })
        return layers

    return jax.jit(init)(jax.random.PRNGKey((int(seed) + 0x5EED) % (1 << 32)))


def layer(p, self_h: np.ndarray, nbr_h: np.ndarray, *, matmul, last: bool) -> np.ndarray:
    out = matmul(self_h, p["w_self"]) + matmul(nbr_h.sum(axis=1), p["w_nbr"]) + p["b"]
    return out if last else np.maximum(out, np.float32(0.0))


def forward_flops(dims: list[int], fanouts, batch: int, model_cfg: dict) -> float:
    """Per layer: the neighbour sum, two products, their add and the bias."""
    rev = tuple(int(f) for f in reversed(tuple(fanouts)))
    sizes = reference.frontier_sizes(batch, fanouts)
    flops = 0.0
    for li in range(len(rev)):
        level = len(rev) - 1 - li  # destination frontier of model layer li
        n, f, k, m = sizes[level], rev[level], dims[li], dims[li + 1]
        flops += n * (f - 1) * k
        flops += 2 * (2.0 * n * k * m) + 2 * n * m
    return flops
