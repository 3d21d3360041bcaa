"""GCN, the mean over self and neighbours (DCI Table III).

A layer maps destination rows ``h_self`` and their ``fanout`` sampled
neighbour rows to ``h' = ((h_self + sum of the neighbour rows) / (fanout +
1)) @ W + b``, with ReLU after every layer but the last.

A model plug-in of the benchmark.  The harness loads ``models/<name>.py``
by the configuration's ``model.name``, and every plug-in defines:

* ``widths(model_cfg, dataset)``: the layer widths (``dims``): the feature
  width first, the class count last;
* ``engine_args(model_cfg)``: the model's keyword arguments for the
  program's ``GNNInferenceEngine``;
* ``make_weights(dims, seed, model_cfg)``: seeded float32 weights in the
  program's parameter layout, made on the device in one jitted call;
* ``layer(p, self_h, nbr_h, *, matmul, last)``: one reference layer in
  plain float32 numpy, activation included (``nbr_h`` is ``[rows, fanout,
  width]``; ``matmul`` is ``reference.matmul`` at the run's precision;
  ``last`` marks the output layer);
* ``forward_flops(dims, fanouts, batch, model_cfg)``: the model's own
  arithmetic per sampled batch, counted from shapes, so that a faster
  implementation moves time and never the count.
"""

from __future__ import annotations

import numpy as np

import reference


def widths(model_cfg: dict, dataset: dict) -> list[int]:
    """Layer widths: features, hidden..., classes."""
    hidden = [int(model_cfg["hidden"])] * (int(model_cfg["num_layers"]) - 1)
    return [int(dataset["feat_dim"])] + hidden + [int(dataset["num_classes"])]


def engine_args(model_cfg: dict) -> dict:
    return {"model": "gcn"}


def make_weights(dims: list[int], seed: int, model_cfg: dict):
    """Seeded float32 ``w_self`` and ``b`` per layer, made on the device in
    one jitted call.  The biases are drawn, not zero, so that a forward
    which drops or misplaces the bias add fails the comparison."""
    import jax
    import jax.numpy as jnp

    def init(key):
        layers = []
        for i in range(len(dims) - 1):
            key, k1, _k2, k3 = jax.random.split(key, 4)  # k2: GraphSAGE's w_nbr
            scale = 1.0 / jnp.sqrt(jnp.float32(dims[i]))
            layers.append({
                "w_self": jax.random.normal(k1, (dims[i], dims[i + 1]), jnp.float32) * scale,
                "b": jax.random.normal(k3, (dims[i + 1],), jnp.float32) * 0.1,
            })
        return layers

    return jax.jit(init)(jax.random.PRNGKey((int(seed) + 0x5EED) % (1 << 32)))


def layer(p, self_h: np.ndarray, nbr_h: np.ndarray, *, matmul, last: bool) -> np.ndarray:
    agg = (self_h + nbr_h.sum(axis=1)) / np.float32(nbr_h.shape[1] + 1)
    out = matmul(agg, p["w_self"]) + p["b"]
    return out if last else np.maximum(out, np.float32(0.0))


def forward_flops(dims: list[int], fanouts, batch: int, model_cfg: dict) -> float:
    """Per layer: the self and neighbour sum, the mean's divide, one
    product and the bias."""
    rev = tuple(int(f) for f in reversed(tuple(fanouts)))
    sizes = reference.frontier_sizes(batch, fanouts)
    flops = 0.0
    for li in range(len(rev)):
        level = len(rev) - 1 - li  # destination frontier of model layer li
        n, f, k, m = sizes[level], rev[level], dims[li], dims[li + 1]
        flops += n * f * k + n * k
        flops += 2.0 * n * k * m + n * m
    return flops
