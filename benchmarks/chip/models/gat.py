"""GAT with skip connections (Veličković et al., arXiv:1710.10903), at the
widths of PyTorch Geometric's ogbn-products model
(``examples/ogbn_products_gat.py``): layer ``i`` is ``GATConv`` plus a
linear skip of the destination rows, ELU after every layer but the last.

Per destination ``i`` and head ``h``, with ``z = x W`` reshaped to ``[..,
H, C]`` and ``j`` running over the destination itself (the self-loop,
position 0) and its ``fanout`` sampled draws::

    e_ij  = LeakyReLU_0.2(<z_j,h, att_src_h> + <z_i,h, att_dst_h>)
    α_ij  = softmax_j(e_ij)
    out_i = concat_h (mean_h on the last layer) Σ_j α_ij z_j,h + b + x_i W_skip + b_skip

The hidden layers concatenate ``heads`` heads of ``hidden``; the last has
``heads`` heads of the class count, averaged.  A sampled draw that is the
node itself is one more edge (PyG removes such self-edges before adding
its one self-loop; the sampler draws with replacement).

A model plug-in of the benchmark, loaded by the configuration's
``model.name``; ``gcn.py`` documents the functions every plug-in defines.
This one adds ``attn_edges`` (the edge scores a batch computes, heads
counted) and ``forward_bytes`` (the least HBM traffic of a batch's
forward), which ``metrics/gat_forward_roofline.py`` reads.
"""

from __future__ import annotations

import numpy as np

import reference

NEGATIVE_SLOPE = np.float32(0.2)


def widths(model_cfg: dict, dataset: dict) -> list[int]:
    """Layer widths: features, heads × hidden (concatenated)..., classes."""
    hidden = int(model_cfg["hidden"]) * int(model_cfg["heads"])
    return [int(dataset["feat_dim"])] + [hidden] * (int(model_cfg["num_layers"]) - 1) + [
        int(dataset["num_classes"])
    ]


def engine_args(model_cfg: dict) -> dict:
    return {"model": "gat"}


def _shapes(dims: list[int], heads: int):
    """Per layer: ``(in, heads, per-head width, out, last)``."""
    out = []
    for i in range(len(dims) - 1):
        last = i == len(dims) - 2
        ch = dims[i + 1] if last else dims[i + 1] // heads
        if not last and ch * heads != dims[i + 1]:
            raise ValueError(f"width {dims[i + 1]} is not {heads} heads wide")
        out.append((dims[i], heads, ch, dims[i + 1], last))
    return out


def make_weights(dims: list[int], seed: int, model_cfg: dict):
    """Seeded float32 weights in the program's layout, per layer: ``w [in,
    H·C]``, ``att_src`` and ``att_dst [H, C]``, ``b [H·C]`` (``[C]`` on the
    last layer), ``w_skip [in, out]``, ``b_skip [out]``; made on the device
    in one jitted call.  Every tensor is drawn, the biases included, so
    that a forward which drops a bias, the skip or a head fails the
    comparison."""
    import jax
    import jax.numpy as jnp

    shapes = _shapes(dims, int(model_cfg["heads"]))

    def init(key):
        layers = []
        for d_in, heads, ch, out, _ in shapes:
            key, k1, k2, k3, k4, k5, k6 = jax.random.split(key, 7)
            scale = 1.0 / jnp.sqrt(jnp.float32(d_in))
            att = 1.0 / jnp.sqrt(jnp.float32(ch))
            layers.append({
                "w": jax.random.normal(k1, (d_in, heads * ch), jnp.float32) * scale,
                "att_src": jax.random.normal(k2, (heads, ch), jnp.float32) * att,
                "att_dst": jax.random.normal(k3, (heads, ch), jnp.float32) * att,
                "b": jax.random.normal(k4, (out,), jnp.float32) * 0.1,
                "w_skip": jax.random.normal(k5, (d_in, out), jnp.float32) * scale,
                "b_skip": jax.random.normal(k6, (out,), jnp.float32) * 0.1,
            })
        return layers

    return jax.jit(init)(jax.random.PRNGKey((int(seed) + 0x5EED) % (1 << 32)))


def _per_head(att: np.ndarray) -> np.ndarray:
    """``[H, C]`` -> block-diagonal ``[H·C, H]``, so that ``z @ it`` is the
    per-head ``<z_h, att_h>``."""
    heads, ch = att.shape
    out = np.zeros((heads * ch, heads), np.float32)
    for h in range(heads):
        out[h * ch : (h + 1) * ch, h] = att[h]
    return out


def layer(p, self_h: np.ndarray, nbr_h: np.ndarray, *, matmul, last: bool) -> np.ndarray:
    heads, ch = p["att_src"].shape
    n, f, k = nbr_h.shape
    x = np.concatenate([self_h[:, None, :], nbr_h], axis=1).reshape(n * (1 + f), k)
    z = matmul(x, p["w"])  # [n·(1+f), H·C]
    src = matmul(z, _per_head(p["att_src"])).reshape(n, 1 + f, heads)
    z = z.reshape(n, 1 + f, heads * ch)
    dst = matmul(z[:, 0], _per_head(p["att_dst"]))  # [n, H]
    e = src + dst[:, None, :]
    e = np.where(e > 0, e, NEGATIVE_SLOPE * e)
    e = np.exp(e - e.max(axis=1, keepdims=True))
    alpha = e / e.sum(axis=1, keepdims=True)  # [n, 1+f, H]
    z = z.reshape(n, 1 + f, heads, ch)
    att_out = (alpha[..., None] * z).sum(axis=1)  # [n, H, C]
    att_out = att_out.mean(axis=1) if last else att_out.reshape(n, heads * ch)
    out = att_out + p["b"] + (matmul(self_h, p["w_skip"]) + p["b_skip"])
    return out if last else np.where(out > 0, out, np.expm1(out))


def _layers(dims, fanouts, batch, model_cfg):
    """Per model layer: ``(S_dst, fanout, in, heads, per-head width, out, last)``."""
    rev = tuple(int(f) for f in reversed(tuple(fanouts)))
    sizes = reference.frontier_sizes(batch, fanouts)
    shapes = _shapes(dims, int(model_cfg["heads"]))
    out = []
    for li, (k, heads, ch, m, last) in enumerate(shapes):
        level = len(rev) - 1 - li  # destination frontier of model layer li
        out.append((sizes[level], rev[level], k, heads, ch, m, last))
    return out


def forward_flops(dims: list[int], fanouts, batch: int, model_cfg: dict) -> float:
    """Per layer, over the sampled block (``S`` destinations, ``N = S (1 +
    f)`` source positions, ``E = S (f + 1)`` edges per head): the
    projection of every source position (``2 N k H·C``); the source and
    destination scores (``2 (N + S) H·C``); the score add and LeakyReLU
    (``2 E H``); the softmax's max, subtract, exp, sum and divide (``5 E
    H``); the weighted sum (``2 E H·C``); the head mean on the last layer
    (``S H·C``); the bias, the skip product, its bias and its add (``2 S k
    out + 3 S out``); ELU on every layer but the last (``S out``)."""
    flops = 0.0
    for s, f, k, heads, ch, m, last in _layers(dims, fanouts, batch, model_cfg):
        hc = heads * ch
        n = s * (1 + f)
        e = s * (f + 1) * heads
        flops += 2.0 * n * k * hc
        flops += 2.0 * (n + s) * hc
        flops += 7.0 * e
        flops += 2.0 * e * ch
        flops += s * hc if last else 0.0
        flops += 2.0 * s * k * m + 3.0 * s * m
        flops += 0.0 if last else s * m
    return flops


def attn_edges(dims: list[int], fanouts, batch: int, model_cfg: dict) -> int:
    """Edge scores one batch's forward computes, heads counted: Σ over
    layers of ``S_dst × (fanout + 1) × heads``."""
    return sum(s * (f + 1) * heads
               for s, f, _, heads, _, _, _ in _layers(dims, fanouts, batch, model_cfg))


def forward_bytes(dims: list[int], fanouts, batch: int, model_cfg: dict, *,
                  unique_rows: float) -> float:
    """The least HBM traffic of one batch's forward, in bytes: each of the
    batch's ``unique_rows`` distinct input rows read once, each layer's
    output written once and read once, and the weights read once.  What an
    implementation chooses to materialise (the rebuilt frontier, the
    projected block, the scores) is not counted, so no faster program can
    read above the roofline."""
    total = float(unique_rows) * dims[0] * 4
    for s, _, k, heads, ch, m, _ in _layers(dims, fanouts, batch, model_cfg):
        hc = heads * ch
        total += 2.0 * s * m * 4
        total += (k * hc + 2 * hc + m + k * m + m) * 4
    return total
