"""GraphSAGE, GCN and GAT models (paper Table III: 3 layers, hidden 128, FC apply;
GAT: 4 heads × 128 with skips, PyG's ogbn-products model).

Pure-JAX functional models: ``init_params`` builds a parameter pytree,
``forward`` consumes input-frontier features plus the block structure
(static fan-outs) and produces per-seed logits.  Every model is one entry
of :data:`LAYER_KINDS`; a name outside it raises.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable

import jax
import jax.numpy as jnp

from repro.configs.gnn_paper import GNN_MODELS
from repro.models.gnn.layers import (
    gat_layer,
    gat_segment_layer,
    gcn_layer,
    gcn_segment_layer,
    sage_layer,
    sage_segment_layer,
)

__all__ = [
    "LAYER_KINDS",
    "LayerKind",
    "MODELS",
    "attn_edges",
    "forward",
    "forward_layer",
    "init_params",
    "layer_kind",
]


def _init_sage(k1, k2, d_in, ch, heads, last):
    scale = 1.0 / jnp.sqrt(d_in)
    return {
        "w_self": jax.random.normal(k1, (d_in, ch), jnp.float32) * scale,
        "b": jnp.zeros((ch,), jnp.float32),
        "w_nbr": jax.random.normal(k2, (d_in, ch), jnp.float32) * scale,
    }


def _init_gcn(k1, k2, d_in, ch, heads, last):
    scale = 1.0 / jnp.sqrt(d_in)
    return {
        "w_self": jax.random.normal(k1, (d_in, ch), jnp.float32) * scale,
        "b": jnp.zeros((ch,), jnp.float32),
    }


def _init_gat(k1, k2, d_in, ch, heads, last):
    """``w [in, H·C]``, ``att_src``/``att_dst [H, C]``, ``b [H·C]`` (``[C]``
    on the last layer, which averages its heads), ``w_skip [in, out]``,
    ``b_skip [out]``."""
    k_src, k_dst, k_skip = jax.random.split(k2, 3)
    out = ch if last else heads * ch
    scale = 1.0 / jnp.sqrt(d_in)
    return {
        "w": jax.random.normal(k1, (d_in, heads * ch), jnp.float32) * scale,
        "att_src": jax.random.normal(k_src, (heads, ch), jnp.float32) / jnp.sqrt(ch),
        "att_dst": jax.random.normal(k_dst, (heads, ch), jnp.float32) / jnp.sqrt(ch),
        "b": jnp.zeros((out,), jnp.float32),
        "w_skip": jax.random.normal(k_skip, (d_in, out), jnp.float32) * scale,
        "b_skip": jnp.zeros((out,), jnp.float32),
    }


@dataclasses.dataclass(frozen=True)
class LayerKind:
    """One model's layer: its sampled-block form ``(params, h, num_dst,
    fanout)``, its segment form over exact in-neighborhoods (layer-wise
    mode; see :mod:`repro.models.gnn.layers`), the activation between
    layers, the output width and attention heads read off a layer's
    parameters (0 heads: no per-edge scores), and its initializer."""

    block: Callable
    segment: Callable
    activation: Callable
    out_dim: Callable
    heads: Callable
    init: Callable


LAYER_KINDS = {
    "graphsage": LayerKind(
        sage_layer, sage_segment_layer, jax.nn.relu,
        lambda p: int(p["w_self"].shape[1]), lambda p: 0, _init_sage,
    ),
    "gcn": LayerKind(
        gcn_layer, gcn_segment_layer, jax.nn.relu,
        lambda p: int(p["w_self"].shape[1]), lambda p: 0, _init_gcn,
    ),
    "gat": LayerKind(
        gat_layer, gat_segment_layer, jax.nn.elu,
        lambda p: int(p["b"].shape[0]), lambda p: int(p["att_src"].shape[0]), _init_gat,
    ),
}
MODELS = tuple(LAYER_KINDS)


def layer_kind(model: str) -> LayerKind:
    if model not in LAYER_KINDS:
        raise ValueError(f"unknown GNN model {model!r} (have {MODELS})")
    return LAYER_KINDS[model]


def init_params(
    key: jax.Array,
    model: str,
    in_dim: int,
    num_classes: int,
    hidden: int = 128,
    n_layers: int = 3,
    heads: int | None = None,
) -> list[dict]:
    """Layer ``i`` maps ``dims[i]`` to ``hidden`` per head (``num_classes``
    on the last layer).  ``heads`` defaults to the model's paper value
    (:data:`~repro.configs.gnn_paper.GNN_MODELS`); models without
    attention ignore it."""
    kind = layer_kind(model)
    heads = GNN_MODELS[model].get("heads", 1) if heads is None else int(heads)
    d_in = in_dim
    params = []
    for i in range(n_layers):
        key, k1, k2 = jax.random.split(key, 3)
        last = i == n_layers - 1
        layer = kind.init(k1, k2, d_in, num_classes if last else hidden, heads, last)
        params.append(layer)
        d_in = kind.out_dim(layer)
    return params


def _frontier_sizes(num_seeds: int, rev: tuple[int, ...]) -> list[int]:
    """Frontier sizes from seeds outward: ``|frontier_l+1| = |frontier_l| (1 + f)``."""
    sizes = [num_seeds]
    for f in rev:
        sizes.append(sizes[-1] * (1 + f))
    return sizes


@functools.partial(jax.jit, static_argnames=("model", "fanouts"))
def forward(
    params: list[dict],
    input_feats: jax.Array,
    *,
    model: str,
    fanouts: tuple[int, ...],
    frontier_sizes: tuple[int, ...] | None = None,
    inverse_index: jax.Array | None = None,
) -> jax.Array:
    """Run the GNN over one sampled block.

    ``input_feats`` covers the deepest frontier (``block.input_nodes``).
    Frontier sizes are implied by ``fanouts`` and the seed count, which we
    recover from the feature row count (all shapes are static under jit).

    ``inverse_index`` switches to the unique-frontier form: ``input_feats``
    then holds one row per DISTINCT input node (a deduped gather, possibly
    pow2-padded — the pad rows are never referenced) and ``inverse_index``
    maps every frontier position to its unique row.  The per-frontier
    ``[self | neighbors]`` layout is reconstructed by one gather,
    ``input_feats[inverse_index]`` — each reconstructed row is the same
    bits the duplicate-carrying gather would have produced, so everything
    downstream (and therefore the logits) is bit-identical to the
    ``inverse_index=None`` path.
    """
    kind = layer_kind(model)
    rev = tuple(reversed(fanouts))  # expansion order used by sample_blocks
    # Recover seed count: |frontier_L| = B * Π(1 + f)
    mult = 1
    for f in rev:
        mult *= 1 + f
    if inverse_index is not None:
        input_feats = input_feats[inverse_index.astype(jnp.int32)]
    sizes = _frontier_sizes(input_feats.shape[0] // mult, rev)

    h = input_feats
    n_layers = len(fanouts)
    # Walk from the deepest frontier inward; model layer 0 consumes raw feats.
    for li, l in enumerate(range(n_layers - 1, -1, -1)):
        h = kind.block(params[li], h, sizes[l], rev[l])
        if li < n_layers - 1:
            h = kind.activation(h)
    return h  # [num_seeds, num_classes]


def attn_edges(params: list[dict], model: str, fanouts: tuple[int, ...], num_seeds: int) -> int:
    """Edge scores one sampled forward computes, heads counted: Σ over
    layers of ``S_dst × (fanout + 1) × heads`` (0 for models without
    attention).  From static shapes only — no device sync."""
    kind = layer_kind(model)
    rev = tuple(reversed(tuple(fanouts)))
    sizes = _frontier_sizes(int(num_seeds), rev)
    n_layers = len(rev)
    return sum(
        sizes[l] * (rev[l] + 1) * kind.heads(params[li])
        for li, l in enumerate(range(n_layers - 1, -1, -1))
    )


@functools.partial(jax.jit, static_argnames=("model", "num_dst", "activate"))
def forward_layer(
    layer_params: dict,
    self_feats: jax.Array,
    nbr_feats: jax.Array,
    segment_ids: jax.Array,
    degrees: jax.Array,
    *,
    model: str,
    num_dst: int,
    activate: bool = False,
) -> jax.Array:
    """One GNN layer over EXACT neighbor aggregates — the layer-wise mode's
    per-layer split of :func:`forward`.

    Where :func:`forward` consumes a sampled ``[self | neighbors]`` frontier
    (dense ``fanout`` draws per node), this consumes one node-range chunk's
    full in-neighborhoods in CSC order: ``self_feats[num_dst, F]`` are the
    chunk nodes' own rows, ``nbr_feats[E_pad, F]`` the rows of every
    in-edge's source (pow2-padded; pad rows carry ``segment_ids ==
    num_dst`` and land in a dropped extra segment), ``segment_ids`` each
    edge row's destination within the chunk, and ``degrees[num_dst]`` the
    true in-degrees.  Aggregation is a ``segment_sum`` (GAT: a segment
    softmax, ``segment_max`` / ``exp`` / ``segment_sum``) — the
    ragged-neighborhood analogue of the sampled reshape+reduce.

    With every degree equal to the layer's fanout and sampling enumerating
    deterministically (``sample_neighbors(full_neighborhood=True)``), the
    aggregate equals the sampled sum exactly, so an L-layer chain of these
    is fp-identical to :func:`forward` on regular graphs
    (tests/test_layerwise.py).  Zero-degree nodes aggregate nothing
    (``agg = 0``; GAT attends to the self-loop alone).

    ``activate`` applies the model's inter-layer activation (every layer
    but the last), so the chunk executor never re-reads the output just to
    activate it.
    """
    kind = layer_kind(model)
    h = kind.segment(layer_params, self_feats, nbr_feats, segment_ids, degrees, num_dst)
    return kind.activation(h) if activate else h
