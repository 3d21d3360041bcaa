"""GraphSAGE / GCN / GAT layers over padded sampled blocks (paper Table III).

A block layer's input is a feature matrix over frontier ``l+1`` with the
``[self | neighbors]`` layout produced by ``sample_blocks``; the layer
reduces it to features over frontier ``l``.  With-replacement fan-out
sampling makes neighborhoods dense ``(S, fanout, F)`` tensors, so
aggregation is a plain reshape + reduction — MXU-friendly, no ragged ops.

Each layer kind also has a segment form (``*_segment_layer``) over one
chunk's EXACT in-neighborhoods in CSC order — the layer-wise mode's
ragged analogue: ``self_feats[num_dst, F]``, ``nbr_feats[E_pad, F]`` (one
row per in-edge source, pow2-padded), ``segment_ids[E_pad]`` (each edge's
destination within the chunk; pads carry ``num_dst`` and land in a
dropped extra segment) and ``degrees[num_dst]`` (true in-degrees).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = [
    "sage_layer",
    "gcn_layer",
    "gat_layer",
    "sage_segment_layer",
    "gcn_segment_layer",
    "gat_segment_layer",
    "split_frontier",
]

# GATConv's LeakyReLU slope on the edge scores (Veličković et al.; PyG default).
NEGATIVE_SLOPE = 0.2


def split_frontier(h: jax.Array, num_dst: int, fanout: int) -> tuple[jax.Array, jax.Array]:
    """Split ``[self | neighbors]`` features: ``(dst[S,F], nbrs[S,fanout,F])``."""
    self_part = h[:num_dst]
    nbr_part = h[num_dst:].reshape(num_dst, fanout, h.shape[-1])
    return self_part, nbr_part


def sage_layer(params: dict, h: jax.Array, num_dst: int, fanout: int) -> jax.Array:
    """GraphSAGE: sum-aggregate neighbors, separate self/neighbor FCs."""
    self_h, nbr_h = split_frontier(h, num_dst, fanout)
    agg = nbr_h.sum(axis=1)
    return self_h @ params["w_self"] + agg @ params["w_nbr"] + params["b"]


def gcn_layer(params: dict, h: jax.Array, num_dst: int, fanout: int) -> jax.Array:
    """GCN: mean over {self} ∪ neighbors, single FC."""
    self_h, nbr_h = split_frontier(h, num_dst, fanout)
    agg = (self_h + nbr_h.sum(axis=1)) / (fanout + 1)
    return agg @ params["w_self"] + params["b"]


def _segment_sum(x: jax.Array, segment_ids: jax.Array, num_dst: int) -> jax.Array:
    """Per-destination sums; pad edges land in segment ``num_dst``, dropped."""
    return jax.ops.segment_sum(x, segment_ids, num_segments=num_dst + 1)[:num_dst]


def sage_segment_layer(params, self_feats, nbr_feats, segment_ids, degrees, num_dst):
    """GraphSAGE over exact in-neighborhoods (zero-degree: ``agg = 0``)."""
    agg = _segment_sum(nbr_feats, segment_ids, num_dst)
    return self_feats @ params["w_self"] + agg @ params["w_nbr"] + params["b"]


def gcn_segment_layer(params, self_feats, nbr_feats, segment_ids, degrees, num_dst):
    """GCN: mean over {self} ∪ in-neighbors, single FC."""
    agg = _segment_sum(nbr_feats, segment_ids, num_dst)
    h = ((self_feats + agg) / (degrees[:, None] + 1.0)) @ params["w_self"]
    return h + params["b"]


# ------------------------------------------------------------------- GAT
#
# Per destination i and head h, with z = x W reshaped to [.., H, C]:
#   e_ij = LeakyReLU_0.2(<z_j,h, att_src_h> + <z_i,h, att_dst_h>),  j ∈ {i} ∪ N(i)
#   α_ij = softmax_j(e_ij)
#   out_i = concat_h (or mean_h on the last layer) Σ_j α_ij z_j,h + b + x_i W_skip + b_skip
#
# The self-loop is the destination's own position in the block; a sampled
# draw that is the node itself is one more edge (with-replacement sampling
# keeps it, where PyG's GATConv removes self-edges before adding one).  The
# head count is ``att_src.shape[0]`` and the output concatenates the heads
# where ``b`` is ``H·C`` wide, and averages them where it is ``C`` wide.


def _project(params: dict, x: jax.Array) -> jax.Array:
    heads, ch = params["att_src"].shape
    return (x @ params["w"]).reshape(x.shape[0], heads, ch)


def _score_weights(params: dict, att: jax.Array) -> jax.Array:
    """``W`` folded with a per-head attention vector: ``x @ it`` is the
    per-head score ``<x W_h, att_h>`` (``[in, H]``), so that the scores do
    not read the projected block (which is then read once, by the weighted
    sum)."""
    heads, ch = att.shape
    w = params["w"]
    return (w.reshape(w.shape[0], heads, ch) * att).sum(axis=-1)


def _leaky(e: jax.Array) -> jax.Array:
    return jax.nn.leaky_relu(e, negative_slope=NEGATIVE_SLOPE)


def _merge_heads(params: dict, out: jax.Array) -> jax.Array:
    """``[S, H, C]`` -> concat ``[S, H·C]`` or mean ``[S, C]`` (by ``b``'s width), plus ``b``."""
    s, heads, ch = out.shape
    merged = out.reshape(s, heads * ch) if params["b"].shape[0] == heads * ch else out.mean(axis=1)
    return merged + params["b"]


def _skip(params: dict, x_dst: jax.Array) -> jax.Array:
    with jax.named_scope("gat/skip"):
        return x_dst @ params["w_skip"] + params["b_skip"]


def gat_layer(params: dict, h: jax.Array, num_dst: int, fanout: int) -> jax.Array:
    """GAT with a skip: attention over {self} ∪ the ``fanout`` draws, per head."""
    self_h, nbr_h = h[:num_dst], h[num_dst:]
    with jax.named_scope("gat/project"):
        # The self rows and the draws are projected apart, so that neither
        # projected block is a slice of a larger one.
        z_self = _project(params, self_h)  # [S, H, C]
        z_nbr = _project(params, nbr_h)  # [S·fanout, H, C]
    with jax.named_scope("gat/attend"):
        heads, ch = z_self.shape[1:]
        z_nbr = z_nbr.reshape(num_dst, fanout, heads, ch)
        w_src = _score_weights(params, params["att_src"])
        dst = self_h @ _score_weights(params, params["att_dst"])  # [S, H]
        e_self = _leaky(self_h @ w_src + dst)
        e_nbr = _leaky((nbr_h @ w_src).reshape(num_dst, fanout, heads) + dst[:, None])
        m = jnp.maximum(e_self, e_nbr.max(axis=1))
        p_self = jnp.exp(e_self - m)
        p_nbr = jnp.exp(e_nbr - m[:, None])
        den = p_self + p_nbr.sum(axis=1)
        a_self = p_self / den
        a_nbr = p_nbr / den[:, None]
        out = a_self[..., None] * z_self + (a_nbr[..., None] * z_nbr).sum(axis=1)
        out = _merge_heads(params, out)
    return out + _skip(params, self_h)


def gat_segment_layer(params, self_feats, nbr_feats, segment_ids, degrees, num_dst):
    """GAT over exact in-neighborhoods: a segment softmax over {self} ∪ in-edges.

    A zero-degree node attends only to itself (``α = 1``): its output is
    ``z_i`` plus the skip, as in the sampled form, where the sampler fills
    its draws with the node itself."""
    with jax.named_scope("gat/project"):
        z_self = _project(params, self_feats)  # [D, H, C]
        z_nbr = _project(params, nbr_feats)  # [E_pad, H, C]
    with jax.named_scope("gat/attend"):
        nseg = num_dst + 1
        w_src = _score_weights(params, params["att_src"])
        dst = self_feats @ _score_weights(params, params["att_dst"])  # [D, H]
        dst_all = jnp.concatenate([dst, jnp.zeros((1, dst.shape[1]), dst.dtype)])
        e_self = _leaky(self_feats @ w_src + dst)
        e_nbr = _leaky(nbr_feats @ w_src + dst_all[segment_ids])
        # Max over each segment, the self position included; the pad
        # segment's max is its own, so its exponents stay finite.
        m = jax.ops.segment_max(e_nbr, segment_ids, num_segments=nseg)
        m = m.at[:num_dst].max(e_self)
        p_self = jnp.exp(e_self - m[:num_dst])
        p_nbr = jnp.exp(e_nbr - m[segment_ids])
        den = jax.ops.segment_sum(p_nbr, segment_ids, num_segments=nseg)
        den = den.at[:num_dst].add(p_self)
        a_self = p_self / den[:num_dst]
        a_nbr = p_nbr / den[segment_ids]
        out = a_self[..., None] * z_self + _segment_sum(a_nbr[..., None] * z_nbr, segment_ids, num_dst)
        out = _merge_heads(params, out)
    return out + _skip(params, self_feats)
