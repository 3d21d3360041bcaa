"""The GNN layer kinds (models/gnn): GAT against a per-edge loop, its
self-loop semantics, the table that refuses an unknown model, and GAT
through every serving entry point."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.config import EngineConfig
from repro.models import gnn as gnn_models
from repro.models.gnn.layers import gat_layer, gat_segment_layer
from repro.runtime.gnn_engine import GNNInferenceEngine
from repro.runtime.gnn_serve import MultiStreamServer, make_stream_batches
from repro.runtime.sharded_serve import ShardedServer

# float32 layers against a float64 loop: each output sums at most a few
# dozen float32 products and softmax weights of order 1, so they differ by
# a few units of float32's last place (~1e-7 relative); a wrong score,
# head, neighbour or skip moves an output by the order of the output.
TOL = dict(rtol=1e-5, atol=1e-5)


def _gat_params(rng, d_in, heads, ch, *, last):
    out = ch if last else heads * ch
    return {
        "w": rng.standard_normal((d_in, heads * ch)).astype(np.float32) / np.sqrt(d_in),
        "att_src": rng.standard_normal((heads, ch)).astype(np.float32),
        "att_dst": rng.standard_normal((heads, ch)).astype(np.float32),
        "b": rng.standard_normal(out).astype(np.float32),
        "w_skip": rng.standard_normal((d_in, out)).astype(np.float32),
        "b_skip": rng.standard_normal(out).astype(np.float32),
    }


def _loop_gat(p, h, num_dst, fanout):
    """The GAT equations one destination, one head and one edge at a time,
    over the block's ``[self | neighbors]`` layout, in float64."""
    p = {k: np.asarray(v, np.float64) for k, v in p.items()}
    h = np.asarray(h, np.float64)
    heads, ch = p["att_src"].shape
    concat = p["b"].shape[0] == heads * ch
    out = []
    for i in range(num_dst):
        srcs = [i] + [num_dst + i * fanout + t for t in range(fanout)]
        per_head = []
        for hd in range(heads):
            cols = slice(hd * ch, (hd + 1) * ch)
            z_i = h[i] @ p["w"][:, cols]
            scores, zs = [], []
            for j in srcs:
                z_j = h[j] @ p["w"][:, cols]
                e = z_j @ p["att_src"][hd] + z_i @ p["att_dst"][hd]
                scores.append(e if e > 0 else 0.2 * e)
                zs.append(z_j)
            a = np.exp(np.asarray(scores) - max(scores))
            a /= a.sum()
            per_head.append(sum(a_j * z_j for a_j, z_j in zip(a, zs)))
        att = np.concatenate(per_head) if concat else np.mean(per_head, axis=0)
        out.append(att + p["b"] + h[i] @ p["w_skip"] + p["b_skip"])
    return np.stack(out)


@pytest.mark.parametrize("last", [False, True], ids=["concat", "mean"])
def test_gat_layer_matches_a_per_edge_loop(last):
    rng = np.random.default_rng(3)
    num_dst, fanout, d_in, heads, ch = 3, 2, 5, 2, 4
    p = _gat_params(rng, d_in, heads, ch, last=last)
    h = rng.standard_normal((num_dst * (1 + fanout), d_in)).astype(np.float32)
    got = np.asarray(gat_layer(p, jnp.asarray(h), num_dst, fanout))
    assert got.shape == (num_dst, ch if last else heads * ch)
    np.testing.assert_allclose(got, _loop_gat(p, h, num_dst, fanout), **TOL)


def test_gat_zero_degree_node_is_its_projection_plus_skip():
    """A node without in-edges attends to itself alone: in layer-wise form
    (degree 0) and in sampled form (the sampler fills its draws with the
    node itself) its output is ``z_i`` (heads merged) plus ``b`` and the skip."""
    rng = np.random.default_rng(5)
    d_in, heads, ch, fanout = 6, 2, 3, 4
    p = _gat_params(rng, d_in, heads, ch, last=False)
    x = rng.standard_normal((2, d_in)).astype(np.float32)
    want = x[1] @ p["w"] + p["b"] + x[1] @ p["w_skip"] + p["b_skip"]

    # layer-wise: node 0 has two in-edges, node 1 none; one pad edge
    nbr = rng.standard_normal((4, d_in)).astype(np.float32)
    seg = jnp.asarray([0, 0, 2, 2], jnp.int32)
    deg = jnp.asarray([2.0, 0.0])
    seg_out = np.asarray(gat_segment_layer(p, jnp.asarray(x), jnp.asarray(nbr), seg, deg, 2))
    np.testing.assert_allclose(seg_out[1], want, **TOL)

    # sampled: destination 0 = node 1, its draws all node 1 itself
    block = np.concatenate([x[1:], np.repeat(x[1:], fanout, axis=0)])
    sampled = np.asarray(gat_layer(p, jnp.asarray(block), 1, fanout))
    np.testing.assert_allclose(sampled[0], want, **TOL)


@pytest.mark.parametrize("entry", ["forward", "forward_layer", "init_params"])
def test_an_unknown_model_raises(entry):
    """A name outside the layer-kind table raises; it never runs GCN."""
    params = gnn_models.init_params(jax.random.PRNGKey(0), "gcn", 4, 3, hidden=4, n_layers=1)
    calls = {
        "forward": lambda: gnn_models.forward(
            params, jnp.ones((3, 4)), model="gcnn", fanouts=(2,)
        ),
        "forward_layer": lambda: gnn_models.forward_layer(
            params[0], jnp.ones((2, 4)), jnp.ones((2, 4)), jnp.zeros(2, jnp.int32),
            jnp.ones(2), model="gcnn", num_dst=2,
        ),
        "init_params": lambda: gnn_models.init_params(jax.random.PRNGKey(0), "gcnn", 4, 3),
    }
    with pytest.raises(ValueError, match="unknown GNN model 'gcnn'"):
        calls[entry]()


def test_gat_default_params_are_the_paper_widths():
    """Without ``heads``, GAT takes its paper value (4), concatenates it on
    the hidden layers and averages it on the last."""
    params = gnn_models.init_params(jax.random.PRNGKey(0), "gat", 100, 47)
    assert [p["w"].shape for p in params] == [(100, 512), (512, 512), (512, 188)]
    assert [p["b"].shape for p in params] == [(512,), (512,), (47,)]
    assert [gnn_models.layer_kind("gat").out_dim(p) for p in params] == [512, 512, 47]


FANOUTS = (3, 2)
BATCH = 64
STREAM_SEEDS = [100, 101]


def _gat_engine(dataset):
    params = gnn_models.init_params(
        jax.random.PRNGKey(1), "gat", dataset.spec.feat_dim, dataset.spec.num_classes,
        hidden=8, n_layers=len(FANOUTS), heads=2,
    )
    eng = GNNInferenceEngine(dataset, model="gat", fanouts=FANOUTS, batch_size=BATCH,
                             params=params)
    eng.prepare("dci", total_cache_bytes=200_000, n_presample=2, stream_seeds=STREAM_SEEDS)
    return eng


def test_gat_serves_through_the_engine_and_both_servers(small_dataset):
    """GAT on the normal path: the engine at depth 2 with dedup and prefetch
    gives the serial engine's logits, and the sharded server the
    multi-stream server's, bit for bit."""
    eng = _gat_engine(small_dataset)
    serial = eng.run(max_batches=3, config=EngineConfig(pipeline_depth=1, dedup=False),
                     collect_outputs=True)
    base = [np.asarray(o) for o in eng.last_outputs]
    fast = eng.run(max_batches=3, config=EngineConfig(pipeline_depth=2, dedup=True, prefetch=True),
                   collect_outputs=True)
    assert [o.shape for o in base] == [(BATCH, small_dataset.spec.num_classes)] * 3
    assert all(np.isfinite(o).all() for o in base)
    for a, b in zip(base, eng.last_outputs):
        np.testing.assert_array_equal(a, np.asarray(b))
    assert fast.attn_edges == serial.attn_edges > 0

    queues = make_stream_batches(small_dataset, num_streams=2, batches_per_stream=2,
                                 batch_size=BATCH, seed=7)
    outs = []
    for server in (MultiStreamServer(eng), ShardedServer(eng, num_shards=4)):  # window 2
        for sid, q in enumerate(queues):
            server.add_stream(q, seed=STREAM_SEEDS[sid], collect_outputs=True)
        server.run()
        outs.append([np.asarray(o) for s in server.streams for o in s.runtime.outputs])
    assert len(outs[0]) == len(outs[1]) == 4
    for a, b in zip(*outs):
        np.testing.assert_array_equal(a, b)
