"""Compile the main path for a described TPU v5e chip (no chip attached).

The TPU compiler refuses what interpret mode accepts: tile-misaligned
slices, scalar-memory overflow, programs that do not fit HBM.  These tests
compile the gather kernels at the Table II widths and at real frontier row
counts, the sampler over ogbn-products' CSC, and the GraphSAGE and GAT
forwards at a full batch-1024 frontier, through ``.lower(...).compile()``
on shapes alone.  The topology is described in a
fixture (never at import), so every test worker collects the same tests
and only the worker running this file loads the TPU compiler.
"""

from __future__ import annotations

import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.graph.sampling import DeviceGraph, sample_blocks
from repro.kernels.cached_gather.kernel import (
    LANE,
    ROW_BLOCK,
    cached_gather,
    cached_gather_blocks,
)
from repro.models.gnn.models import forward, init_params

HBM_BYTES = 16 * 2**30  # one v5e chip
FANOUTS = (15, 10, 5)
BATCH = 1024
FRONTIER = BATCH * 16 * 11 * 6  # 1,081,344 input rows at fan-outs 15,10,5

# ogbn-products' CSC (Table II stand-in): col_ptr, row_index, cached_len.
PRODUCTS_CSC = (2_449_030, 61_200_000, 2_449_029)
LAST_LAYER_DRAWS = BATCH * 6 * 11 * 15  # 1,013,760 draws at fan-out 15

# (nodes, feature width): ogbn-products, the ogbn-papers100M width at a
# node count one chip holds, Reddit (Table II).
TABLES = [(2_449_029, 100), (4_000_000, 128), (232_965, 602)]


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described v5e:2x2, with the persistent compilation
    cache off: an entry written here cannot be read back without a chip."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("rows", [2**14, 2**20])
@pytest.mark.parametrize("n,f", TABLES)
@pytest.mark.parametrize("variant", ["rows", "blocks"])
def test_gather_kernel_compiles(one_chip, variant, n, f, rows):
    k = -(-f // LANE)
    hot = _sds((n // 4 * k, LANE), jnp.float32, one_chip)
    host = _sds((n * k, LANE), jnp.float32, one_chip)
    idx = _sds((rows,), jnp.int32, one_chip)
    pos = _sds((rows,), jnp.int32, one_chip)
    if variant == "rows":
        fn = functools.partial(cached_gather, feat_dim=f, interpret=False)
    else:
        fn = functools.partial(
            cached_gather_blocks, feat_dim=f, row_block=ROW_BLOCK, interpret=False
        )
    compiled = jax.jit(fn).lower(hot, host, idx, pos).compile()
    assert "tpu_custom_call" in compiled.as_text()
    out = jax.eval_shape(fn, hot, host, idx, pos)
    assert out.shape == (rows, f) and out.dtype == jnp.float32


def test_graphsage_forward_fits_one_chip(one_chip):
    params = jax.eval_shape(
        functools.partial(init_params, model="graphsage", in_dim=100, num_classes=47),
        jax.random.PRNGKey(0),
    )
    params = jax.tree.map(lambda s: _sds(s.shape, s.dtype, one_chip), params)
    feats = _sds((FRONTIER, 100), jnp.float32, one_chip)
    compiled = forward.lower(params, feats, model="graphsage", fanouts=FANOUTS).compile()
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.output_size_in_bytes + mem.temp_size_in_bytes
    assert used < HBM_BYTES, f"forward needs {used} B of HBM"
    assert jax.eval_shape(
        functools.partial(forward, model="graphsage", fanouts=FANOUTS), params, feats
    ).shape == (BATCH, 47)


def test_gat_forward_fits_one_chip(one_chip):
    """GAT at PyG's ogbn-products widths (4 heads x 128, skips) in the
    unique-frontier form the engine runs with dedup: 2^20 distinct rows
    rebuilt into the 1,081,344-row frontier.  The projected neighbour block
    alone is 1,013,760 x 512 floats (2.1 GB)."""
    params = jax.eval_shape(
        functools.partial(init_params, model="gat", in_dim=100, num_classes=47),
        jax.random.PRNGKey(0),
    )
    params = jax.tree.map(lambda s: _sds(s.shape, s.dtype, one_chip), params)
    feats = _sds((2**20, 100), jnp.float32, one_chip)
    inverse = _sds((FRONTIER,), jnp.int32, one_chip)
    with jax.default_matmul_precision("highest"):
        compiled = forward.lower(
            params, feats, model="gat", fanouts=FANOUTS, inverse_index=inverse
        ).compile()
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.output_size_in_bytes + mem.temp_size_in_bytes
    assert used < HBM_BYTES // 2, f"forward needs {used} B of HBM"
    assert jax.eval_shape(
        functools.partial(forward, model="gat", fanouts=FANOUTS), params, feats,
        inverse_index=inverse,
    ).shape == (BATCH, 47)


def test_sampler_reads_each_neighbor_once(one_chip):
    """``sample_blocks`` at sage-products' shapes (batch 1024, fan-outs
    15,10,5, dedup) gathers its 1,013,760 last-layer draws once, from the
    resident ``row_index``: the adjacency cache adds a compare, not a
    second gather."""
    g = DeviceGraph(*(_sds((n,), jnp.int32, one_chip) for n in PRODUCTS_CSC))
    assert len(jax.tree.leaves(g)) == 3
    compiled = sample_blocks.lower(
        _sds((2,), jnp.uint32, one_chip), g, _sds((BATCH,), jnp.int32, one_chip),
        FANOUTS, dedup=True, dedup_pad_id=_sds((), jnp.int32, one_chip),
    ).compile()
    text = compiled.as_text()
    entry = text[text.index("\nENTRY"):]
    entry = entry[: entry.index("\n}\n")]
    gathers = [
        line for line in entry.splitlines()
        if f"= s32[{LAST_LAYER_DRAWS}]" in line
        and "kind=kCustom" in line
        and re.search(r'op_name="[^"]*gather"', line)
    ]
    assert len(gathers) == 1, gathers
