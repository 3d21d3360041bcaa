"""The miss-row pack's reused host buffers (``FeatureStore.pack_ring``).

Load-bearing guarantees:

  * a pack written into a reused buffer is bit-for-bit the fresh
    ``np.zeros`` + fancy-index pack, pad rows included, however the miss
    count falls and rises between packs that share a buffer;
  * rewriting a buffer never changes an array already staged from it,
    also where the backend puts the buffer zero-copy;
  * buffers are allocated on a bucket's first pack only: a warm run
    allocates none, and a refreshed store inherits them;
  * the host mirror the pack reads is row-major whatever layout the
    device hands back (a TPU's is column-major).
"""

import jax
import numpy as np
import pytest

from repro.core.config import EngineConfig
from repro.core.trace import Tracer
from repro.graph.features import FeatureStore, build_feature_cache, refresh_feature_cache
from repro.graph.sampling import pow2_bucket
from repro.runtime.gnn_engine import GNNInferenceEngine

N, F, S = 600, 12, 256


@pytest.fixture
def store():
    rng = np.random.default_rng(3)
    feats = rng.standard_normal((N, F), dtype=np.float32)
    counts = rng.integers(0, 9, N).astype(np.int64)
    return build_feature_cache(feats, counts, capacity_bytes=N // 2 * F * 4)


def _nodes_with_misses(store, k, rng, size=S):
    """``size`` ids of which exactly ``k`` miss the cache, shuffled."""
    pos = store.position_np()
    missed, cached = np.nonzero(pos < 0)[0], np.nonzero(pos >= 0)[0]
    nodes = np.concatenate([rng.choice(missed, k, replace=False), rng.choice(cached, size - k)])
    return rng.permutation(nodes).astype(np.int32)


def _fresh_pack(store, nodes):
    """The pack as it was built before the ring: a fresh zeroed buffer."""
    miss = np.nonzero(store.position_np()[nodes] < 0)[0].astype(np.int32)
    bucket = pow2_bucket(miss.size, nodes.size)
    rows = np.zeros((bucket, store.feat_dim), store.host_np().dtype)
    rows[: miss.size] = store.host_np()[nodes[miss]]
    idx = np.full(bucket, nodes.size, np.int32)
    idx[: miss.size] = miss
    pack_pos = np.zeros(nodes.size, np.int32)
    pack_pos[miss] = np.arange(miss.size, dtype=np.int32)
    return rows, idx, pack_pos


def _align_ring(store, buckets):
    """Allocate ``buckets``' buffers, then swap each for a 64-byte-aligned
    zeroed one, which the CPU backend puts zero-copy."""
    ring = store.pack_ring()
    for b in buckets:
        ring.take(b)
    for slot in (s for slots in ring.slots.values() for s in slots):
        raw = np.zeros(slot.buf.nbytes + 64, np.uint8)
        off = -raw.ctypes.data % 64
        slot.buf = raw[off : off + slot.buf.nbytes].view(slot.buf.dtype).reshape(slot.buf.shape)
        assert slot.buf.ctypes.data % 64 == 0
    probe = next(iter(ring.slots.values()))[0].buf
    put = jax.device_put(probe)
    if jax.default_backend() == "cpu":
        assert put.unsafe_buffer_pointer() == probe.ctypes.data  # the hazard is live


@pytest.mark.parametrize("aligned", [False, True])
@pytest.mark.parametrize("pack_in_thread", [True, False])
def test_reused_pack_matches_fresh_pack(store, pack_in_thread, aligned):
    """More packs than a bucket has buffers, with miss counts that fall and
    rise inside bucket 64 (and a detour through 32): every staged pack,
    held to the end, equals the fresh pack bit for bit."""
    rng = np.random.default_rng(11)
    counts = [60, 40, 63, 33, 20, 64, 34, 61, 17, 45, 36, 62]
    if aligned:
        _align_ring(store, {pow2_bucket(k, S) for k in counts})
    staged, expected = [], []
    for k in counts:
        nodes = _nodes_with_misses(store, k, rng)
        expected.append(_fresh_pack(store, nodes))
        staged.append(store.prefetch_misses(nodes, pack_in_thread=pack_in_thread))
    assert store.pack_ring().slots.keys() == {32, 64}
    for k, got, (rows, idx, pack_pos) in zip(counts, staged, expected):
        assert got.num_miss == k and got.staged_rows == pow2_bucket(k, S)
        np.testing.assert_array_equal(np.asarray(got.rows), rows)
        np.testing.assert_array_equal(np.asarray(got.idx), idx)
        np.testing.assert_array_equal(np.asarray(got.pack_pos), pack_pos)


def test_warm_run_allocates_no_pack_buffers_and_refresh_inherits_them(small_dataset):
    eng = GNNInferenceEngine(small_dataset, fanouts=(3, 2), batch_size=64)
    eng.prepare("dci", total_cache_bytes=200_000, n_presample=2)
    cfg = EngineConfig(pipeline_depth=2, dedup=True, prefetch=True)
    first = eng.run(config=cfg, max_batches=4, warmup=False)  # its packs warm the ring
    store = eng.pipeline.caches.store
    ring = store.pack_ring()
    warm = ring.allocs
    assert first.pack_buffer_allocs == warm == ring.SLOTS * len(ring.slots) > 0
    tr = Tracer()
    again = eng.run(config=cfg, max_batches=4, warmup=False, tracer=tr)
    assert again.pack_buffer_allocs == 0 and ring.allocs == warm
    assert again.staged_rows == first.staged_rows > 0
    packs = [e for e in tr.events if e["name"] == "prefetch:pack"]
    assert len(packs) == again.num_batches == 4
    assert all(e["args"]["pack_buffer_allocs"] == 0 for e in packs)

    counts = np.random.default_rng(5).integers(0, 9, store.num_nodes).astype(np.int64)
    refreshed, stats = refresh_feature_cache(store, counts, capacity_bytes=150_000)
    assert stats.changed and refreshed.pack_ring() is ring
    rng = np.random.default_rng(2)
    for bucket in sorted(ring.slots):  # a miss count on each warm bucket's edge
        nodes = _nodes_with_misses(refreshed, bucket, rng, size=2 * bucket)
        got = refreshed.prefetch_misses(nodes)
        assert got.pack_buffer_allocs == 0 and got.staged_rows == bucket
        np.testing.assert_array_equal(np.asarray(got.rows), _fresh_pack(refreshed, nodes)[0])
    assert ring.allocs == warm


def test_host_mirror_is_row_major(store):
    """A column-major table (as a TPU hands it back) is mirrored row-major,
    bit for bit, and the pack from it is the fresh pack."""
    table = store.host_np()
    col_major = FeatureStore(
        host_table=np.asfortranarray(table),
        hot_table=store.hot_table,
        position_map=store.position_map,
    )
    mirror = col_major.host_np()
    assert mirror.flags.c_contiguous and not np.asfortranarray(table).flags.c_contiguous
    np.testing.assert_array_equal(mirror, table)
    nodes = _nodes_with_misses(col_major, 40, np.random.default_rng(4))
    got = col_major.prefetch_misses(nodes)
    np.testing.assert_array_equal(np.asarray(got.rows), _fresh_pack(store, nodes)[0])
