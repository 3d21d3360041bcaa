"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.cached_gather.kernel import (
    cached_gather,
    cached_gather_blocks,
    default_interpret,
)
from repro.kernels.cached_gather.ref import cached_gather_ref
from repro.kernels.flash_attention.kernel import flash_attention_2d
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.seg_agg.kernel import seg_agg
from repro.kernels.seg_agg.ref import seg_agg_ref

RNG = np.random.default_rng(0)


@pytest.mark.parametrize("h,n,f,s", [(16, 100, 64, 32), (8, 50, 602, 7), (4, 256, 128, 200), (1, 10, 16, 1)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_cached_gather_matches_ref(h, n, f, s, dtype):
    hot = jnp.asarray(RNG.standard_normal((h, f)), dtype)
    host = jnp.asarray(RNG.standard_normal((n, f)), dtype)
    idx = jnp.asarray(RNG.integers(0, n, s), jnp.int32)
    pos = jnp.asarray(RNG.integers(-1, h, s), jnp.int32)
    out = cached_gather(hot, host, idx, pos)
    ref = cached_gather_ref(hot, host, idx, pos)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), rtol=1e-6
    )


def test_cached_gather_all_hits_and_all_misses():
    hot = jnp.asarray(RNG.standard_normal((4, 8)), jnp.float32)
    host = jnp.asarray(RNG.standard_normal((9, 8)), jnp.float32)
    idx = jnp.arange(4, dtype=jnp.int32)
    all_hit = cached_gather(hot, host, idx, jnp.arange(4, dtype=jnp.int32))
    np.testing.assert_allclose(np.asarray(all_hit), np.asarray(hot[:4]))
    all_miss = cached_gather(hot, host, idx, jnp.full((4,), -1, jnp.int32))
    np.testing.assert_allclose(np.asarray(all_miss), np.asarray(host[:4]))


def test_cached_gather_empty_index_set():
    """S=0: no kernel launch, just the empty batch buffer."""
    hot = jnp.asarray(RNG.standard_normal((4, 96)), jnp.float32)
    host = jnp.asarray(RNG.standard_normal((9, 96)), jnp.float32)
    out = cached_gather(hot, host, jnp.zeros((0,), jnp.int32), jnp.zeros((0,), jnp.int32))
    assert out.shape == (0, 96) and out.dtype == host.dtype


@pytest.mark.parametrize("f", [96, 130, 250, 602])
def test_cached_gather_non_vreg_feature_dims(f):
    """Feature dims that are not multiples of the 128-lane VREG width:
    pad-and-slice must stay bit-exact for every source row."""
    hot = jnp.asarray(RNG.standard_normal((6, f)), jnp.float32)
    host = jnp.asarray(RNG.standard_normal((40, f)), jnp.float32)
    idx = jnp.asarray(RNG.integers(0, 40, 17), jnp.int32)
    pos = jnp.asarray(RNG.integers(-1, 6, 17), jnp.int32)
    out = cached_gather(hot, host, idx, pos)
    ref = cached_gather_ref(hot, host, idx, pos)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


@pytest.mark.parametrize("gather_buffers", [1, 2, 3, 4])
def test_cached_gather_buffer_counts(gather_buffers):
    """1 slot = serial copies, 2 = double buffering, more = deeper rotation;
    the slot-reuse waits must keep every variant bit-exact."""
    hot = jnp.asarray(RNG.standard_normal((8, 160)), jnp.float32)
    host = jnp.asarray(RNG.standard_normal((64, 160)), jnp.float32)
    idx = jnp.asarray(RNG.integers(0, 64, 33), jnp.int32)
    pos = jnp.asarray(RNG.integers(-1, 8, 33), jnp.int32)
    out = cached_gather(hot, host, idx, pos, gather_buffers=gather_buffers)
    ref = cached_gather_ref(hot, host, idx, pos)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_cached_gather_rejects_bad_buffers():
    hot = jnp.zeros((1, 8), jnp.float32)
    host = jnp.zeros((2, 8), jnp.float32)
    idx = jnp.zeros((2,), jnp.int32)
    with pytest.raises(ValueError):
        cached_gather(hot, host, idx, idx, gather_buffers=0)


@pytest.mark.parametrize("h,n,f,s", [(16, 100, 64, 32), (8, 50, 602, 7), (4, 256, 128, 200)])
def test_cached_gather_blocks_matches_ref_random(h, n, f, s):
    """Arbitrary (unsorted, mixed-source) index sets: every block falls
    back to per-row copies and the output must still be bit-exact."""
    hot = jnp.asarray(RNG.standard_normal((h, f)), jnp.float32)
    host = jnp.asarray(RNG.standard_normal((n, f)), jnp.float32)
    idx = jnp.asarray(RNG.integers(0, n, s), jnp.int32)
    pos = jnp.asarray(RNG.integers(-1, h, s), jnp.int32)
    out = cached_gather_blocks(hot, host, idx, pos)
    ref = cached_gather_ref(hot, host, idx, pos)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_cached_gather_blocks_contiguous_runs():
    """Sorted ids with id-ordered slots — the dedup frontier's shape: whole
    blocks collapse to single run DMAs on both the hit and miss source."""
    n, f = 64, 128
    host = jnp.asarray(RNG.standard_normal((n, f)), jnp.float32)
    ids = jnp.asarray(np.arange(10, 42, dtype=np.int32))
    all_hit = cached_gather_blocks(host, host, ids, ids)
    np.testing.assert_array_equal(np.asarray(all_hit), np.asarray(host)[10:42])
    all_miss = cached_gather_blocks(host, host, ids, jnp.full((32,), -1, jnp.int32))
    np.testing.assert_array_equal(np.asarray(all_miss), np.asarray(host)[10:42])


def test_cached_gather_blocks_singleton_runs():
    """Strided sorted ids: every run breaks after one row (mode-0 blocks
    throughout) — the worst case must still be exact."""
    n, f = 64, 96
    hot = jnp.asarray(RNG.standard_normal((8, f)), jnp.float32)
    host = jnp.asarray(RNG.standard_normal((n, f)), jnp.float32)
    idx = jnp.asarray(np.arange(0, 34, 2, dtype=np.int32))  # stride 2: no runs
    pos = jnp.asarray(RNG.integers(-1, 8, 17), jnp.int32)
    out = cached_gather_blocks(hot, host, idx, pos)
    ref = cached_gather_ref(hot, host, idx, pos)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_cached_gather_blocks_empty_and_row_block_edges():
    """S=0 short-circuits; row_block=1 routes to the per-row kernel; a
    row_block larger than S pads to one block; non-128 feature dims keep
    the pad-and-slice bit-exact."""
    hot = jnp.asarray(RNG.standard_normal((4, 130)), jnp.float32)
    host = jnp.asarray(RNG.standard_normal((9, 130)), jnp.float32)
    empty = cached_gather_blocks(
        hot, host, jnp.zeros((0,), jnp.int32), jnp.zeros((0,), jnp.int32)
    )
    assert empty.shape == (0, 130)
    idx = jnp.asarray(RNG.integers(0, 9, 3), jnp.int32)
    pos = jnp.asarray([-1, 0, 2], jnp.int32)
    ref = cached_gather_ref(hot, host, idx, pos)
    for rb in (1, 4, 16):
        out = cached_gather_blocks(hot, host, idx, pos, row_block=rb)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    with pytest.raises(ValueError):
        cached_gather_blocks(hot, host, idx, pos, row_block=0)


@pytest.mark.parametrize("gather_buffers", [1, 2, 3])
def test_cached_gather_blocks_buffer_rotation(gather_buffers):
    hot = jnp.asarray(RNG.standard_normal((8, 160)), jnp.float32)
    host = jnp.asarray(RNG.standard_normal((64, 160)), jnp.float32)
    idx = jnp.asarray(np.sort(RNG.choice(64, 33, replace=False)).astype(np.int32))
    pos = jnp.asarray(RNG.integers(-1, 8, 33), jnp.int32)
    out = cached_gather_blocks(hot, host, idx, pos, gather_buffers=gather_buffers)
    ref = cached_gather_ref(hot, host, idx, pos)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_interpret_default_resolves_by_backend():
    assert default_interpret() == (jax.default_backend() != "tpu")


@pytest.mark.skipif(
    jax.default_backend() != "tpu", reason="compiled Pallas backend (TPU) not available"
)
def test_cached_gather_compiled_matches_interpret():
    """Where a compiled backend exists, compiled and interpret mode must
    agree bit-for-bit (same DMA schedule, same select)."""
    hot = jnp.asarray(RNG.standard_normal((8, 256)), jnp.float32)
    host = jnp.asarray(RNG.standard_normal((64, 256)), jnp.float32)
    idx = jnp.asarray(RNG.integers(0, 64, 33), jnp.int32)
    pos = jnp.asarray(RNG.integers(-1, 8, 33), jnp.int32)
    compiled = cached_gather(hot, host, idx, pos, interpret=False)
    interpreted = cached_gather(hot, host, idx, pos, interpret=True)
    np.testing.assert_array_equal(np.asarray(compiled), np.asarray(interpreted))


@pytest.mark.parametrize("s,fo,f", [(32, 5, 128), (7, 2, 602), (100, 15, 64), (1, 1, 1)])
@pytest.mark.parametrize("mode", ["sum", "mean"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_seg_agg_matches_ref(s, fo, f, mode, dtype):
    x = jnp.asarray(RNG.standard_normal((s, fo, f)), dtype)
    out = seg_agg(x, mode=mode)
    ref = seg_agg_ref(x, mode=mode)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), rtol=2e-2, atol=2e-2
    )


@pytest.mark.parametrize(
    "sq,sk,d,causal,window,cap",
    [
        (128, 128, 64, True, None, None),
        (256, 256, 128, True, None, 50.0),
        (200, 200, 64, True, 64, None),
        (128, 128, 64, False, None, None),
        (96, 160, 64, False, None, None),
        (64, 64, 128, True, 16, 30.0),
    ],
)
def test_flash_attention_matches_ref(sq, sk, d, causal, window, cap):
    q = jnp.asarray(RNG.standard_normal((sq, d)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((sk, d)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((sk, d)), jnp.float32)
    out = flash_attention_2d(q, k, v, causal=causal, window=window, softcap=cap)
    ref = attention_ref(q, k, v, causal=causal, window=window, softcap=cap)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=3e-4, atol=3e-4)


def test_flash_attention_bf16():
    q = jnp.asarray(RNG.standard_normal((128, 64)), jnp.bfloat16)
    k = jnp.asarray(RNG.standard_normal((128, 64)), jnp.bfloat16)
    v = jnp.asarray(RNG.standard_normal((128, 64)), jnp.bfloat16)
    out = flash_attention_2d(q, k, v, causal=True)
    ref = attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), rtol=5e-2, atol=5e-2
    )


def test_multi_head_wrapper_gqa():
    from repro.kernels.flash_attention.ops import multi_head_attention

    b, hq, hkv, s, d = 2, 8, 2, 64, 32
    q = jnp.asarray(RNG.standard_normal((b, hq, s, d)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((b, hkv, s, d)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((b, hkv, s, d)), jnp.float32)
    out_kernel = multi_head_attention(q, k, v, use_kernel=True)
    out_ref = multi_head_attention(q, k, v, use_kernel=False)
    assert out_kernel.shape == (b, hq, s, d)
    np.testing.assert_allclose(np.asarray(out_kernel), np.asarray(out_ref), rtol=3e-4, atol=3e-4)


def test_flash_attention_decode_shape():
    """Sq=1 against a long KV — the serving hot path through the kernel."""
    q = jnp.asarray(RNG.standard_normal((1, 64)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((1024, 64)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((1024, 64)), jnp.float32)
    # non-causal with window: the decode-style mask
    out = flash_attention_2d(q, k, v, causal=False, window=None)
    ref = attention_ref(q, k, v, causal=False, window=None)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=3e-4, atol=3e-4)
