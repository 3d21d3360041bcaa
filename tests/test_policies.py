"""All cache policies produce runnable pipelines with sane stats."""

import numpy as np
import pytest

from repro.core.policies import POLICIES, prepare
from repro.runtime.gnn_engine import GNNInferenceEngine

KW = dict(total_cache_bytes=200_000, fanouts=(3, 2), batch_size=64, n_presample=2)


@pytest.mark.parametrize("policy", sorted(POLICIES))
def test_policy_end_to_end(small_dataset, policy):
    eng = GNNInferenceEngine(small_dataset, fanouts=(3, 2), batch_size=64)
    eng.prepare(policy, total_cache_bytes=200_000, n_presample=2)
    rep = eng.run(max_batches=3)
    assert rep.num_batches == 3
    assert 0 <= rep.adj_hit_rate <= 1
    assert 0 <= rep.feat_hit_rate <= 1
    assert rep.total_seconds > 0
    if policy == "dgl":
        assert rep.adj_hit_rate == 0 or rep.adj_hit_rate < 0.2  # only self-loops
        assert rep.feat_hit_rate == 0
    if policy in ("dci", "ducati"):
        assert rep.adj_hit_rate > 0
    if policy in ("dci", "sci", "ducati"):
        assert rep.feat_hit_rate > 0
    if policy == "aci":  # the whole budget to adjacency: no feature row cached
        assert rep.feat_hits == 0 and rep.adj_hit_rate > 0


def test_dci_allocation_follows_eq1(small_dataset):
    pipe = prepare("dci", small_dataset, **KW)
    a = pipe.caches.allocation
    assert a.adj_bytes + a.feat_bytes == KW["total_cache_bytes"]
    assert 0.0 <= a.sample_fraction <= 1.0


def test_sci_all_budget_to_features(small_dataset):
    pipe = prepare("sci", small_dataset, **KW)
    a = pipe.caches.allocation
    assert a.adj_bytes == 0
    assert a.feat_bytes == KW["total_cache_bytes"]
    assert pipe.caches.adj_cached_elements == 0


def test_sci_feature_hits_monotone_in_capacity(small_dataset):
    """Fig. 2: a larger feature-only budget never hits less.  SCI takes no
    Eq. 1 split, so the hits are exact counts, free of the wall clock."""
    hits = []
    for cap in (0, 20_000, 80_000, 320_000, 1_280_000):
        eng = GNNInferenceEngine(small_dataset, fanouts=(3, 2), batch_size=64)
        eng.prepare("sci", total_cache_bytes=cap, n_presample=2)
        hits.append(eng.run(max_batches=4).feat_hits)
    assert hits[0] == 0
    assert hits == sorted(hits) and hits[-1] > hits[1]


def test_sci_hit_rate_stable_by_eight_presample_batches(small_dataset):
    """Fig. 11: eight pre-sampling batches already rank the hot rows about
    as well as sixteen do (SCI, so no wall-clock split enters)."""
    rates = {}
    for n in (8, 16):
        eng = GNNInferenceEngine(small_dataset, fanouts=(3, 2), batch_size=64)
        eng.prepare("sci", total_cache_bytes=100_000, n_presample=n)
        rates[n] = eng.run(max_batches=8).feat_hit_rate
    assert abs(rates[16] - rates[8]) < 0.05


def test_rain_batch_order_is_permutation(small_dataset):
    pipe = prepare("rain", small_dataset, batch_size=64)
    nb = max(len(small_dataset.test_idx) // 64, 1)
    order = np.sort(pipe.batch_order)
    np.testing.assert_array_equal(order, np.arange(nb))
    assert pipe.reuse_prev_batch


def test_ducati_prep_slower_than_dci(small_dataset, jit_warm):
    # The shared jit_warm fixture has already compiled the presample/fill
    # programs, so both prepares below measure steady-state prep cost.
    t_dci = prepare("dci", small_dataset, **KW).prep_seconds
    t_duc = prepare("ducati", small_dataset, **KW).prep_seconds
    # DUCATI gathers 4x the statistics + global sorts + curve fits.
    assert t_duc > t_dci


def test_unknown_policy_raises(small_dataset):
    with pytest.raises(KeyError):
        prepare("nope", small_dataset, **KW)
