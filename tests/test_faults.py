"""Fault injection and fault-tolerant serving (core/faults.py, core/retry.py).

The load-bearing guarantees:

  * determinism — a FaultPlan replays bit-for-bit: the same plan against
    the same call sequence triggers the same faults (and the retry
    backoff schedule is a pure function of (policy, key));
  * zero-diff when disabled — fault knobs on but no plan (or an empty
    plan) leave outputs, hit accounting, and RNG draws bit-identical to
    the pre-fault-subsystem serve, across the dedup × prefetch grid and
    the sharded server;
  * recovery semantics — retry recovers transient faults bit-identically,
    degraded mode keeps availability at 1.0 with per-request marking,
    shed drops exactly the failing request, fail-fast drains and records
    the error instead of dropping work silently;
  * transactional refresh — a refresh that dies mid-apply rolls back to
    the byte-identical old epoch and serving continues against it;
  * shard failover — a lost shard's id range is served from the host
    mirror bit-identically until rejoin, hit sums still tiling the
    global counters.
"""

import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core.config import EngineConfig, ServeConfig
from repro.core.faults import (
    SITES,
    FaultInjector,
    FaultPlan,
    FaultRule,
    InjectedFault,
)
from repro.core.retry import (
    RetryExhausted,
    RetryPolicy,
    StageTimeout,
    call_with_retry,
)
from repro.runtime.gnn_engine import GNNInferenceEngine
from repro.runtime.gnn_serve import MultiStreamServer, make_stream_batches

FANOUTS = (3, 2)
BATCH = 64
KW = dict(total_cache_bytes=200_000, n_presample=2)
STREAM_SEEDS = [100, 101, 102]


def _shared_engine(dataset, policy="dci"):
    eng = GNNInferenceEngine(dataset, fanouts=FANOUTS, batch_size=BATCH)
    eng.prepare(policy, stream_seeds=STREAM_SEEDS, **KW)
    return eng


def _queues(dataset, n=2, batches=3):
    return make_stream_batches(
        dataset, num_streams=n, batches_per_stream=batches, batch_size=BATCH, seed=7
    )


def _fast_retry(**kw):
    """A retry config whose sleeps are microscopic (tests never wait)."""
    base = dict(fault_policy="retry", retry_attempts=3, retry_backoff_ms=0.01)
    base.update(kw)
    return base


def _serve(engine, queues, *, cfg=None, injector=None, refresh=None, **run_kw):
    srv = MultiStreamServer(engine, config=cfg, injector=injector, refresh=refresh)
    for sid, q in enumerate(queues):
        srv.add_stream(q, seed=STREAM_SEEDS[sid], collect_outputs=True)
    rep = srv.run(**run_kw)
    outs = [[np.asarray(o) for o in s.runtime.outputs] for s in srv.streams]
    return srv, rep, outs


def _assert_same_serve(rep_a, outs_a, rep_b, outs_b):
    assert (rep_a.feat_hits, rep_a.feat_lookups) == (rep_b.feat_hits, rep_b.feat_lookups)
    assert (rep_a.adj_hits, rep_a.adj_lookups) == (rep_b.adj_hits, rep_b.adj_lookups)
    for a_list, b_list in zip(outs_a, outs_b):
        assert len(a_list) == len(b_list)
        for a, b in zip(a_list, b_list):
            np.testing.assert_array_equal(a, b)


# ------------------------------------------------------------ plan (unit)


def test_plan_json_round_trip(tmp_path):
    plan = FaultPlan(
        seed=13,
        rules=(
            FaultRule("host_fetch", probability=0.25, start_after=4, max_faults=7),
            FaultRule("prefetch", kind="delay", latency_s=0.002, burst_period=8, burst_length=2),
            FaultRule("shard_exchange", shard=1, down_for=3),
        ),
    )
    assert FaultPlan.from_dict(plan.to_dict()) == plan
    path = tmp_path / "plan.json"
    plan.save(str(path))
    assert FaultPlan.load(str(path)) == plan
    assert plan.sites == ("host_fetch", "prefetch", "shard_exchange")
    assert plan.rule_for("host_fetch").max_faults == 7
    assert plan.rule_for("refresh_fill") is None


def test_plan_and_rule_validation():
    with pytest.raises(ValueError):
        FaultRule("not-a-site")
    with pytest.raises(ValueError):
        FaultRule("host_fetch", kind="explode")
    with pytest.raises(ValueError):
        FaultRule("host_fetch", probability=1.5)
    with pytest.raises(ValueError):
        FaultRule("host_fetch", burst_period=4)  # length missing
    with pytest.raises(ValueError):
        FaultRule("host_fetch", burst_period=2, burst_length=5)
    with pytest.raises(ValueError):  # duplicate site
        FaultPlan(rules=(FaultRule("host_fetch"), FaultRule("host_fetch")))
    with pytest.raises(ValueError):  # unknown JSON field
        FaultRule.from_dict({"site": "host_fetch", "blast_radius": 3})


def test_injector_schedule_is_deterministic_and_capped():
    plan = FaultPlan(
        seed=5,
        rules=(FaultRule("host_fetch", probability=0.4, start_after=3, max_faults=4),),
    )

    def fault_calls():
        inj = FaultInjector(plan)
        hits = []
        for call in range(60):
            try:
                inj.check("host_fetch")
            except InjectedFault as err:
                assert err.site == "host_fetch" and err.call == call
                hits.append(call)
        return hits, inj

    hits_a, inj = fault_calls()
    hits_b, _ = fault_calls()
    assert hits_a == hits_b  # pure function of (plan, call index)
    assert len(hits_a) == 4 and min(hits_a) >= 3  # armed after start_after, capped
    assert inj.counts() == {"host_fetch": {"calls": 60, "faults": 4}}
    assert inj.active("host_fetch") and not inj.active("adj_fetch")
    # unlisted sites count calls but never fault
    inj.check("adj_fetch")
    assert inj.counts()["adj_fetch"] == {"calls": 1, "faults": 0}
    with pytest.raises(ValueError):
        inj.check("not-a-site")


def test_injector_draws_do_not_depend_on_window_phase():
    """The k-th call's probability draw is consumed armed or not, so the
    fault decision at call k is invariant to start_after: a late-armed
    rule faults at exactly the early rule's post-arming fault calls."""

    def hits(start_after):
        plan = FaultPlan(
            seed=11, rules=(FaultRule("host_fetch", probability=0.3, start_after=start_after),)
        )
        inj = FaultInjector(plan)
        out = []
        for call in range(80):
            try:
                inj.check("host_fetch")
            except InjectedFault:
                out.append(call)
        return out

    early, late = hits(0), hits(25)
    assert late == [c for c in early if c >= 25]


def test_injector_burst_and_delay_kinds():
    sleeps = []
    plan = FaultPlan(
        rules=(
            FaultRule(
                "prefetch", kind="delay", latency_s=0.5, burst_period=4, burst_length=2
            ),
        )
    )
    inj = FaultInjector(plan, sleep=sleeps.append)
    for _ in range(8):
        inj.check("prefetch")  # delay kind never raises
    # armed calls are the first 2 of every 4-call window: 0,1,4,5
    assert sleeps == [0.5] * 4
    assert inj.delays["prefetch"] == 4
    assert inj.counts()["prefetch"] == {"calls": 8, "faults": 4}


# ------------------------------------------------------------ retry (unit)


def test_backoff_delays_deterministic_and_bounded():
    pol = RetryPolicy(max_attempts=5, backoff_s=1e-3, max_backoff_s=4e-3, jitter=0.5)
    d1 = pol.backoff_delays(("host_fetch", 3))
    d2 = pol.backoff_delays(("host_fetch", 3))
    assert d1 == d2 and len(d1) == 4
    assert all(0.0 <= d <= pol.max_backoff_s * (1 + pol.jitter) for d in d1)
    assert sum(d1) <= pol.total_backoff_bound()
    # distinct keys get distinct jitter schedules
    others = [pol.backoff_delays(("host_fetch", k)) for k in range(8)]
    assert any(d != d1 for d in others)


def test_call_with_retry_recovers_then_exhausts():
    pol = RetryPolicy(max_attempts=3, backoff_s=0.0, jitter=0.0)
    attempts, retries = [], []

    def flaky():
        attempts.append(1)
        if len(attempts) < 3:
            raise InjectedFault("host_fetch", len(attempts))
        return 42

    got = call_with_retry(
        flaky,
        policy=pol,
        retryable=(InjectedFault,),
        on_retry=lambda a, d, e: retries.append((a, type(e).__name__)),
        sleep=lambda _s: None,
    )
    assert got == 42 and len(attempts) == 3
    assert retries == [(1, "InjectedFault"), (2, "InjectedFault")]

    def always():
        raise InjectedFault("host_fetch", 0)

    with pytest.raises(RetryExhausted) as ei:
        call_with_retry(always, policy=pol, retryable=(InjectedFault,), sleep=lambda _s: None)
    assert ei.value.attempts == 3 and isinstance(ei.value.last, InjectedFault)


def test_call_with_retry_propagates_non_retryable_immediately():
    calls = []

    def bug():
        calls.append(1)
        raise ValueError("real bug, not a fault")

    with pytest.raises(ValueError):
        call_with_retry(
            bug,
            policy=RetryPolicy(max_attempts=4, backoff_s=0.0, jitter=0.0),
            retryable=(InjectedFault,),
            sleep=lambda _s: None,
        )
    assert len(calls) == 1  # no retry budget spent on real bugs


def test_per_attempt_timeout_discards_late_success():
    ticks = iter(range(100))
    pol = RetryPolicy(max_attempts=2, backoff_s=0.0, jitter=0.0, timeout_s=0.5)
    with pytest.raises(RetryExhausted) as ei:
        call_with_retry(
            lambda: "late",  # every attempt "succeeds" after 1 fake second
            policy=pol,
            retryable=(InjectedFault,),
            sleep=lambda _s: None,
            clock=lambda: float(next(ticks)),
        )
    assert isinstance(ei.value.last, StageTimeout)
    assert ei.value.last.timeout_s == 0.5
    # without a timeout the same thunk returns on attempt 1
    assert call_with_retry(lambda: "ok", policy=RetryPolicy(), sleep=lambda _s: None) == "ok"


# ----------------------------------------------------- properties (hypothesis)


@settings(max_examples=50, deadline=None)
@given(
    max_attempts=st.integers(1, 6),
    backoff_ms=st.floats(0.0, 10.0, allow_nan=False),
    multiplier=st.floats(1.0, 3.0, allow_nan=False),
    max_backoff_ms=st.floats(0.0, 20.0, allow_nan=False),
    jitter=st.floats(0.0, 1.0, allow_nan=False),
    seed=st.integers(0, 2**31),
    key=st.integers(0, 10_000),
)
def test_property_backoff_schedule_bounds(
    max_attempts, backoff_ms, multiplier, max_backoff_ms, jitter, seed, key
):
    """Every jittered schedule is deterministic per key, per-delay bounded
    by max_backoff * (1 + jitter), and summed below the closed-form bound."""
    pol = RetryPolicy(
        max_attempts=max_attempts,
        backoff_s=backoff_ms * 1e-3,
        backoff_multiplier=multiplier,
        max_backoff_s=max_backoff_ms * 1e-3,
        jitter=jitter,
        seed=seed,
    )
    delays = pol.backoff_delays(key)
    assert delays == pol.backoff_delays(key)
    assert len(delays) == max_attempts - 1
    cap = pol.max_backoff_s * (1.0 + jitter) + 1e-12
    assert all(0.0 <= d <= cap for d in delays)
    assert sum(delays) <= pol.total_backoff_bound() + 1e-12


@settings(max_examples=40, deadline=None)
@given(
    seed=st.integers(0, 2**31),
    probability=st.floats(0.0, 1.0, allow_nan=False),
    start_after=st.integers(0, 20),
    max_faults=st.one_of(st.none(), st.integers(0, 10)),
    calls=st.integers(0, 60),
    site=st.sampled_from(SITES),
)
def test_property_injector_replay_is_pure(
    seed, probability, start_after, max_faults, calls, site
):
    """Two injectors over the same plan agree on every fault decision, the
    faults respect the armed window, and the cap is never exceeded."""
    plan = FaultPlan(
        seed=seed,
        rules=(
            FaultRule(
                site, probability=probability, start_after=start_after, max_faults=max_faults
            ),
        ),
    )

    def run():
        inj = FaultInjector(plan)
        out = []
        for call in range(calls):
            try:
                inj.check(site)
            except InjectedFault:
                out.append(call)
        return out

    hits_a, hits_b = run(), run()
    assert hits_a == hits_b
    assert all(c >= start_after for c in hits_a)
    if max_faults is not None:
        assert len(hits_a) <= max_faults
    if probability == 1.0 and max_faults is None:
        assert hits_a == list(range(start_after, calls))


@settings(max_examples=5, deadline=None)
@given(failed_attempts=st.integers(1, 3))
def test_property_refresh_rollback_is_byte_identical(small_dataset, failed_attempts):
    """However many refresh attempts die mid-apply, the cache stays on the
    old epoch's exact objects (JAX arrays are immutable, so object
    identity IS byte identity) and a later clean refresh still lands."""
    eng = _shared_engine(small_dataset)
    caches = eng.pipeline.caches
    stats = eng.pipeline.presample
    before = (caches.dgraph, caches.store, caches.allocation, caches.epoch)
    plan = FaultPlan(rules=(FaultRule("refresh_fill", max_faults=failed_attempts),))
    inj = FaultInjector(plan)
    for _ in range(failed_attempts):
        with pytest.raises(InjectedFault):
            caches.refresh(
                allocation=caches.allocation,
                node_counts=stats.node_counts,
                edge_counts=stats.edge_counts,
                injector=inj,
            )
        assert (caches.dgraph, caches.store, caches.allocation, caches.epoch) == before
    # the injector's cap is spent: the next refresh commits
    delta = caches.refresh(
        allocation=caches.allocation,
        node_counts=stats.node_counts,
        edge_counts=stats.edge_counts,
        injector=inj,
    )
    assert caches.epoch == before[3] + 1 and delta.epoch == caches.epoch


# ------------------------------------------------- serving: zero-diff baseline


@pytest.mark.parametrize("dedup", [False, True])
@pytest.mark.parametrize("prefetch", [False, True])
def test_fault_knobs_without_faults_are_bit_identical(small_dataset, dedup, prefetch):
    """Retry policy armed, degraded mode on, an injector with an EMPTY
    plan installed — and the serve is still bit-for-bit the plain one:
    no RNG draws, no accounting drift, nothing on any knob combination."""
    eng = _shared_engine(small_dataset)
    queues = _queues(small_dataset)
    engine_cfg = EngineConfig(pipeline_depth=2, dedup=dedup, prefetch=prefetch)
    _, rb, ob = _serve(eng, queues, cfg=ServeConfig(engine=engine_cfg))
    cfg = ServeConfig(
        engine=engine_cfg, **_fast_retry(degraded_mode=True, retry_timeout_ms=10_000.0)
    )
    srv, rf, of = _serve(eng, queues, cfg=cfg, injector=FaultInjector(FaultPlan()))
    _assert_same_serve(rb, ob, rf, of)
    assert rf.availability == 1.0 and rf.requests_retried == 0
    assert rf.requests_degraded == 0
    assert all(v["faults"] == 0 for v in rf.faults.values())  # calls charged, none fault
    assert srv.injector is not None and not srv.injector.enabled


# ---------------------------------------------------- serving: fault policies


def test_retry_recovers_transient_faults_bit_identically(small_dataset):
    """A bounded burst of miss-path faults under the retry policy: every
    batch completes and outputs + hit accounting equal the fault-free run
    (site ops are idempotent, so a retried gather is THE gather)."""
    eng = _shared_engine(small_dataset)
    queues = _queues(small_dataset)
    cfg0 = ServeConfig(engine=EngineConfig(pipeline_depth=2))
    _, rb, ob = _serve(eng, queues, cfg=cfg0)
    plan = FaultPlan(
        seed=3,
        rules=(
            FaultRule("host_fetch", start_after=1, max_faults=2),
            FaultRule("adj_fetch", start_after=2, max_faults=1),
        ),
    )
    cfg = cfg0.replace(**_fast_retry())
    srv, rf, of = _serve(eng, queues, cfg=cfg, injector=FaultInjector(plan))
    _assert_same_serve(rb, ob, rf, of)
    assert rf.availability == 1.0 and rf.requests_shed == 0
    assert rf.requests_retried > 0
    assert rf.faults["host_fetch"]["faults"] == 2
    assert rf.faults["adj_fetch"]["faults"] == 1
    assert sum(s.runtime.stage_retries for s in srv.streams) >= 3
    assert rf.summary()["fault_policy"] == "retry"


def test_degraded_mode_serves_cache_only_when_miss_path_is_down(small_dataset):
    """host_fetch down for the whole run: with degraded mode the serve
    completes everything from cache-hit rows (miss rows zeroed), marks
    each affected request, and availability stays 1.0."""
    eng = _shared_engine(small_dataset)
    queues = _queues(small_dataset)
    plan = FaultPlan(rules=(FaultRule("host_fetch"),))  # always down
    cfg = ServeConfig(
        engine=EngineConfig(pipeline_depth=2),
        **_fast_retry(retry_attempts=2, degraded_mode=True),
    )
    srv, rep, outs = _serve(eng, queues, cfg=cfg, injector=FaultInjector(plan))
    offered = sum(len(q) for q in _queues(small_dataset))
    assert rep.total_batches == offered and rep.availability == 1.0
    assert rep.requests_degraded == offered and rep.requests_shed == 0
    assert all(s.batches_degraded == len(outs[i]) for i, s in enumerate(srv.streams))
    assert sum(s.runtime.degraded_batches for s in srv.streams) == offered
    # hit accounting is untouched: degraded gathers count the same lookups
    assert rep.feat_lookups > 0 and rep.feat_hits > 0


def test_degraded_retry_holds_availability_where_fail_fast_collapses(small_dataset):
    """One seeded plan of transient miss-path faults, two policies: the
    fail-fast serve dies at the first fault and answers at most half the
    offer, while retry plus degraded mode answers all of it, some requests
    retried and one served cache-only.  The plan is a pure function of
    (seed, call index), so both availabilities are exact."""
    eng = _shared_engine(small_dataset)
    queues = _queues(small_dataset)
    plan = FaultPlan(seed=2, rules=(FaultRule("host_fetch", probability=0.3),))
    cfg = ServeConfig(engine=EngineConfig(pipeline_depth=2))
    _, ff, _ = _serve(eng, queues, cfg=cfg, injector=FaultInjector(plan), raise_on_error=False)
    dg_cfg = cfg.replace(**_fast_retry(degraded_mode=True))
    _, dg, _ = _serve(eng, queues, cfg=dg_cfg, injector=FaultInjector(plan))
    assert ff.error is not None and ff.availability <= 0.5
    assert dg.availability >= 0.99 and dg.requests_shed == 0
    assert dg.requests_retried > 0 and dg.requests_degraded > 0


def test_kernel_faults_fall_back_to_the_table_route(small_dataset):
    """kernel_gather down: every gather reroutes to the table route, which
    is bit-identical by the kernel-parity contract, so nothing is degraded
    and the serve report counts each reroute in ``kernel_fallbacks``."""
    eng = _shared_engine(small_dataset)
    queues = _queues(small_dataset, n=1, batches=2)
    cfg = ServeConfig(engine=EngineConfig(pipeline_depth=1, use_kernel=True))
    _, rb, ob = _serve(eng, queues, cfg=cfg)
    assert rb.kernel_fallbacks == 0
    plan = FaultPlan(rules=(FaultRule("kernel_gather"),))  # always down
    srv, rf, of = _serve(
        eng, queues, cfg=cfg.replace(fault_policy="fail"), injector=FaultInjector(plan)
    )
    offered = sum(len(q) for q in queues)
    assert rf.kernel_fallbacks == offered == srv.streams[0].runtime.kernel_fallbacks
    assert rf.requests_degraded == 0 and rf.availability == 1.0
    assert rf.summary()["kernel_fallbacks"] == offered
    _assert_same_serve(rb, ob, rf, of)


def test_prefetch_faults_skip_staging_without_degrading(small_dataset):
    """A dead prefetch stage is invisible: staging is optional by design,
    so the serve falls back to gather-time fetches bit-identically and no
    request is marked degraded."""
    eng = _shared_engine(small_dataset)
    queues = _queues(small_dataset)
    cfg0 = ServeConfig(engine=EngineConfig(pipeline_depth=2, prefetch=True))
    _, rb, ob = _serve(eng, queues, cfg=cfg0)
    plan = FaultPlan(rules=(FaultRule("prefetch"),))
    cfg = cfg0.replace(**_fast_retry(retry_attempts=2, degraded_mode=True))
    _, rf, of = _serve(eng, queues, cfg=cfg, injector=FaultInjector(plan))
    _assert_same_serve(rb, ob, rf, of)
    assert rf.requests_degraded == 0 and rf.availability == 1.0
    assert sum(s.prefetched_rows for s in rf.streams) == 0  # nothing was staged


def test_fail_fast_drains_and_records_the_error(small_dataset):
    """fault_policy="fail": the first unrecovered fault aborts the serve.
    raise_on_error=True surfaces it; raise_on_error=False records it on
    the report, and completed + unserved still covers the whole offer."""
    eng = _shared_engine(small_dataset)
    queues = _queues(small_dataset)
    plan = FaultPlan(rules=(FaultRule("host_fetch", start_after=2),))
    cfg = ServeConfig(engine=EngineConfig(pipeline_depth=2))
    with pytest.raises(InjectedFault):
        _serve(eng, queues, cfg=cfg, injector=FaultInjector(plan))
    srv, rep, _ = _serve(
        eng, queues, cfg=cfg, injector=FaultInjector(plan), raise_on_error=False
    )
    offered = sum(len(q) for q in queues)
    assert rep.error is not None and "host_fetch" in rep.error
    assert rep.fault_policy == "fail"
    assert rep.total_batches + rep.unserved + rep.requests_shed == offered
    assert rep.availability < 1.0
    assert rep.summary()["error"] == rep.error


def test_shed_policy_sheds_exactly_the_failing_request(small_dataset):
    """fault_policy="shed": a request whose retries exhaust is dropped —
    exactly once, exactly that one — and the serve keeps going; every
    offered request is either completed or shed, never both or neither."""
    eng = _shared_engine(small_dataset)
    queues = _queues(small_dataset, n=2, batches=3)
    # 2 faults with a 2-attempt budget: one batch exhausts and sheds, the
    # cap is then spent so every later batch completes cleanly.
    plan = FaultPlan(rules=(FaultRule("host_fetch", start_after=1, max_faults=2),))
    cfg = ServeConfig(
        engine=EngineConfig(pipeline_depth=2),
        **_fast_retry(fault_policy="shed", retry_attempts=2),
    )
    srv, rep, outs = _serve(eng, queues, cfg=cfg, injector=FaultInjector(plan))
    offered = sum(len(q) for q in queues)
    assert rep.requests_shed == 1
    assert rep.total_batches == offered - 1
    assert rep.unserved == 0
    assert rep.total_batches + rep.requests_shed == offered  # shed XOR completed
    assert rep.availability == pytest.approx((offered - 1) / offered)
    assert sum(s.batches_shed for s in srv.streams) == 1
    assert sum(len(o) for o in outs) == offered - 1
    assert rep.summary()["requests_shed"] == 1


# ------------------------------------------------------------ refresh rollback


def test_refresh_manager_records_rollback_and_serving_continues(small_dataset):
    """A refresh_fill fault mid-serve rolls the epoch back and serving
    finishes on the stale epoch: availability 1.0, the failure recorded,
    and outputs bit-identical to the refresh-free serve (refreshes move
    bytes, never values — a rolled-back one moves nothing at all)."""
    from repro.runtime.cache_refresh import RefreshConfig

    eng = _shared_engine(small_dataset)
    queues = _queues(small_dataset)
    cfg0 = ServeConfig(engine=EngineConfig(pipeline_depth=2))
    _, rb, ob = _serve(eng, queues, cfg=cfg0)
    plan = FaultPlan(rules=(FaultRule("refresh_fill", max_faults=1),))
    refresh = RefreshConfig(mode="interval", interval_batches=2)
    srv, rf, of = _serve(
        eng, queues, cfg=cfg0.replace(**_fast_retry()), injector=FaultInjector(plan), refresh=refresh
    )
    assert len(srv.refresh_manager.failures) == 1
    failure = srv.refresh_manager.failures[0]
    assert failure.epoch == 0 and "InjectedFault" in failure.error
    assert rf.availability == 1.0
    # later refreshes (cap spent) commit: the epoch moved past the rollback
    assert eng.pipeline.caches.epoch >= 1
    # outputs (not hit counters — committed refreshes re-rank the caches)
    # stay bit-identical to the refresh-free serve
    for a_list, b_list in zip(ob, of):
        for a, b in zip(a_list, b_list):
            np.testing.assert_array_equal(a, b)


def test_refresh_manager_propagates_real_errors(small_dataset, monkeypatch):
    """Only fault-subsystem errors roll a refresh back: a real error in the
    re-fill (a device OOM, a compile error, a bug) propagates instead of
    leaving a run that serves the stale epoch and still exits 0."""
    from repro.core.cache import DualCache
    from repro.runtime.cache_refresh import CacheRefreshManager, RefreshConfig

    eng = _shared_engine(small_dataset)
    manager = CacheRefreshManager(
        eng.pipeline,
        small_dataset,
        fanouts=eng.fanouts,
        batch_size=eng.batch_size,
        config=RefreshConfig(mode="interval", interval_batches=2),
    )

    def broken_refresh(self, **kw):
        raise RuntimeError("RESOURCE_EXHAUSTED: out of device memory")

    monkeypatch.setattr(DualCache, "refresh", broken_refresh)
    with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
        manager.refresh("manual")
    assert manager.failures == []


# ------------------------------------------------------------- shard failover


def test_shard_failover_serves_lost_range_from_host_and_rejoins(small_dataset):
    """Losing a shard mid-serve routes its id range to the host mirror —
    outputs and hit accounting stay bit-identical to the healthy sharded
    serve (the mirror holds the same rows), per-shard hits still tile the
    global counters, and the shard rejoins after its down_for window."""
    from repro.runtime.sharded_serve import ShardedServer

    eng = _shared_engine(small_dataset)
    queues = _queues(small_dataset)
    cfg = ServeConfig(engine=EngineConfig(pipeline_depth=2))

    def serve_sharded(injector):
        srv = ShardedServer(eng, config=cfg, num_shards=2, injector=injector)
        for sid, q in enumerate(queues):
            srv.add_stream(q, seed=STREAM_SEEDS[sid], collect_outputs=True)
        rep = srv.run()
        outs = [[np.asarray(o) for o in s.runtime.outputs] for s in srv.streams]
        return srv, rep, outs

    _, rb, ob = serve_sharded(None)
    plan = FaultPlan(
        rules=(FaultRule("shard_exchange", start_after=2, max_faults=1, shard=1, down_for=2),)
    )
    srv, rf, of = serve_sharded(FaultInjector(plan))
    _assert_same_serve(rb, ob, rf, of)
    assert rf.failovers == [{"shard": 1, "down_for": 2, "call": 2}]
    assert srv.sharded.down == {}  # rejoined before the serve ended
    assert [p.get("failed_over", False) for p in rf.shards] == [False, True]
    per = rf.shards
    assert sum(p["feat_hits"] for p in per) == rf.feat_hits
    assert sum(p["feat_lookups"] for p in per) == rf.feat_lookups
    assert rf.availability == 1.0


# ------------------------------------------------------- single-stream engine


def test_engine_run_accepts_live_fault_handles(small_dataset):
    """The single-stream path (infer_gnn's else-branch): injector +
    retry policy passed straight to engine.run, recovery bit-identical."""
    eng = _shared_engine(small_dataset)
    batches = _queues(small_dataset, n=1, batches=4)[0]
    rb = eng.run(batches=list(batches), pipeline_depth=1, collect_outputs=True)
    ob = [np.asarray(o) for o in eng.last_outputs]
    plan = FaultPlan(rules=(FaultRule("host_fetch", start_after=1, max_faults=2),))
    rf = eng.run(
        batches=list(batches),
        pipeline_depth=1,
        collect_outputs=True,
        injector=FaultInjector(plan),
        retry_policy=RetryPolicy(max_attempts=3, backoff_s=1e-5, jitter=0.0),
    )
    assert (rb.feat_hits, rb.feat_lookups) == (rf.feat_hits, rf.feat_lookups)
    assert (rb.adj_hits, rb.adj_lookups) == (rf.adj_hits, rf.adj_lookups)
    for a, b in zip(ob, eng.last_outputs):
        np.testing.assert_array_equal(a, b)
