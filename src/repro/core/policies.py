"""Cache/preparation policies: DCI and every baseline the paper compares.

Each ``prepare_*`` returns a :class:`PreparedPipeline` — caches (or none),
an optional batch schedule (RAIN), and the measured preprocessing wall
time, which is itself a headline metric in the paper (Tables IV, Fig. 10).
The prepared pipeline is a shared runtime object: one instance serves a
single engine, the staged batch executor at any ``pipeline_depth``, or
every stream of the multi-stream server (runtime/gnn_serve.py)
simultaneously.  Its caches are immutable by default; the online refresh
subsystem (runtime/cache_refresh.py) may swap them to a new epoch as a
delta re-fill, which consumers pick up at their next stage dispatch.

Presampling policies (``dci``/``sci``/``aci``/``ducati``) profile the
workload before filling.  Two modes:

  - single stream (default): ``n_presample`` batches from one seed — the
    paper's setup (hit rates stabilize at ~8 batches, Fig. 11);
  - shared across streams (``stream_seeds=[...]``): the SAME total
    presampling budget split evenly over the streams' seeds and merged
    (:func:`repro.core.presample.merge_stats`), so the one shared cache is
    allocated and filled for the union workload at no extra preprocessing
    cost, where N private per-stream preparations would pay it N times.

  - ``dci``     the paper's system: Eq. 1 split + lightweight fill
  - ``sci``     single-cache baseline: whole budget to node features
  - ``dgl``     no caches (DGL's stock pipeline)
  - ``ducati``  DUCATI's dual-cache population: value curves + slope fit +
                knapsack-style density fill (heavier preprocessing, the
                paper's point)
  - ``rain``    RAIN: LSH clustering of batches + cross-batch feature reuse
"""

from __future__ import annotations

import dataclasses
import time

import numpy as np

from repro.core.allocation import CacheAllocation, allocate_capacity
from repro.core.cache import DualCache
from repro.core.presample import PresampleStats, merge_stats, run_presampling
from repro.graph.datasets import SyntheticGraphDataset

__all__ = [
    "ADMISSION_POLICIES",
    "AdmissionPolicy",
    "EDFAdmission",
    "POLICIES",
    "PreparedPipeline",
    "RoundRobinAdmission",
    "SLOAdmission",
    "prepare",
]


@dataclasses.dataclass(frozen=True)
class PreparedPipeline:
    name: str
    caches: DualCache
    prep_seconds: float
    presample: PresampleStats | None = None
    batch_order: np.ndarray | None = None  # RAIN: inference-order permutation of batches
    reuse_prev_batch: bool = False  # RAIN: reuse previous batch's features
    # Default execution knobs for runs against this pipeline (overridable
    # per run; outputs and hit accounting are knob-invariant):
    prefetch: bool = False  # stage missed host rows for batch i+1 during batch i's compute
    use_kernel: bool = False  # route gathers through the Pallas cached_gather kernel
    gather_buffers: int = 2  # kernel row copies in flight (1 serial, 2 double buffered)
    dedup: bool = False  # gather/prefetch/model on sorted-unique frontiers only


# ---------------------------------------------------------------- DCI / SCI


def _presample_profile(
    dataset: SyntheticGraphDataset,
    *,
    fanouts: tuple[int, ...],
    batch_size: int,
    n_presample: int,
    seed: int,
    pipeline_depth: int,
    stream_seeds,
) -> PresampleStats:
    """One workload profile, single- or multi-stream.

    With ``stream_seeds`` the total ``n_presample`` budget is split across
    the streams (remainder batches go to the first streams, so the total
    is exact) and the per-stream profiles merged — constant preprocessing
    cost regardless of how many streams share the cache.  Every stream is
    profiled at least once, so with more streams than budget the total
    grows to one batch per stream — the floor at which the merged profile
    still covers every stream's workload."""
    if not stream_seeds:
        return run_presampling(
            dataset,
            fanouts=fanouts,
            batch_size=batch_size,
            n_batches=n_presample,
            seed=seed,
            pipeline_depth=pipeline_depth,
        )
    base, extra = divmod(n_presample, len(stream_seeds))
    return merge_stats(
        [
            run_presampling(
                dataset,
                fanouts=fanouts,
                batch_size=batch_size,
                n_batches=max(1, base + (1 if i < extra else 0)),
                seed=s,
                pipeline_depth=pipeline_depth,
            )
            for i, s in enumerate(stream_seeds)
        ]
    )


def prepare_dci(
    dataset: SyntheticGraphDataset,
    *,
    total_cache_bytes: int,
    fanouts: tuple[int, ...],
    batch_size: int,
    n_presample: int = 8,
    seed: int = 0,
    pipeline_depth: int = 1,
    stream_seeds=None,
    _feat_only: bool = False,
    _adj_only: bool = False,
) -> PreparedPipeline:
    stats = _presample_profile(
        dataset,
        fanouts=fanouts,
        batch_size=batch_size,
        n_presample=n_presample,
        seed=seed,
        pipeline_depth=pipeline_depth,
        stream_seeds=stream_seeds,
    )
    # Preprocessing cost = steady-state pre-sampling work + allocation +
    # cache filling.  The one-time jit compile inside run_presampling's
    # warmup is excluded (it is paid once per process, not per preparation).
    t0 = time.perf_counter() - sum(stats.sample_times) - sum(stats.feature_times)
    if _feat_only:  # SCI: the single-cache state of the art
        alloc = CacheAllocation(
            total_bytes=total_cache_bytes,
            adj_bytes=0,
            feat_bytes=total_cache_bytes,
            sample_fraction=0.0,
        )
    elif _adj_only:  # ACI ablation: adjacency-only cache
        alloc = CacheAllocation(
            total_bytes=total_cache_bytes,
            adj_bytes=total_cache_bytes,
            feat_bytes=0,
            sample_fraction=1.0,
        )
    else:
        alloc = allocate_capacity(
            stats.sample_times,
            stats.feature_times,
            total_cache_bytes,
            adj_need_bytes=dataset.graph.num_edges * 4,
            feat_need_bytes=dataset.features.nbytes,
        )
    caches = DualCache.build(
        dataset,
        node_counts=stats.node_counts,
        edge_counts=stats.edge_counts,
        allocation=alloc,
    )
    name = "dci"
    if _feat_only:
        name = "sci"
    elif _adj_only:
        name = "aci"
    return PreparedPipeline(
        name=name,
        caches=caches,
        prep_seconds=time.perf_counter() - t0,
        presample=stats,
    )


def prepare_sci(dataset, **kw) -> PreparedPipeline:
    return prepare_dci(dataset, _feat_only=True, **kw)


def prepare_aci(dataset, **kw) -> PreparedPipeline:
    """Ablation: the whole budget to the ADJACENCY cache (no feature cache).
    Not a paper baseline — isolates each cache's contribution next to SCI."""
    return prepare_dci(dataset, _adj_only=True, **kw)


# ---------------------------------------------------------------------- DGL


def prepare_dgl(dataset: SyntheticGraphDataset, **_kw) -> PreparedPipeline:
    t0 = time.perf_counter()
    caches = DualCache.none(dataset)
    return PreparedPipeline(name="dgl", caches=caches, prep_seconds=time.perf_counter() - t0)


# ------------------------------------------------------------------- DUCATI


def prepare_ducati(
    dataset: SyntheticGraphDataset,
    *,
    total_cache_bytes: int,
    fanouts: tuple[int, ...],
    batch_size: int,
    n_presample: int = 8,
    seed: int = 0,
    pipeline_depth: int = 1,
    stream_seeds=None,
) -> PreparedPipeline:
    """DUCATI's dual-cache population, adapted to inference.

    DUCATI (training-oriented) builds *value curves* for nfeat and adj
    entries (counts sorted descending — a full O(n log n) sort over both
    populations), fits slopes by curve fitting, and fills a knapsack by
    value density.  Amortizable over training epochs, expensive for
    inference — exactly the comparison in Fig. 10.  We reproduce the
    algorithmic structure: global sorts + polynomial slope fits + joint
    density-greedy fill; the capacity split *emerges* from the knapsack
    instead of Eq. 1.
    """
    # DUCATI gathers statistics over substantially more batches (epoch-level
    # in training); we follow with 4x DCI's presampling.  Jit-compile time
    # is excluded the same way as prepare_dci.
    stats = _presample_profile(
        dataset,
        fanouts=fanouts,
        batch_size=batch_size,
        n_presample=4 * n_presample,
        seed=seed,
        pipeline_depth=pipeline_depth,
        stream_seeds=stream_seeds,
    )
    t0 = time.perf_counter() - sum(stats.sample_times) - sum(stats.feature_times)
    row_bytes = dataset.feature_nbytes_per_row()
    deg = np.diff(dataset.graph.col_ptr)

    # --- value curves + slope fitting (the expensive part) -----------------
    nfeat_curve = np.sort(stats.node_counts)[::-1].astype(np.float64)
    starts = np.minimum(dataset.graph.col_ptr[:-1], max(dataset.graph.num_edges - 1, 0))
    node_totals = np.add.reduceat(stats.edge_counts.astype(np.int64), starts)
    node_totals = np.where(deg > 0, node_totals, 0)
    adj_curve = np.sort(node_totals)[::-1].astype(np.float64)
    for curve in (nfeat_curve, adj_curve):
        x = np.arange(1, curve.shape[0] + 1, dtype=np.float64)
        with np.errstate(divide="ignore"):
            np.polyfit(np.log(x), np.log(curve + 1.0), deg=2)  # slope fit

    # --- joint knapsack by value density ------------------------------------
    # nfeat entry v: value = visits, size = row_bytes
    # adj entry v:   value = total visits of v's list, size = deg[v]*4 bytes
    n = dataset.num_nodes
    sizes = np.concatenate([np.full(n, row_bytes, np.int64), deg.astype(np.int64) * 4])
    values = np.concatenate([stats.node_counts.astype(np.float64), node_totals.astype(np.float64)])
    density = values / np.maximum(sizes, 1)
    order = np.argsort(-density, kind="stable")  # global O(n log n) sort
    csum = np.cumsum(sizes[order])
    chosen = order[csum <= total_cache_bytes]
    feat_nodes = chosen[chosen < n]
    adj_nodes = chosen[chosen >= n] - n

    feat_bytes = int(len(feat_nodes) * row_bytes)
    adj_bytes = int(deg[adj_nodes].sum() * 4)
    alloc = CacheAllocation(
        total_bytes=total_cache_bytes,
        adj_bytes=adj_bytes,
        feat_bytes=min(feat_bytes, total_cache_bytes - adj_bytes),
        sample_fraction=float(adj_bytes) / max(total_cache_bytes, 1),
    )
    # Fill with the knapsack's own selections: bias counts so exactly the
    # chosen entries rank on top, then reuse the standard fill paths.
    node_counts_sel = np.zeros(n, np.int64)
    node_counts_sel[feat_nodes] = stats.node_counts[feat_nodes].astype(np.int64) + 1
    edge_counts_sel = stats.edge_counts.copy()
    caches = DualCache.build(
        dataset,
        node_counts=node_counts_sel,
        edge_counts=edge_counts_sel,
        allocation=alloc,
    )
    return PreparedPipeline(
        name="ducati",
        caches=caches,
        prep_seconds=time.perf_counter() - t0,
        presample=stats,
    )


# --------------------------------------------------------------------- RAIN


def _minhash_signatures(batches: np.ndarray, num_hashes: int, seed: int) -> np.ndarray:
    """MinHash signature per batch over its seed set (RAIN's LSH front end)."""
    rng = np.random.default_rng(seed)
    a = rng.integers(1, 2**31 - 1, num_hashes, dtype=np.int64)
    b = rng.integers(0, 2**31 - 1, num_hashes, dtype=np.int64)
    p = np.int64(2**31 - 1)
    # batches: [num_batches, batch_size] node ids
    h = (batches[:, None, :] * a[None, :, None] + b[None, :, None]) % p
    return h.min(axis=2)  # [num_batches, num_hashes]


def prepare_rain(
    dataset: SyntheticGraphDataset,
    *,
    batch_size: int,
    num_hashes: int = 32,
    bands: int = 8,
    seed: int = 0,
    **_kw,
) -> PreparedPipeline:
    """RAIN: LSH-cluster similar batches, run them adjacently, reuse features.

    No device cache is built; the win comes from cross-batch reuse.  The
    preprocessing cost is the signature + banding pass over *every* test
    batch — O(#batches · batch_size · num_hashes), the linear-but-heavy
    term of Table IV.
    """
    t0 = time.perf_counter()
    test = dataset.test_idx
    nb = max(len(test) // batch_size, 1)
    if len(test) < nb * batch_size:  # tiny datasets: cycle to fill one batch
        test = np.tile(test, -(-nb * batch_size // max(len(test), 1)))
    trimmed = test[: nb * batch_size].reshape(nb, batch_size).astype(np.int64)
    sig = _minhash_signatures(trimmed, num_hashes, seed)
    # Band the signatures; batches sharing any band bucket are "similar".
    per_band = num_hashes // bands
    keys = np.zeros(nb, np.int64)
    buckets: dict[tuple, list[int]] = {}
    for i in range(nb):
        for band in range(bands):
            k = (band, *sig[i, band * per_band : (band + 1) * per_band].tolist())
            buckets.setdefault(k, []).append(i)
    # Greedy cluster ordering: walk buckets, emit unseen members together.
    order: list[int] = []
    seen = np.zeros(nb, bool)
    for members in buckets.values():
        for m in members:
            if not seen[m]:
                seen[m] = True
                order.append(m)
    del keys
    caches = DualCache.none(dataset)
    return PreparedPipeline(
        name="rain",
        caches=caches,
        prep_seconds=time.perf_counter() - t0,
        batch_order=np.asarray(order, np.int64),
        reuse_prev_batch=True,
    )


POLICIES = {
    "dci": prepare_dci,
    "sci": prepare_sci,
    "aci": prepare_aci,
    "dgl": prepare_dgl,
    "ducati": prepare_ducati,
    "rain": prepare_rain,
}


# ------------------------------------------------------- admission policies
#
# Cache policies above decide WHAT to keep on device; admission policies
# decide WHICH queued request the serving front-end
# (runtime/request_queue.py) dispatches next.  They are pure ordering
# logic over duck-typed requests (``arrival_s``, optional ``deadline_s``,
# and ``admission_deadline_s`` — the deadline as admission should see it,
# None for a deferred/blown request): the server applies the mechanical
# parts — in-flight caps, the progress fallback, and the round-robin
# cursor — so a policy here never touches runtime state and stays
# property-testable in isolation (tests/test_request_queue.py).


class AdmissionPolicy:
    """Order the admissible requests of one serving step.

    ``order(candidates, now)`` receives ``(stream_key, head_request)``
    pairs — one per stream whose head request has arrived by ``now`` —
    and returns them in service-preference order (most urgent first), or
    ``None`` to defer to the server's own round-robin cursor.  ``sheds``
    marks policies that drop (or defer) requests whose deadline has
    already passed before selecting."""

    name = "fifo"
    sheds = False

    def order(self, candidates, now):
        del now
        return sorted(candidates, key=lambda c: (c[1].arrival_s, c[0]))


class RoundRobinAdmission(AdmissionPolicy):
    """The bit-for-bit baseline: defer entirely to the server's
    round-robin cursor (returning ``None``), so a request-queue serve
    with zero arrival offsets reproduces ``MultiStreamServer``'s
    admission log — and outputs — exactly."""

    name = "round-robin"

    def order(self, candidates, now):
        del candidates, now
        return None


class EDFAdmission(AdmissionPolicy):
    """Earliest-deadline-first.

    Deadline-free requests sort last (a deadline is a promise; absence of
    one is best-effort), ties break by arrival then stream key, so the
    order is total and deterministic.  For a single machine serving
    sequential batches EDF minimizes maximum lateness (Jackson's rule) —
    under a burst this approximates global FCFS over the backlog, which
    is what beats round-robin's interleaving on p99."""

    name = "edf"

    def order(self, candidates, now):
        del now
        inf = float("inf")

        def key(c):
            stream_key, req = c
            dl = getattr(req, "admission_deadline_s", req.deadline_s)
            return (inf if dl is None else dl, req.arrival_s, stream_key)

        return sorted(candidates, key=key)


class SLOAdmission(EDFAdmission):
    """EDF plus SLO enforcement at admission time.

    Before selecting, the server drops every arrived request whose
    deadline has already passed (``blown="shed"`` — the request never
    runs and is accounted as shed) or demotes it to best-effort
    (``blown="defer"`` — it keeps its batch but sorts after every
    deadline-carrying request, via ``admission_deadline_s = None``).
    Either way a blown request can no longer delay ones that can still
    meet their deadlines."""

    name = "slo"
    sheds = True

    def __init__(self, blown: str = "shed"):
        if blown not in ("shed", "defer"):
            raise ValueError(f"blown must be 'shed' or 'defer', got {blown!r}")
        self.blown = blown


ADMISSION_POLICIES = {
    "round-robin": RoundRobinAdmission,
    "edf": EDFAdmission,
    "slo": SLOAdmission,
}


def prepare(policy: str, dataset: SyntheticGraphDataset, **kw) -> PreparedPipeline:
    """Dispatch to a policy's ``prepare_*``.

    Presampling policies accept two extra knobs, both forwarded to
    :func:`repro.core.presample.run_presampling`:

      - ``pipeline_depth`` (default 1 = serial, the Eq. 1 timing
        semantics; >1 overlaps presample batches through the staged
        executor);
      - ``stream_seeds`` (default None): profile the union workload of
        several request streams, splitting the same total presampling
        budget across them — used when one cache will be shared by the
        multi-stream server (runtime/gnn_serve.py).

    Execution knobs (``prefetch``, ``use_kernel``, ``gather_buffers``,
    ``dedup``) are policy-independent: they are recorded on the returned
    :class:`PreparedPipeline` as the defaults every engine run and every
    serving stream resolves against, without changing what gets cached.
    ``dedup`` routes the feature path through sorted-unique frontiers
    (gather each distinct row once, expand through the inverse map); like
    the others it never changes outputs or hit accounting, only how many
    rows move.

    ``dgl`` and ``rain`` build no presampled caches; the extra knobs are
    ignored for them."""
    if policy not in POLICIES:
        raise KeyError(f"unknown policy {policy!r}; have {sorted(POLICIES)}")
    if kw.get("pipeline_depth") == "auto":
        # "auto" sizes the RUN-time executor window (the engine resolves it
        # from a measured compute:prep probe); presampling stays serial —
        # Eq. 1's stage-time ratio assumes fully synchronized stages.
        kw["pipeline_depth"] = 1
    exec_kw = {
        "prefetch": bool(kw.pop("prefetch", False)),
        "use_kernel": bool(kw.pop("use_kernel", False)),
        "gather_buffers": int(kw.pop("gather_buffers", 2)),
        "dedup": bool(kw.pop("dedup", False)),
    }
    if exec_kw["gather_buffers"] < 1:
        raise ValueError(f"gather_buffers must be >= 1, got {exec_kw['gather_buffers']}")
    fn = POLICIES[policy]
    if policy == "dgl":
        pipe = fn(dataset)
    elif policy == "rain":
        pipe = fn(
            dataset,
            batch_size=kw["batch_size"],
            seed=kw.get("seed", 0),
        )
    else:
        pipe = fn(dataset, **kw)
    return dataclasses.replace(pipe, **exec_kw)
