"""The comparison that decides ``correct``.

Three layers are checked on batches that the timed window itself served,
drawn from the run's seed once the window has closed:

* sampling: every sampled neighbour is an in-neighbour of its destination
  in the host CSC that the benchmark built (count of bad draws, limit 0),
  and the frontier starts with the request's seeds;
* the feature gather: the rows the store gathers for the batch's unique
  frontier equal the float32 feature table bit for bit (count of bad rows,
  limit 0);
* the forward: the logits the window produced against the plain float32
  numpy reference over the same frontier, as the largest absolute error
  over the batch's largest reference logit (limit from the configuration).

The block each served batch was sampled with is recovered by replaying the
engine's documented RNG stream: one ``PRNGKey(engine seed + 1)`` per
``run`` call, split once per batch, into the program's own
``sample_blocks`` with the run's knobs.  That frontier is input the program
prepared: the sampling check holds it to the CSC, and the reference reads
its rows from the benchmark's own table, so a window that sampled or
gathered anything else than the replay shows it in its logits.  The
logits compared are the ones the window produced, not a second forward.
The gather check re-gathers the replayed frontier through the store after
the window; the rows the window itself gathered are checked through its
logits.
"""

from __future__ import annotations

import dataclasses

import numpy as np

import reference


@dataclasses.dataclass
class Served:
    """One served batch: its seeds, its logits, where its key came from."""

    seeds: np.ndarray
    logits: np.ndarray
    key_seed: int  # the runtime's key is PRNGKey(key_seed + 1)
    chain: object  # one id per ``run`` call
    index: int  # position in that runtime's split chain (0 = first batch)


@dataclasses.dataclass
class Readings:
    bad_samples: int = 0
    bad_rows: int = 0
    logit_err: float = 0.0
    nonfinite: int = 0
    batches: int = 0

    def merge(self, other: "Readings") -> None:
        self.bad_samples += other.bad_samples
        self.bad_rows += other.bad_rows
        self.nonfinite += other.nonfinite
        self.batches += other.batches
        self.logit_err = max(self.logit_err, other.logit_err)


def bad_neighbours(col_ptr: np.ndarray, row_index: np.ndarray, dst: np.ndarray, src: np.ndarray) -> int:
    """How many ``(dst, src)`` draws are not edges ``src -> dst`` of the CSC.

    A node without in-neighbours samples itself (the sampler's documented
    self loop), which counts as valid."""
    dst = dst.astype(np.int64)
    src = src.astype(np.int64)
    n = col_ptr.shape[0] - 1
    if dst.size == 0:
        return 0
    if dst.min() < 0 or dst.max() >= n or src.min() < 0 or src.max() >= n:
        return int(np.count_nonzero((dst < 0) | (dst >= n) | (src < 0) | (src >= n)))
    cols = np.unique(dst)
    starts, ends = col_ptr[cols], col_ptr[cols + 1]
    lens = ends - starts
    # every edge of the touched columns, as dst * n + src keys
    offs = np.repeat(starts - np.concatenate([[0], np.cumsum(lens)[:-1]]), lens)
    edge_pos = np.arange(int(lens.sum()), dtype=np.int64) + offs
    keys = np.repeat(cols, lens) * n + row_index[edge_pos].astype(np.int64)
    keys.sort()
    want = dst * n + src
    hit = np.searchsorted(keys, want)
    ok = (hit < keys.size) & (keys[np.minimum(hit, keys.size - 1)] == want)
    isolated = (col_ptr[dst + 1] - col_ptr[dst] == 0) & (src == dst)
    return int(np.count_nonzero(~(ok | isolated)))


def check_sampling(ids: np.ndarray, seeds: np.ndarray, fanouts, col_ptr, row_index) -> int:
    """Bad draws in one deepest frontier (plus every seed out of place)."""
    batch = seeds.shape[0]
    sizes = reference.frontier_sizes(batch, fanouts)
    rev = tuple(int(f) for f in reversed(tuple(fanouts)))
    bad = int(np.count_nonzero(ids[:batch] != seeds))
    for level, f in enumerate(rev):
        n = sizes[level]
        dst = np.repeat(ids[:n], f)
        src = ids[n : n * (1 + f)]
        bad += bad_neighbours(col_ptr, row_index, dst, src)
    return bad


def replay(served: list[Served], *, sample_blocks, dgraph, fanouts, dedup: bool, pad_id: int):
    """Yield ``(served, block)`` for every served batch, re-sampling each
    from its runtime's key chain (one walk per chain)."""
    import jax
    import jax.numpy as jnp

    by_chain: dict[object, list[Served]] = {}
    for s in served:
        by_chain.setdefault(s.chain, []).append(s)
    for items in by_chain.values():
        items.sort(key=lambda s: s.index)
        key = jax.random.PRNGKey(items[0].key_seed + 1)
        i = 0
        for s in items:
            while True:
                key, sub = jax.random.split(key)
                if i == s.index:
                    break
                i += 1
            i += 1
            yield s, sample_blocks(
                sub,
                dgraph,
                jnp.asarray(s.seeds),
                tuple(fanouts),
                dedup=dedup,
                dedup_pad_id=pad_id if dedup else None,
            )


def check_batches(
    served: list[Served],
    *,
    model,
    params_np,
    graph,
    fanouts,
    store,
    sample_blocks,
    pow2_bucket,
    dgraph,
    dedup: bool,
) -> Readings:
    """Readings over ``served`` (see the module docstring); ``model`` is
    the model's plug-in module."""
    out = Readings()
    pad_id = store.pad_node_id() if dedup else -1
    for s, block in replay(
        served, sample_blocks=sample_blocks, dgraph=dgraph, fanouts=fanouts, dedup=dedup,
        pad_id=pad_id,
    ):
        ids = np.asarray(block.input_nodes)
        r = Readings(batches=1)
        r.bad_samples = check_sampling(ids, s.seeds, fanouts, graph.col_ptr, graph.row_index)
        if dedup:
            nu = int(block.dedup.num_unique)
            uids = block.dedup.unique_ids[: pow2_bucket(nu, ids.shape[0])]
        else:
            nu, uids = ids.shape[0], block.input_nodes
        rows, _ = store.gather(uids)
        rows = np.asarray(rows)[:nu]
        want = graph.features[np.asarray(uids)[:nu]]
        r.bad_rows = int(np.count_nonzero(~np.all(rows == want, axis=1)))
        del rows, want
        ref = reference.forward(model, params_np, graph.features, ids, s.seeds.shape[0], fanouts)
        got = np.asarray(s.logits, np.float32)
        if got.shape != ref.shape or not np.isfinite(got).all():
            r.nonfinite = 1
            r.logit_err = float("inf")
        else:
            scale = float(np.abs(ref).max())
            r.logit_err = float(np.abs(got - ref).max()) / max(scale, 1e-30)
        out.merge(r)
    return out


def verdict(readings: Readings, limits: dict) -> tuple[bool, dict]:
    """``(correct, {name: {"value", "limit"}})`` for the result line."""
    numbers = {
        "bad_samples": {"value": readings.bad_samples, "limit": 0},
        "bad_rows": {"value": readings.bad_rows, "limit": 0},
        "nonfinite_batches": {"value": readings.nonfinite, "limit": 0},
        "logit_err": {"value": readings.logit_err, "limit": float(limits["logit_err"])},
        "checked_batches": {"value": readings.batches, "limit": int(limits["min_batches"])},
    }
    ok = (
        readings.batches >= int(limits["min_batches"])
        and readings.bad_samples == 0
        and readings.bad_rows == 0
        and readings.nonfinite == 0
        and readings.logit_err <= float(limits["logit_err"])
    )
    return ok, numbers
