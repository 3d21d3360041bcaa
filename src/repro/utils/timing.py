"""Wall-clock helpers (pre-sampling stage timing is part of DCI's Eq. 1).

``StageClock`` is the overlap-aware stage timer behind the pipelined batch
executor (runtime/pipeline.py): in serial mode it synchronizes (blocks on
device values) at every stage boundary, reproducing the per-stage Eq. 1
decomposition exactly; in overlap mode stages only measure host dispatch
time and the wait for in-flight device work is booked by ``drain()`` at
pipeline-retire boundaries.
"""

from __future__ import annotations

import contextlib
import time

import jax

__all__ = ["StageClock"]


class StageClock:
    """Per-stage wall-clock accounting that understands stage overlap.

    Serial mode (``overlap=False``): :meth:`stage` blocks on the stage's
    ``sync`` value before stopping the timer, so every lap is a fully
    synchronized stage time — the semantics DCI's Eq. 1 stage decomposition
    assumes, and what the pre-pipeline engine measured.

    Overlap mode (``overlap=True``): :meth:`stage` never blocks; laps
    measure host dispatch time only, while JAX async dispatch keeps the
    device busy with earlier batches.  The wait for in-flight work is
    recorded by :meth:`drain` when the pipeline retires a batch and is
    attributed (in ``totals`` only, not ``laps``) to the stage whose output
    is drained, so ``sum(totals.values())`` stays consistent with the
    loop's wall clock.

    Invariants (property-tested in tests/test_pipeline_executor.py):
    every lap is >= 0, ``totals[name] >= sum(laps[name])``, and
    ``sum(totals) == sum(all laps) + drain_seconds``.
    """

    def __init__(self, *, overlap: bool = False):
        self.overlap = overlap
        self.totals: dict[str, float] = {}
        self.laps: dict[str, list[float]] = {}
        self.drain_seconds = 0.0

    @contextlib.contextmanager
    def stage(self, name: str, *, sync: object = None):
        """Time one stage lap.  ``sync`` is the device value (or a callable
        producing it) to block on at the stage boundary in serial mode."""
        t0 = time.perf_counter()
        ok = False
        try:
            yield
            ok = True
        finally:
            # Only evaluate sync when the body succeeded — a failed stage
            # has no output, and a KeyError from the sync callable would
            # mask the stage's real exception.
            if ok and sync is not None and not self.overlap:
                value = sync() if callable(sync) else sync
                if value is not None:
                    jax.block_until_ready(value)
            self._lap(name, time.perf_counter() - t0)

    def drain(self, name: str, value) -> None:
        """Block on an in-flight device value; attribute the wait to ``name``."""
        t0 = time.perf_counter()
        jax.block_until_ready(value)
        dt = time.perf_counter() - t0
        self.drain_seconds += dt
        self.totals[name] = self.totals.get(name, 0.0) + dt

    def _lap(self, name: str, dt: float) -> None:
        self.totals[name] = self.totals.get(name, 0.0) + dt
        self.laps.setdefault(name, []).append(dt)

    def total(self, name: str) -> float:
        return self.totals.get(name, 0.0)
