"""Pallas TPU kernel: DCI's two-source cached row gather.

TPU adaptation of the paper's cache-hit feature load (DESIGN.md §3): for
each requested row the kernel reads the row id (``indices``) and cache slot
(``positions``) from scalar memory and issues exactly one HBM→VMEM copy
from the right source (hot cache on a hit, full host table on a miss),
never both.

Layout.  Both tables are read in *lane layout* (:func:`lane_layout`): the
feature axis zero-padded to ``k = ceil(F / 128)`` lane groups and viewed
as ``[R * k, 128]``, so one table row is ``k`` consecutive 128-lane rows.
The TPU compiler accepts a DMA of any run of rows of a 128-lane-wide HBM
array, but refuses a single row of a wider one (the (8, 128) tiling) and a
feature slice that is not a multiple of 128 lanes.  Building the padded
view once, at store build time, keeps the per-call pad off the hot path.

Grid.  Rows are tiled over the grid, ``TILE_ROWS`` per step.  The int32
index operands are blocked into scalar memory one tile at a time (all S
rows would overflow it at real frontier sizes), and each step's rows are
copied straight into that step's output VMEM block, which Pallas writes
back while the next step gathers.  Within a step, up to ``gather_buffers``
row copies are kept in flight (1 = serial copies, 2 = double buffering).

``interpret=None`` resolves by backend: compiled on TPU, interpret mode on
the CPU, where the tests run.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "cached_gather",
    "cached_gather_blocks",
    "default_interpret",
    "lane_layout",
]

LANE = 128
ROW_BLOCK = 8  # default rows per DMA tile in the row-block variant
# Rows per grid step.  A 1-D int32 operand is laid out in 1024-entry tiles,
# so a scalar-memory block of a longer index array must be a multiple of it.
TILE_ROWS = 1024


def default_interpret() -> bool:
    """Compiled on TPU, interpret mode everywhere else (CPU validation)."""
    return jax.default_backend() != "tpu"


def lane_layout(table: jax.Array) -> jax.Array:
    """``[R, F]`` → ``[R * k, 128]``: the feature axis zero-padded to
    ``k = ceil(F / 128)`` lane groups, one table row per ``k`` lane rows."""
    r, f = table.shape
    k = -(-f // LANE)
    if f != k * LANE:
        table = jnp.pad(table, ((0, 0), (0, k * LANE - f)))
    return table.reshape(r * k, LANE)


def _tiling(s: int, unit: int) -> tuple[int, int]:
    """(rows per grid step, padded row count) for ``s`` rows in ``unit``s."""
    sp = -(-s // unit) * unit
    if sp <= TILE_ROWS:
        return sp, sp
    tile = TILE_ROWS - TILE_ROWS % unit
    return tile, -(-s // tile) * tile


def _windowed(n: int, window: int, start, wait) -> None:
    """Issue ``n`` copies in order, keeping up to ``window`` in flight."""
    if window > 1:
        def prime(i, c):
            start(i)
            return c

        jax.lax.fori_loop(0, min(window - 1, n), prime, 0)

    def body(i, c):
        @pl.when(i + window - 1 < n)
        def _():
            start(i + window - 1)

        wait(i)
        return c

    jax.lax.fori_loop(0, n, body, 0)


def _row_copies(idx_ref, pos_ref, hot_hbm, host_hbm, out_ref, sem, k):
    """(start, wait) for the one-row copy of tile row ``r``.  All copies
    share one semaphore and have one size, so a wait may be rebuilt from
    any same-sized descriptor."""

    def start(r):
        p = pos_ref[r]
        dst = out_ref.at[pl.ds(r * k, k)]

        @pl.when(p >= 0)
        def _():
            pltpu.make_async_copy(hot_hbm.at[pl.ds(p * k, k)], dst, sem).start()

        @pl.when(p < 0)
        def _():
            pltpu.make_async_copy(host_hbm.at[pl.ds(idx_ref[r] * k, k)], dst, sem).start()

    def wait(r):
        pltpu.make_async_copy(host_hbm.at[pl.ds(0, k)], out_ref.at[pl.ds(r * k, k)], sem).wait()

    return start, wait


def _row_kernel(idx_ref, pos_ref, hot_hbm, host_hbm, out_ref, sem, *, k: int, window: int):
    start, wait = _row_copies(idx_ref, pos_ref, hot_hbm, host_hbm, out_ref, sem, k)
    _windowed(idx_ref.shape[0], window, start, wait)


def _block_kernel(
    idx_ref, pos_ref, hot_hbm, host_hbm, out_ref, sem, mode_ref, *, k, row_block, window
):
    """Row-block variant of :func:`_row_kernel` (same window, coarser copies).

    Sorted unique frontiers make whole row blocks land on *consecutive*
    source rows (hit runs are consecutive hot-table slots because slots are
    assigned in node-id order; miss runs are consecutive prefetch-pack
    slots or dense id ranges).  Each block is classified from the scalar
    operands when its copy starts, and the class is kept in ``mode_ref``
    for the wait: 1 = contiguous hit run → ONE copy of ``row_block`` rows
    from the hot table, 2 = contiguous miss run → one copy from the host
    table, 0 = mixed/broken (a pad row breaks a run too) → per-row copies.
    """
    row_start, row_wait = _row_copies(idx_ref, pos_ref, hot_hbm, host_hbm, out_ref, sem, k)
    run = row_block * k

    def source(r):
        p = pos_ref[r]
        return p >= 0, jnp.where(p >= 0, p, idx_ref[r])

    def classify(r0):
        hit0, src0 = source(r0)

        def same_run(j, ok):
            hit, src = source(r0 + j)
            return ok & (hit == hit0).astype(jnp.int32) & (src == src0 + j).astype(jnp.int32)

        contig = jax.lax.fori_loop(1, row_block, same_run, jnp.int32(1))
        return jnp.where(contig == 1, jnp.where(hit0, 1, 2), 0).astype(jnp.int32), src0

    def start(b):
        r0 = b * row_block
        mode, src0 = classify(r0)
        mode_ref[b] = mode
        dst = out_ref.at[pl.ds(r0 * k, run)]

        @pl.when(mode == 1)
        def _():
            pltpu.make_async_copy(hot_hbm.at[pl.ds(src0 * k, run)], dst, sem).start()

        @pl.when(mode == 2)
        def _():
            pltpu.make_async_copy(host_hbm.at[pl.ds(src0 * k, run)], dst, sem).start()

        @pl.when(mode == 0)
        def _():
            def row(j, c):
                row_start(r0 + j)
                return c

            jax.lax.fori_loop(0, row_block, row, 0)

    def wait(b):
        r0 = b * row_block

        @pl.when(mode_ref[b] != 0)
        def _():
            pltpu.make_async_copy(
                host_hbm.at[pl.ds(0, run)], out_ref.at[pl.ds(r0 * k, run)], sem
            ).wait()

        @pl.when(mode_ref[b] == 0)
        def _():
            def row(j, c):
                row_wait(r0 + j)
                return c

            jax.lax.fori_loop(0, row_block, row, 0)

    _windowed(idx_ref.shape[0] // row_block, window, start, wait)


def _gather_call(kernel, idx, pos, hot, host, *, s, sp, tile, k, feat_dim, interpret, scratch=()):
    """Run ``kernel`` over ``sp // tile`` row tiles, the per-row int32
    operands blocked into scalar memory one tile at a time."""
    smem = pl.BlockSpec((tile,), lambda t: (t,), memory_space=pltpu.SMEM)
    out = pl.pallas_call(
        kernel,
        grid=(sp // tile,),
        in_specs=[
            smem,
            smem,
            pl.BlockSpec(memory_space=pl.ANY),  # hot table stays in HBM
            pl.BlockSpec(memory_space=pl.ANY),  # host table stays in HBM
        ],
        out_specs=pl.BlockSpec((tile * k, LANE), lambda t: (t, 0)),
        scratch_shapes=[pltpu.SemaphoreType.DMA(()), *scratch],
        out_shape=jax.ShapeDtypeStruct((sp * k, LANE), host.dtype),
        interpret=interpret,
    )(idx, pos, hot, host)
    return out.reshape(sp, k * LANE)[:s, :feat_dim]


def _operands(indices, positions, sp, *, hot_rows, host_rows):
    """Clamp and pad the per-row operands; pad rows are misses of host row 0."""
    s = indices.shape[0]
    idx = jnp.clip(indices.astype(jnp.int32), 0, host_rows - 1)
    pos = positions.astype(jnp.int32)
    pos = jnp.where(pos >= 0, jnp.minimum(pos, hot_rows - 1), -1)
    if sp != s:
        idx = jnp.pad(idx, (0, sp - s))
        pos = jnp.pad(pos, (0, sp - s), constant_values=-1)
    return idx, pos


def _pad_rows(table, rows):
    """At least ``rows`` lane rows: a run copy's source slice has a static
    size, and interpret mode traces both sides of every branch."""
    return table if table.shape[0] >= rows else jnp.pad(table, ((0, rows - table.shape[0]), (0, 0)))


@functools.partial(jax.jit, static_argnames=("feat_dim", "gather_buffers", "interpret"))
def _cached_gather_rows(hot, host, indices, positions, *, feat_dim, gather_buffers, interpret):
    k = -(-feat_dim // LANE)
    s = indices.shape[0]
    tile, sp = _tiling(s, 1)
    idx, pos = _operands(
        indices, positions, sp, hot_rows=hot.shape[0] // k, host_rows=host.shape[0] // k
    )
    return _gather_call(
        functools.partial(_row_kernel, k=k, window=gather_buffers),
        idx,
        pos,
        hot,
        host,
        s=s,
        sp=sp,
        tile=tile,
        k=k,
        feat_dim=feat_dim,
        interpret=interpret,
    )


@functools.partial(
    jax.jit, static_argnames=("feat_dim", "row_block", "gather_buffers", "interpret")
)
def _cached_gather_blocks(
    hot, host, indices, positions, *, feat_dim, row_block, gather_buffers, interpret
):
    k = -(-feat_dim // LANE)
    s = indices.shape[0]
    tile, sp = _tiling(s, row_block)
    idx, pos = _operands(
        indices, positions, sp, hot_rows=hot.shape[0] // k, host_rows=host.shape[0] // k
    )
    hot = _pad_rows(hot, row_block * k)
    host = _pad_rows(host, row_block * k)
    return _gather_call(
        functools.partial(_block_kernel, k=k, row_block=row_block, window=gather_buffers),
        idx,
        pos,
        hot,
        host,
        s=s,
        sp=sp,
        tile=tile,
        k=k,
        feat_dim=feat_dim,
        interpret=interpret,
        scratch=(pltpu.SMEM((tile // row_block,), jnp.int32),),
    )


def _check(hot_table, host_table, gather_buffers, feat_dim):
    if hot_table.shape[1] != host_table.shape[1]:
        raise ValueError("hot and host tables must share the feature dim")
    if gather_buffers < 1:
        raise ValueError(f"gather_buffers must be >= 1, got {gather_buffers}")
    if feat_dim is None:
        return lane_layout(hot_table), lane_layout(host_table), host_table.shape[1]
    if host_table.shape[1] != LANE:
        raise ValueError(f"lane-layout tables must be {LANE} wide, got {host_table.shape[1]}")
    return hot_table, host_table, feat_dim


def cached_gather(
    hot_table: jax.Array,  # [H, F], or [H * k, 128] lane layout with feat_dim
    host_table: jax.Array,  # [N, F], or [N * k, 128] lane layout with feat_dim
    indices: jax.Array,  # int32 [S]
    positions: jax.Array,  # int32 [S] (slot or -1)
    *,
    feat_dim: int | None = None,
    gather_buffers: int = 2,
    interpret: bool | None = None,
) -> jax.Array:
    """Two-source gather; see the module docstring.  Returns ``[S, F]``.

    ``feat_dim=None`` takes ``[R, F]`` tables and builds their lane layout
    on every call; callers that gather repeatedly pass tables already in
    :func:`lane_layout` together with the logical ``feat_dim``.
    ``gather_buffers`` is the number of row copies kept in flight (1 =
    serial copies, 2 = double buffering, the default).
    """
    hot, host, feat_dim = _check(hot_table, host_table, gather_buffers, feat_dim)
    if interpret is None:
        interpret = default_interpret()
    if indices.shape[0] == 0:  # nothing to gather; skip the kernel launch
        return jnp.zeros((0, feat_dim), host_table.dtype)
    return _cached_gather_rows(
        hot,
        host,
        indices,
        positions,
        feat_dim=feat_dim,
        gather_buffers=gather_buffers,
        interpret=interpret,
    )


def cached_gather_blocks(
    hot_table: jax.Array,
    host_table: jax.Array,
    indices: jax.Array,
    positions: jax.Array,
    *,
    feat_dim: int | None = None,
    row_block: int = ROW_BLOCK,
    gather_buffers: int = 2,
    interpret: bool | None = None,
) -> jax.Array:
    """Row-block two-source gather for sorted-run frontiers.

    Semantics and table arguments are those of :func:`cached_gather` for
    ANY index order — blocks that are not contiguous single-source runs
    fall back to per-row copies inside the kernel — but the intended
    caller hands it a deduped (sorted unique) frontier, where most blocks
    collapse to one copy per ``row_block`` rows.  ``row_block == 1`` is
    :func:`cached_gather`.
    """
    if row_block < 1:
        raise ValueError(f"row_block must be >= 1, got {row_block}")
    hot, host, feat_dim = _check(hot_table, host_table, gather_buffers, feat_dim)
    if interpret is None:
        interpret = default_interpret()
    if indices.shape[0] == 0:
        return jnp.zeros((0, feat_dim), host_table.dtype)
    kw = dict(feat_dim=feat_dim, gather_buffers=gather_buffers, interpret=interpret)
    if row_block == 1:
        return _cached_gather_rows(hot, host, indices, positions, **kw)
    return _cached_gather_blocks(hot, host, indices, positions, row_block=row_block, **kw)
