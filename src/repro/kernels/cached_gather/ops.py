"""Public op: cached feature gather (kernel on TPU, oracle elsewhere).

``use_kernel=True`` routes through the Pallas kernel — compiled when the
backend is TPU, interpret mode on the CPU (resolved per backend by
:func:`~repro.kernels.cached_gather.kernel.default_interpret`).  The kernel
copies only the winning source row (hit → hot cache, miss → host table),
keeping ``gather_buffers`` row copies in flight.
"""

from __future__ import annotations

import jax

from repro.kernels.cached_gather.kernel import cached_gather, default_interpret
from repro.kernels.cached_gather.ref import cached_gather_ref

__all__ = ["cached_feature_gather", "default_interpret"]


def cached_feature_gather(
    hot_table: jax.Array,
    host_table: jax.Array,
    indices: jax.Array,
    positions: jax.Array,
    *,
    use_kernel: bool = False,
    gather_buffers: int = 2,
    interpret: bool | None = None,
) -> jax.Array:
    """Gather feature rows via DCI's dual-source cache.

    Args:
      hot_table: ``f32[H, F]`` — the device-resident feature cache
        (``H >= 1``; row 0 is a placeholder when the cache is empty).
      host_table: ``f32[N, F]`` — the full host/UVA feature table.
      indices: ``int32[S]`` — node ids to gather (``0 <= id < N``).
      positions: ``int32[S]`` — each id's slot in ``hot_table``, or ``-1``
        for a cache miss (the ``FeatureStore.position_map`` lookup).
      use_kernel: route through the Pallas kernel instead of the jnp
        oracle.
      gather_buffers: row copies the kernel keeps in flight (1 = serial
        copies, 2 = double buffering).
      interpret: force interpret mode on/off; ``None`` resolves by backend
        (compiled on TPU, interpret mode on the CPU).

    Returns:
      ``f32[S, F]`` — row ``i`` is ``hot_table[positions[i]]`` on a hit,
      ``host_table[indices[i]]`` on a miss.
    """
    if use_kernel:
        return cached_gather(
            hot_table,
            host_table,
            indices,
            positions,
            gather_buffers=gather_buffers,
            interpret=interpret,
        )
    return cached_gather_ref(hot_table, host_table, indices, positions)
