"""Production meshes (multi-pod dry-run §0/§1 of the brief) and the
sharded-serving mesh.

FUNCTIONS, not module constants: importing this module never touches jax
device state.  Single pod = 256 chips as (data=16, model=16); two pods
= 512 chips as (pod=2, data=16, model=16).  The serving mesh is 1-D over
local devices — one axis, one feature shard per device — sized for the
CPU-mesh CI (`XLA_FLAGS=--xla_force_host_platform_device_count=N`) as
much as for real accelerators.
"""

from __future__ import annotations

import numpy as np

import jax

__all__ = ["make_production_mesh", "make_serving_mesh", "serving_devices", "HW"]

SERVE_AXIS = "shard"


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_serving_mesh(num_shards: int):
    """A 1-D ``shard`` mesh over the first ``num_shards`` local devices.

    Clamps to the devices actually present, so ``make_serving_mesh(4)``
    on a 1-device host returns a size-1 mesh (the sharded server then
    co-locates its shards — same partition math, same accounting, no
    cross-device traffic).  A caller that needs the full size checks
    ``mesh.size``.  Built from the device array, not ``jax.make_mesh``,
    so the mesh takes exactly the first ``num_shards`` devices."""
    if num_shards < 1:
        raise ValueError(f"num_shards must be >= 1, got {num_shards}")
    devices = jax.devices()[: max(1, min(num_shards, len(jax.devices())))]
    return jax.sharding.Mesh(np.asarray(devices), (SERVE_AXIS,))


def serving_devices(mesh) -> list:
    """The mesh's devices as a flat per-shard list."""
    return list(np.asarray(mesh.devices).reshape(-1))


# TPU v5e hardware constants for the roofline (per chip).
HW = {
    "peak_flops_bf16": 197e12,  # FLOP/s
    "hbm_bw": 819e9,  # B/s
    "ici_bw_per_link": 50e9,  # B/s per link
}
