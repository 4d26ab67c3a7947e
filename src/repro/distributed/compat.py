"""Lane meshes for the lane-sharded simulator and trainer sweeps."""
from __future__ import annotations

import os

import jax
import numpy as np


def lanes_mesh(n_lanes: int):
    """1-D device mesh over all local devices for lane-sharded sweeps.

    Returns ``None`` when sharding is pointless or unsafe: a single device,
    or a lane count the device count does not divide (lane buckets are
    powers of two, so any power-of-two device count divides them; odd
    device counts fall back to single-device execution).
    """
    devs = jax.devices()
    if len(devs) <= 1 or n_lanes % len(devs) != 0:
        return None
    from jax.sharding import Mesh

    return Mesh(np.array(devs), ("lanes",))


def lane_shardings(n_lanes: int):
    """(lane-sharded, replicated) NamedShardings for a sweep of ``n_lanes``
    independent lanes, or ``(None, None)`` on a single device.

    REPRO_SIM_SHARD=0 is the documented kill switch for ALL lane-sharded
    sweep dispatches (simulator scans and trainer lanes alike) — checked
    here so every caller honours it."""
    if os.environ.get("REPRO_SIM_SHARD", "1") == "0":
        return None, None
    mesh = lanes_mesh(n_lanes)
    if mesh is None:
        return None, None
    from jax.sharding import NamedSharding, PartitionSpec

    return NamedSharding(mesh, PartitionSpec("lanes")), NamedSharding(mesh, PartitionSpec())

