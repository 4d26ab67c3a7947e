"""Production mesh construction.

A FUNCTION, not a module-level constant — importing this module never touches
jax device state, so smoke tests see 1 device while the dry-run (which sets
``--xla_force_host_platform_device_count=512`` before any import) sees the
full placeholder fleet.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def auto_mesh(shape: tuple, axes: tuple):
    """``jax.make_mesh`` with Auto axes.  The sharding rules place arrays
    with ``with_sharding_constraint``, which refuses the Explicit axes that
    ``jax.make_mesh`` builds by default."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_mesh(spec: str):
    """Parse e.g. '16x16' / 'pod:2x16x16' / '4x2' into a mesh (small-mesh tests)."""
    if spec.startswith("pod:"):
        dims = tuple(int(x) for x in spec[4:].split("x"))
        axes = ("pod", "data", "model")[-len(dims):]
    else:
        dims = tuple(int(x) for x in spec.split("x"))
        axes = ("data", "model")[-len(dims):]
    return auto_mesh(dims, axes)


# TPU v5e hardware constants (roofline targets; the container runs on CPU).
PEAK_FLOPS_BF16 = 197e12  # per chip
HBM_BW = 819e9  # bytes/s per chip
ICI_BW = 50e9  # bytes/s per link
ICI_LINKS = 4  # 2D torus: 4 links/chip (v5e)
DCI_BW = 25e9  # bytes/s per chip across pods (optics), used for the pod axis
HBM_PER_CHIP = 16 * 2**30
