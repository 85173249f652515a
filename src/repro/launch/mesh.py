"""Production mesh construction.

A FUNCTION, not a module-level constant: importing this module never
touches jax device state (the dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before any jax
init; smoke tests and benchmarks must keep seeing 1 device).
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips/pod (single pod), or 2 pods = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes):
    """Elastic-restart target meshes (any factorization of the devices).

    Auto axes: the sharding rules place arrays by PartitionSpec and let
    GSPMD propagate (`jax.make_mesh` defaults to Explicit axes, under
    which the model's gathers need per-op output shardings)."""
    from jax.sharding import AxisType
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))
