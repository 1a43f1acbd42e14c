"""Mesh construction — the ONE shared path for every launcher (train, serve,
api, tests). Functions (not module-level constants) so importing never
touches jax device state. Every axis is `AxisType.Auto`: GSPMD propagates
shardings from the placements the sharding rules pin.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str],
              devices: Optional[Sequence] = None) -> jax.sharding.Mesh:
    axes = tuple(axes)
    return jax.make_mesh(tuple(shape), axes,
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_host_mesh(model: int = 1,
                   devices: Optional[Sequence] = None) -> jax.sharding.Mesh:
    """(data, model) mesh over `devices` (default: every local device)."""
    n = len(devices) if devices is not None else jax.device_count()
    if n % model:
        raise ValueError(f"model parallelism {model} does not divide "
                         f"device count {n}")
    data = n // model
    return make_mesh((data, model), ("data", "model"), devices=devices)
