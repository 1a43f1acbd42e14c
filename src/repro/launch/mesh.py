"""Mesh construction — the ONE shared path for every launcher (train, serve,
dryrun, tests). Functions (not module-level constants) so importing never
touches jax device state; the dry-run sets XLA_FLAGS for 512 host devices
before any jax import. Every axis is `AxisType.Auto`: GSPMD propagates
shardings from the placements the plan sources pin.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str],
              devices: Optional[Sequence] = None) -> jax.sharding.Mesh:
    axes = tuple(axes)
    return jax.make_mesh(tuple(shape), axes,
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def parse_mesh_shape(spec: str) -> Tuple[Tuple[int, ...], Tuple[str, ...]]:
    """'4x2' -> ((4, 2), ('data', 'model')); a 3-dim spec adds 'pod'."""
    dims = tuple(int(x) for x in spec.split("x"))
    if not 1 <= len(dims) <= 3:
        raise ValueError(f"mesh spec {spec!r}: want 1-3 'x'-separated dims")
    axes = ("pod", "data", "model")[-len(dims):]
    return dims, axes


def make_host_mesh(model: int = 1,
                   devices: Optional[Sequence] = None) -> jax.sharding.Mesh:
    """(data, model) mesh over `devices` (default: every local device)."""
    n = len(devices) if devices is not None else jax.device_count()
    if n % model:
        raise ValueError(f"model parallelism {model} does not divide "
                         f"device count {n}")
    data = n // model
    return make_mesh((data, model), ("data", "model"), devices=devices)
