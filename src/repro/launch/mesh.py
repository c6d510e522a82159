"""Production mesh construction.

A FUNCTION, not a module constant: importing this module never touches jax
device state (the dry-run must set XLA_FLAGS before first jax init).

Every mesh is built with ``Auto`` axes: the sharding rules hand XLA's SPMD
partitioner constraints and let it place the collectives. ``jax.make_mesh``
defaults to ``Explicit`` axes on jax 0.9, under which the embedding gather
and the layer scan are refused at trace time.
"""

from __future__ import annotations


import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, devices=None):
    """An ``Auto``-axis mesh over the first prod(shape) devices."""
    n = 1
    for s in shape:
        n *= s
    devices = list(devices if devices is not None else jax.devices())[:n]
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {tuple(shape)} needs {n} devices, have {len(devices)} — "
            f"set XLA_FLAGS=--xla_force_host_platform_device_count={n} before "
            f"any jax import (launch/dryrun.py does this)")
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(shape),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    if multi_pod:
        return make_mesh((2, 16, 16), ("pod", "data", "model"))
    return make_mesh((16, 16), ("data", "model"))
