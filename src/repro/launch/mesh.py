"""Mesh construction for the graph system.

FUNCTIONS, not module constants — importing this module never touches
jax device state.  Callers that need N host devices must set
XLA_FLAGS=--xla_force_host_platform_device_count=N before any jax import
(launch/dryrun.py does; tests spawn subprocesses).

Every mesh here has ``Auto`` axes (``jax.make_mesh``'s default is
``Explicit``).  The graph meshes are entered only by ``shard_map``, which
takes every axis into manual control whatever its type."""
from __future__ import annotations

import warnings

import jax
from jax.sharding import AxisType


def _auto_mesh(shape: tuple, axes: tuple):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_graph_mesh(k: int):
    """The graph engine's mesh for k partitions: one flat axis ``parts``
    over the largest number of visible devices that divides k — k itself
    when k ≤ devices, else k/D partitions on each of D devices (16
    partitions on four chips put four on each).  The engine reads the
    partitions per device off the layout and the axis size.  Where no
    count of devices above one divides k (a prime k above the device
    count) it raises, rather than put every partition on one chip of a
    multi-chip host; where the divisor leaves devices idle (k = 20 on
    eight) it warns."""
    n = len(jax.devices())
    devices = max(d for d in range(1, min(k, n) + 1) if k % d == 0)
    if k > n:
        if devices == 1 < n:
            raise ValueError(
                f"no count of the {n} devices above one divides k = {k}: "
                f"pick a k that {n} devices or fewer divide")
        if devices < n:
            warnings.warn(f"k = {k} partitions on {devices} of the {n} "
                          f"devices: {n - devices} stay idle", stacklevel=2)
    return _auto_mesh((devices,), ("parts",))


def make_stream_mesh(n: int):
    """The sharded partitioner's mesh: n stream slices on one flat axis
    (repro.core.partitioner backend="sharded", paper §III-C)."""
    return _auto_mesh((n,), ("stream",))
