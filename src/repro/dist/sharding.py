"""Named-axis sharding rules (logical tags → mesh axes).

The partitioner never names mesh axes: it tags each array dim with a
logical name and ``resolve_spec`` looks the tag up in a rule table to
find the mesh axis (if any) the dim lands on.

Rule table:
- ``PARTITIONER_RULES`` — the §III-C stream split: the edge stream on a
  flat ``stream`` axis, one contiguous slice per device; vertex state
  replicated.

Axes that do not evenly divide their dim are dropped (replicated): a bad
tag can cost performance, never a compile failure.
"""
from __future__ import annotations

from jax.sharding import PartitionSpec as P

# the partitioner's edge stream: one contiguous stream slice per device
# along a flat "stream" axis (repro.core.partitioner, paper §III-C)
PARTITIONER_RULES: dict = {
    "stream": "stream",
    "vertex": None,                   # vertex state replicated per node
}


def resolve_spec(shape: tuple, tags: tuple, rules: dict,
                 axis_sizes: dict) -> P:
    """Pure tag→PartitionSpec resolution (unit-testable without devices).

    Per dim: look the tag up in ``rules``; drop axes absent from the mesh,
    axes already used by an earlier dim, and axes whose product does not
    divide the dim size (replicate instead).
    """
    assert len(tags) == len(shape), (tags, shape)
    used: set = set()
    entries = []
    for dim, tag in zip(shape, tags):
        ax = rules.get(tag) if tag is not None else None
        if ax is None:
            entries.append(None)
            continue
        axes = ax if isinstance(ax, tuple) else (ax,)
        axes = tuple(a for a in axes if a in axis_sizes and a not in used)
        size = 1
        for a in axes:
            size *= axis_sizes[a]
        if not axes or dim % size != 0:
            entries.append(None)
            continue
        used.update(axes)
        entries.append(axes if len(axes) > 1 else axes[0])
    return P(*entries)
