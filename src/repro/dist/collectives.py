"""Named-axis collective helpers — the one door mesh reductions go through.

The ROADMAP guardrail says mesh-facing code routes through ``repro.dist``,
not raw ``jax.lax`` collectives, and ``repro.analysis``'s RAW-COLLECTIVE
lint rule machine-checks it: outside this package, ``lax.psum`` & co. are
findings.  These helpers are the sanctioned spelling.  They all take
``axis=None`` to mean "no mesh" and degrade to the single-host identity,
which is exactly the ``jax.lax.psum(x, axis) if axis is not None else x``
pattern the engine/game/transform call sites used to hand-roll — the
stacked simulators and the shard_map production path share one body and
differ only in whether an axis is bound.

Wire-shaping collectives (all_to_all routing tables, ppermute rings,
quantized payloads) live in ``repro.dist.halo`` behind the exchange
registry; this module only carries the axis-wide reductions and index
helpers that appear inside shared jit/shard_map bodies.
"""
from __future__ import annotations

import jax


def psum(x, axis: str | None = None):
    """Sum ``x`` across the mesh ``axis``; identity when ``axis`` is None
    (the stacked/single-host form of the same body)."""
    return jax.lax.psum(x, axis) if axis is not None else x


def pmax(x, axis: str | None = None):
    """Max of ``x`` across the mesh ``axis``; identity when unbound."""
    return jax.lax.pmax(x, axis) if axis is not None else x


def pmin(x, axis: str | None = None):
    """Min of ``x`` across the mesh ``axis``; identity when unbound."""
    return jax.lax.pmin(x, axis) if axis is not None else x


def axis_index(axis: str):
    """This device's position along ``axis`` (for per-device seeding)."""
    return jax.lax.axis_index(axis)


def varying(tree, axis: str | None = None):
    """Type every leaf of ``tree`` as varying over the mesh ``axis``.

    A value made inside ``shard_map`` from constants (zeros, a program's
    init) is the same on every device, while one step of a per-device
    loop makes it differ.  A loop carry must keep one type, so such
    initial carries are cast up front.  Leaves that already vary pass
    through; ``axis=None`` (the stacked form) is the identity."""
    if axis is None:
        return tree

    def cast(x):
        if axis in jax.typeof(x).vma:
            return x
        return jax.lax.pcast(x, (axis,), to="varying")

    return jax.tree_util.tree_map(cast, tree)
