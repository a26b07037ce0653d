"""Mosaic or the Pallas interpreter, decided where a kernel call lowers."""
from __future__ import annotations

import functools

import jax


def by_platform(kernel, *args, interpret: bool | None = None):
    """``kernel(*args, interpret=...)``.  ``interpret=None`` means: the
    compiled Mosaic kernel where the call is lowered for a TPU, the Pallas
    interpreter on every other platform.  The choice is made per lowering
    (``lax.platform_dependent``), not from the process's default backend:
    importing a kernel touches no backend, and an ahead-of-time compile
    for a TPU from a CPU host gets the real kernel.  An explicit bool
    forces one mode."""
    if interpret is not None:
        return kernel(*args, interpret=interpret)
    return jax.lax.platform_dependent(
        *args, tpu=functools.partial(kernel, interpret=False),
        default=functools.partial(kernel, interpret=True))
