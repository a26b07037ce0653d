"""Pure-jnp oracle of the game best-response kernel (the allclose
target).  The clustering and transform kernels are checked against the
XLA scans they replace (``core.clustering``, ``core.transform``)."""
from __future__ import annotations

import jax.numpy as jnp

BIG = 3.0e38


def game_bestresponse_ref(aff, sizes, row_tot, cur, loads, *, lam: float,
                          k: int | None = None):
    M, kpad = aff.shape
    if k is None:
        k = kpad
    pids = jnp.arange(kpad)[None, :]
    own = (pids == cur[:, None]).astype(jnp.float32)
    loads_ex = loads[None, :].astype(jnp.float32) - sizes[:, None] * own
    cost = (lam / k) * sizes[:, None].astype(jnp.float32) \
        * (loads_ex + sizes[:, None]) \
        + 0.5 * (row_tot[:, None].astype(jnp.float32) - aff)
    cost = jnp.where(pids < k, cost, BIG)
    return jnp.argmin(cost, 1).astype(jnp.int32), jnp.min(cost, 1)
