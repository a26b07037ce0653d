"""Jit'd public wrappers around the Pallas kernels.

``interpret=None`` (the default) compiles the Mosaic kernel where a call
is lowered for a TPU and runs the Pallas interpreter elsewhere
(``kernels.platform.by_platform``); an explicit bool forces one mode.
"""
from __future__ import annotations

from functools import partial

import jax

from .game_bestresponse import game_bestresponse as _gbr
from .cluster_scatter import cluster_scatter as _cscat


@partial(jax.jit, static_argnames=("k", "block_m", "interpret"))
def game_best_response(aff, sizes, row_tot, cur, loads, lam,
                       k: int | None = None, block_m: int = 256,
                       interpret: bool | None = None):
    return _gbr(aff, sizes, row_tot, cur, loads, lam=lam, k=k,
                block_m=block_m, interpret=interpret)


@partial(jax.jit, static_argnames=("allow_split", "split_degree_factor",
                                   "interpret"))
def cluster_scatter(ints, buf, scal, vmax, allow_split: bool = True,
                    split_degree_factor: float = 0.0,
                    interpret: bool | None = None):
    return _cscat(ints, buf, scal, vmax, allow_split=allow_split,
                  split_degree_factor=split_degree_factor,
                  interpret=interpret)
