"""Jit'd public wrappers around the Pallas kernels.

``interpret=None`` (the default) compiles the Mosaic kernel where a call
is lowered for a TPU and runs the Pallas interpreter elsewhere
(``kernels.platform.by_platform``); an explicit bool forces one mode.
"""
from __future__ import annotations

from functools import partial

import jax

from .flash_attention import flash_attention as _flash
from .game_bestresponse import game_bestresponse as _gbr
from .ell_spmv import ell_spmv as _spmv
from .cluster_scatter import cluster_scatter as _cscat
from .platform import by_platform


@partial(jax.jit, static_argnames=("causal", "block_q", "block_kv",
                                   "interpret"))
def flash_attention(q, k, v, causal: bool = True, block_q: int = 128,
                    block_kv: int = 128, interpret: bool | None = None):
    return by_platform(partial(_flash, causal=causal, block_q=block_q,
                               block_kv=block_kv), q, k, v,
                       interpret=interpret)


@partial(jax.jit, static_argnames=("k", "block_m", "interpret"))
def game_best_response(aff, sizes, row_tot, cur, loads, lam,
                       k: int | None = None, block_m: int = 256,
                       interpret: bool | None = None):
    return _gbr(aff, sizes, row_tot, cur, loads, lam=lam, k=k,
                block_m=block_m, interpret=interpret)


@partial(jax.jit, static_argnames=("block_m", "interpret"))
def ell_spmv(vals, cols, x, block_m: int = 256,
             interpret: bool | None = None):
    return by_platform(partial(_spmv, block_m=block_m), vals, cols, x,
                       interpret=interpret)


@partial(jax.jit, static_argnames=("allow_split", "split_degree_factor",
                                   "interpret"))
def cluster_scatter(ints, buf, scal, vmax, allow_split: bool = True,
                    split_degree_factor: float = 0.0,
                    interpret: bool | None = None):
    return _cscat(ints, buf, scal, vmax, allow_split=allow_split,
                  split_degree_factor=split_degree_factor,
                  interpret=interpret)
