"""Game best-response — Pallas TPU kernel for the paper's compute hot spot.

Paper §V: "the retrieval of the Nash equilibrium is compute-bound".  The
inner loop evaluates, for every cluster i in a batch, the cost of each of
the k partition choices

    cost(i, p) = (λ/k)·|c_i|·(loads_p − |c_i|·[a_i = p] + |c_i|)
               + ½·(row_tot_i − A[i, p])

and takes the argmin.  HDRF pays a lock on a global table per edge; CLUGP's
batched game turns this into an embarrassingly-tileable (m × k) sweep —
exactly the MXU/VPU-friendly shape.  The cut-mass matrix A (batch rows ×
k) is produced by a preceding SpMM (cluster adjacency × one-hot assign);
this kernel fuses the cost assembly + argmin so the (m, k) cost matrix
never hits HBM.

Blocks: (block_m, kpad) rows of A in VMEM, transposed in-kernel so the
batch rows run along the 128 lanes; the per-row operands and results
travel as lane-dense (1, M) rows and loads as one (kpad, 1) column, so
every reduction over the k choices runs down the sublanes.  k is padded
to a lane multiple (128) and the padded choices cost +BIG.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .platform import by_platform

BIG = 3.0e38


def _br_kernel(aff_ref, sizes_ref, rowtot_ref, cur_ref, loads_ref, lam_ref,
               best_ref, cost_ref, *, k: int, kpad: int):
    aff = aff_ref[...].astype(jnp.float32).T         # (kpad, bm)
    sizes = sizes_ref[...].astype(jnp.float32)       # (1, bm)
    rowtot = rowtot_ref[...].astype(jnp.float32)     # (1, bm)
    cur = cur_ref[...]                               # (1, bm)
    loads = loads_ref[...].astype(jnp.float32)       # (kpad, 1)
    lam = lam_ref[0]                                 # SMEM scalar

    pids = jax.lax.broadcasted_iota(jnp.int32, aff.shape, 0)
    own = (pids == cur).astype(jnp.float32)
    loads_ex = loads - sizes * own
    cost = (lam / k) * sizes * (loads_ex + sizes) + 0.5 * (rowtot - aff)
    cost = jnp.where(pids < k, cost, BIG)
    low = jnp.min(cost, axis=0, keepdims=True)
    # argmin as "lowest choice reaching the minimum" — the tie rule of
    # jnp.argmin, spelled with the min reductions Mosaic lowers
    best_ref[...] = jnp.min(jnp.where(cost == low, pids, kpad), axis=0,
                            keepdims=True)
    cost_ref[...] = low


def game_bestresponse(aff, sizes, row_tot, cur, loads, *, lam,
                      k: int | None = None, block_m: int = 256,
                      interpret: bool | None = None):
    """aff: (M, Kpad) cut mass; sizes/row_tot: (M,); cur: (M,) int32;
    loads: (Kpad,).  ``k`` = real partition count (< Kpad ⇒ padded lanes
    masked to +BIG).  ``lam`` may be a python float or a traced scalar —
    the jitted partitioner pipeline computes λ_max from the streamed
    cluster graph, so it is data-dependent and ships to the kernel as a
    (1,)-shaped input rather than a compile-time constant.
    Returns (best (M,), cost (M,)).  ``interpret`` as in
    ``kernels.platform.by_platform``."""
    M, kpad = aff.shape
    if k is None:
        k = kpad
    assert M % block_m == 0
    grid = (M // block_m,)
    lam_arr = jnp.asarray(lam, jnp.float32).reshape((1,))
    kern = functools.partial(_br_kernel, k=int(k), kpad=int(kpad))
    row = pl.BlockSpec((1, block_m), lambda i: (0, i))

    def call(*args, interpret: bool):
        return pl.pallas_call(
            kern,
            grid=grid,
            in_specs=[
                pl.BlockSpec((block_m, kpad), lambda i: (i, 0)),
                row, row, row,
                pl.BlockSpec((kpad, 1), lambda i: (0, 0)),
                pl.BlockSpec(memory_space=pltpu.SMEM),
            ],
            out_specs=[row, row],
            name="game_bestresponse",
            out_shape=[
                jax.ShapeDtypeStruct((1, M), jnp.int32),
                jax.ShapeDtypeStruct((1, M), jnp.float32),
            ],
            interpret=interpret,
        )(*args)

    best, cost = by_platform(
        call, aff, sizes.reshape(1, M), row_tot.reshape(1, M),
        cur.reshape(1, M), loads.reshape(kpad, 1), lam_arr,
        interpret=interpret)
    return best[0], cost[0]
