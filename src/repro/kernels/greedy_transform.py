"""Greedy partition transformation — Pallas TPU kernel for Alg. 1.

The transform pass (paper Alg. 1) walks the edge stream once, placing
each edge on one of its endpoints' prior partitions unless that
partition is full, against a k-entry load table that every placement
updates.  It is a sequential scalar recurrence: as an XLA ``lax.scan``
each edge is one loop iteration of a dozen tiny ops (11 µs/edge
measured on a TPU v5e for streams of 0.1–1.6M edges).  This kernel runs
the recurrence on the TPU's scalar unit instead: the load table lives in
SMEM for the whole stream, blocks of edges stream through SMEM in grid
order, and each edge costs a few dozen scalar instructions.

The decision rule is ``core.transform._transform_step`` verbatim (the
equivalence suite pins the two bit for bit): the least-loaded fallback
takes the first minimum, like ``jnp.argmin``, and dead (padding) edges
get partition 0 and add no load.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .platform import by_platform

FIELDS = 7          # pu, pv, du, dv, divu, divv, live per edge
# 1-D operands are tiled by 1024 words in HBM, so a block is a multiple
BLOCK = 1024


def _greedy_kernel(edges_ref, lmax_ref, assign_ref, loads, *, k: int,
                   block: int):
    @pl.when(pl.program_id(0) == 0)
    def _():
        for p in range(k):
            loads[p] = jnp.int32(0)

    lmax = lmax_ref[0]

    def least_loaded():
        def scan(p, best):
            return jnp.where(loads[p] < loads[best], p, best)
        return jax.lax.fori_loop(1, k, scan, jnp.int32(0))

    def body(i, c):
        pu = edges_ref[i]
        pv = edges_ref[block + i]
        du = edges_ref[2 * block + i]
        dv = edges_ref[3 * block + i]
        divu = edges_ref[4 * block + i]
        divv = edges_ref[5 * block + i]
        live = edges_ref[6 * block + i]
        full_u = loads[pu].astype(jnp.float32) >= lmax
        full_v = loads[pv].astype(jnp.float32) >= lmax
        full = full_u | full_v
        least = jax.lax.cond(full_u & full_v, least_loaded,
                             lambda: jnp.int32(0))
        overflow_choice = jnp.where(~full_u, pu,
                                    jnp.where(~full_v, pv, least))
        mirror_choice = jnp.where(divu != 0, pv, pu)
        has_mirror = (divu > 0) | (divv > 0)
        degree_choice = jnp.where(dv > du, pu, pv)
        normal = jnp.where(pu == pv, pu,
                           jnp.where(has_mirror, mirror_choice,
                                     degree_choice))
        p = jnp.where(full, overflow_choice, normal)
        p = jnp.where(live != 0, p, 0)
        loads[p] = loads[p] + live
        assign_ref[i] = p
        return c

    jax.lax.fori_loop(0, block, body, 0)


def greedy_transform(pu, pv, du, dv, divu, divv, live, lmax, *, k: int,
                     block: int = BLOCK, interpret: bool | None = None):
    """Alg. 1 over the whole stream: per-edge int32 arrays (E,) of the
    endpoints' prior partitions, streamed degrees, divided flags and the
    live flag; ``lmax`` the balance cap (python float or traced scalar).
    Returns the edge→partition assignment (E,) int32.  ``interpret`` as in
    ``kernels.platform.by_platform``."""
    E = pu.shape[0]
    nblk = max(1, -(-E // block))
    pad = nblk * block - E
    cols = [jnp.pad(jnp.asarray(a, jnp.int32), (0, pad))
            for a in (pu, pv, du, dv, divu, divv, live)]
    # one SMEM block per grid step: the block's fields back to back
    packed = (jnp.stack(cols).reshape(FIELDS, nblk, block)
              .transpose(1, 0, 2).reshape(-1))
    lmax_arr = jnp.asarray(lmax, jnp.float32).reshape((1,))
    kern = functools.partial(_greedy_kernel, k=int(k), block=int(block))

    def call(*args, interpret: bool):
        return pl.pallas_call(
            kern,
            grid=(nblk,),
            in_specs=[
                pl.BlockSpec((FIELDS * block,), lambda i: (i,),
                             memory_space=pltpu.SMEM),
                pl.BlockSpec(memory_space=pltpu.SMEM),
            ],
            out_specs=pl.BlockSpec((block,), lambda i: (i,),
                                   memory_space=pltpu.SMEM),
            out_shape=jax.ShapeDtypeStruct((nblk * block,), jnp.int32),
            scratch_shapes=[pltpu.SMEM((k,), jnp.int32)],
            # the load table carries from block to block: grid order is
            # stream order
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
            interpret=interpret,
        )(*args)

    return by_platform(call, packed, lmax_arr, interpret=interpret)[:E]
