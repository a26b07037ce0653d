"""Clustering fused-scatter — Pallas TPU kernel for the blocked stream scan.

Paper Alg. 2 is a sequential per-edge transition over vertex→cluster /
degree / volume tables.  The blocked scan in ``core.clustering`` localizes
each 128-edge block into one KB-sized fused table ``buf`` ([0, 2B) vertex
slot → local cluster slot, [2B, 4B) streamed degree, [4B, 10B) cluster
volumes) and runs the exact transition per edge with two fused gathers +
ONE fused 8-lane scatter.  XLA:CPU still charges every computed-index
scatter inside a loop body a buffer copy + kernel call (~1.3 µs measured —
the 9.9 µs/edge floor in EXPERIMENTS.md); this kernel keeps the whole
block table resident in kernel memory instead, so the 8-lane scatter is
eight register→memory read-modify-writes with no buffer copy at all.

``edge_decisions`` is the per-edge register math, shared VERBATIM with the
XLA scan path (``core.clustering._edge_step_local`` composes the same
function) — the two strategies are bit-identical by construction, and the
equivalence suite pins it.

``vmax`` ships as a (1,)-shaped input (like ``lam`` in game_bestresponse):
the sharded backend derives each device's V_max from its slice's real edge
count, so it is data-dependent.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .platform import by_platform


def edge_decisions(cu0, cv0, d0, d1, vg0, vg1, live, nid, nid0,
                   seen_v, seen_deg, *, vmax, allow_split: bool,
                   split_degree_factor: float, B: int):
    """One streamed edge's allocation–splitting–migration decisions in
    scalar registers (paper Alg. 2 lines 3–26 + the §IV-A same-cluster tie
    rule and migration post-guard).

    Inputs are the six gathered table entries (endpoint cluster slots,
    streamed degrees, and their clusters' volumes) plus the carried
    counters; outputs are the updated counters, the endpoints' new cluster
    slots, and the ≤4 volume-slot (index, delta) pairs of the fused
    scatter — the caller owns the actual gathers/scatter, so the XLA scan
    and the Pallas kernel share every decision bit."""
    scrap = 6 * B - 1                 # top fresh slot absorbs dead writes

    def sel(p, a0, a1, a2, a3):
        return jnp.where(p == 0, a0, jnp.where(p == 1, a1,
                         jnp.where(p == 2, a2, a3)))

    def bump(p, x, a0, a1, a2, a3):
        return (a0 + jnp.where(p == 0, x, 0), a1 + jnp.where(p == 1, x, 0),
                a2 + jnp.where(p == 2, x, 0), a3 + jnp.where(p == 3, x, 0))

    du = d0 + 1                       # degrees AFTER line 6's increment
    dv = d1 + 1
    duf = du.astype(jnp.float32)
    dvf = dv.astype(jnp.float32)

    # allocation (lines 3-5): u first, then v
    preu, prev = cu0 >= 0, cv0 >= 0
    id0 = jnp.where(preu, cu0, 2 * B + (nid - nid0))
    nid = nid + (live & ~preu).astype(jnp.int32)
    id1 = jnp.where(prev, cv0, 2 * B + (nid - nid0))
    nid = nid + (live & ~prev).astype(jnp.int32)
    same = id0 == id1
    seen_v = seen_v + (live & ~preu).astype(jnp.int32) \
        + (live & ~prev).astype(jnp.int32)
    seen_deg = seen_deg + 2 * live.astype(jnp.int32)
    if split_degree_factor > 0.0:
        dthr = split_degree_factor * seen_deg.astype(jnp.float32) \
            / jnp.maximum(seen_v, 1).astype(jnp.float32)
    else:
        dthr = jnp.float32(0.0)

    # register volumes (v2/v3 are the fresh split slots, created empty)
    v0 = jnp.where(preu, vg0, 0)
    v1 = jnp.where(prev & ~same, vg1, 0)
    v2 = v3 = jnp.int32(0)
    i0, i1 = v0, v1
    lvflag = live.astype(jnp.int32)
    pu = jnp.int32(0)
    pv = jnp.where(same, 0, 1)
    v0, v1, v2, v3 = bump(pu, lvflag, v0, v1, v2, v3)
    v0, v1, v2, v3 = bump(pv, lvflag, v0, v1, v2, v3)

    if allow_split:
        # same-cluster overflow → split only the higher-degree endpoint;
        # different clusters → split u first (lines 8-13), then v (14-18)
        x_is_u = du >= dv
        t1_is_u = jnp.where(same, x_is_u, True)
        pt1 = jnp.where(t1_is_u, pu, pv)
        dt1 = jnp.where(t1_is_u, du, dv)
        fire1 = live & (sel(pt1, v0, v1, v2, v3) >= vmax) \
            & (jnp.where(t1_is_u, duf, dvf) >= dthr)
        f1 = fire1.astype(jnp.int32)
        v0, v1, v2, v3 = bump(pt1, -dt1 * f1, v0, v1, v2, v3)
        v2 = v2 + dt1 * f1
        pu = jnp.where(fire1 & t1_is_u, 2, pu)
        pv = jnp.where(fire1 & ~t1_is_u, 2, pv)
        id2 = 2 * B + (nid - nid0)
        nid = nid + f1
        fire2 = live & ~same & (sel(pv, v0, v1, v2, v3) >= vmax) \
            & (dvf >= dthr)
        f2 = fire2.astype(jnp.int32)
        v0, v1, v2, v3 = bump(pv, -dv * f2, v0, v1, v2, v3)
        v3 = v3 + dv * f2
        id3 = 2 * B + (nid - nid0)
        nid = nid + f2
        pv = jnp.where(fire2, 3, pv)
    else:
        fire1 = fire2 = live & False
        t1_is_u = fire1
        id2 = id3 = jnp.int32(scrap)

    # migration (lines 20-26) with the post-guard
    vu_cur = sel(pu, v0, v1, v2, v3)
    vv_cur = sel(pv, v0, v1, v2, v3)
    both_room = live & (pu != pv) & (vu_cur < vmax) & (vv_cur < vmax)
    u_moves = both_room & (vu_cur <= vv_cur) & (vv_cur + du < vmax)
    v_moves = both_room & (vu_cur > vv_cur) & (vu_cur + dv < vmax)
    mu = u_moves.astype(jnp.int32)
    mv = v_moves.astype(jnp.int32)
    v0, v1, v2, v3 = bump(pu, -du * mu + dv * mv, v0, v1, v2, v3)
    v0, v1, v2, v3 = bump(pv, du * mu - dv * mv, v0, v1, v2, v3)
    pu, pv = (jnp.where(u_moves, pv, pu), jnp.where(v_moves, pu, pv))

    newu = jnp.where(live, sel(pu, id0, id1, id2, id3), cu0)
    newv = jnp.where(live, sel(pv, id0, id1, id2, id3), cv0)
    vol_ids = (jnp.clip(jnp.where(live, id0, scrap), 0, scrap),
               jnp.clip(jnp.where(same, scrap, id1), 0, scrap),
               jnp.clip(jnp.where(fire1, id2, scrap), 0, scrap),
               jnp.clip(jnp.where(fire2, id3, scrap), 0, scrap))
    vol_deltas = (v0 - i0, v1 - i1, v2, v3)
    fire_u = fire1 & t1_is_u
    fire_v = (fire1 & ~t1_is_u) | fire2
    packed = (fire_u.astype(jnp.int32) + 2 * fire_v.astype(jnp.int32))
    return nid, seen_v, seen_deg, newu, newv, vol_ids, vol_deltas, packed


def _cluster_kernel(ints_ref, buf_ref, scal_ref, vmax_ref,
                    buf_out, scal_out, pk_out, *, B: int,
                    allow_split: bool, split_degree_factor: float):
    # every operand lives in SMEM: the transition is scalar work on
    # computed indices, which the TPU's scalar unit does with plain loads
    # and stores, while a vector memory would need a masked vector op per
    # table entry.  The whole block table stays resident in the output
    # block for the full edge loop — the fused 8-lane scatter becomes
    # eight in-memory read-modify-writes (duplicate lanes accumulate,
    # matching .at[].add).  ``ints`` arrives flattened column-major:
    # [0, B) local u slots, [B, 2B) local v slots, [2B, 3B) live flags.
    def copy(j, c):
        buf_out[j] = buf_ref[j]
        return c

    jax.lax.fori_loop(0, 10 * B, copy, 0)
    vmax = vmax_ref[0]
    scrap = 6 * B - 1

    def body(i, carry):
        nid, nid0, seen_v, seen_deg = carry
        lu = ints_ref[i]
        lv_ = ints_ref[B + i]
        live = ints_ref[2 * B + i] != 0
        cu0 = buf_out[lu]
        cv0 = buf_out[lv_]
        d0 = buf_out[2 * B + lu]
        d1 = buf_out[2 * B + lv_]
        vg0 = buf_out[4 * B + jnp.clip(cu0, 0, scrap)]
        vg1 = buf_out[4 * B + jnp.clip(cv0, 0, scrap)]
        (nid, seen_v, seen_deg, newu, newv, vol_ids, vol_deltas,
         packed) = edge_decisions(
            cu0, cv0, d0, d1, vg0, vg1, live, nid, nid0, seen_v, seen_deg,
            vmax=vmax, allow_split=allow_split,
            split_degree_factor=split_degree_factor, B=B)
        lvflag = live.astype(jnp.int32)
        # lane 0 is guarded against lu == lv_ (dead self-loop edges alias
        # the two vertex slots; lane 1 carries the whole pointer update)
        buf_out[lu] = buf_out[lu] + jnp.where(lu != lv_, newu - cu0, 0)
        buf_out[lv_] = buf_out[lv_] + (newv - cv0)
        buf_out[2 * B + lu] = buf_out[2 * B + lu] + lvflag
        buf_out[2 * B + lv_] = buf_out[2 * B + lv_] + lvflag
        for a, dlt in zip(vol_ids, vol_deltas):
            buf_out[4 * B + a] = buf_out[4 * B + a] + dlt
        pk_out[i] = packed
        return (nid, nid0, seen_v, seen_deg)

    nid, nid0, seen_v, seen_deg = jax.lax.fori_loop(
        0, B, body,
        (scal_ref[0], scal_ref[1], scal_ref[2], scal_ref[3]))
    scal_out[0] = nid
    scal_out[1] = nid0
    scal_out[2] = seen_v
    scal_out[3] = seen_deg


def cluster_scatter(ints, buf, scal, vmax, *, allow_split: bool = True,
                    split_degree_factor: float = 0.0,
                    interpret: bool | None = None):
    """One block of the clustering scan: ``ints`` (B, 3) int32 rows of
    (local u slot, local v slot, live); ``buf`` (10B,) int32 fused block
    table; ``scal`` (4,) int32 = (nid, nid0, seen_v, seen_deg); ``vmax``
    python float or traced scalar.  Returns (buf', scal', packed (B,))
    with ``packed`` the per-edge split events (fire_u + 2·fire_v) —
    bit-identical to the XLA inner scan at any input.  ``interpret``
    as in ``kernels.platform.by_platform``."""
    B = ints.shape[0]
    assert buf.shape == (10 * B,), (buf.shape, B)
    vmax_arr = jnp.asarray(vmax, jnp.float32).reshape((1,))
    kern = functools.partial(
        _cluster_kernel, B=int(B), allow_split=bool(allow_split),
        split_degree_factor=float(split_degree_factor))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)

    def call(*args, interpret: bool):
        return pl.pallas_call(
            kern,
            in_specs=[smem, smem, smem, smem],
            out_specs=[smem, smem, smem],
            name="cluster_scatter",
            out_shape=[
                jax.ShapeDtypeStruct((10 * B,), jnp.int32),
                jax.ShapeDtypeStruct((4,), jnp.int32),
                jax.ShapeDtypeStruct((B,), jnp.int32),
            ],
            interpret=interpret,
        )(*args)

    return by_platform(call, jnp.asarray(ints, jnp.int32).T.reshape(-1),
                       buf, jnp.asarray(scal, jnp.int32), vmax_arr,
                       interpret=interpret)
