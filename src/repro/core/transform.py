"""Pass 3 — partition transformation (paper Alg. 1).

Restream the edges and turn the vertex→partition mapping (join of passes
1 and 2) into an edge→partition assignment, strictly enforcing the balance
cap L_max = τ·|E|/k:

  - both endpoints' partitions full   → any underflow partition (least load)
  - same partition                    → keep
  - an endpoint was divided (has mirrors) → reuse the mirror side (free cut)
  - otherwise                         → cut the higher-degree endpoint
                                        (HDRF-style, lines 20-22)

Space O(k) (the load array), time O(|E|) — matching §III-C.
"""
from __future__ import annotations

from functools import partial

import numpy as np

import jax
import jax.numpy as jnp

from ..dist import collectives as coll
from ..kernels.greedy_transform import greedy_transform


def transform_np(src: np.ndarray, dst: np.ndarray,
                 vertex_part: np.ndarray, deg: np.ndarray,
                 divided: np.ndarray, k: int, tau: float = 1.0, *,
                 loads: np.ndarray | None = None,
                 lmax: float | None = None) -> np.ndarray:
    """``loads``/``lmax`` seed the greedy pass with pre-existing
    per-partition edge counts and an external balance cap — the
    incremental window-assign path (``stages.incremental_assign``)
    streams NEW edges against the loads the resident partition already
    carries.  Defaults reproduce the batch Alg. 1 exactly."""
    E = src.shape[0]
    if lmax is None:
        lmax = tau * E / float(k)
    loads = (np.zeros(k, dtype=np.int64) if loads is None
             else np.asarray(loads, dtype=np.int64).copy())
    assign = np.zeros(E, dtype=np.int32)
    vp = vertex_part
    for i in range(E):
        u = int(src[i]); v = int(dst[i])
        pu = int(vp[u]); pv = int(vp[v])
        if loads[pu] >= lmax or loads[pv] >= lmax:      # lines 6-14
            if loads[pu] < lmax:
                p = pu
            elif loads[pv] < lmax:
                p = pv
            else:
                p = int(np.argmin(loads))
        elif pu == pv:                                   # lines 15-16
            p = pu
        elif divided[u]:                                 # lines 17-19
            p = pv
        elif divided[v]:
            p = pu
        elif deg[v] > deg[u]:                            # lines 20-22
            p = pu
        else:
            p = pv
        assign[i] = p
        loads[p] += 1
    return assign


def _transform_step(loads, edge, *, lmax, k: int, k_real=None):
    pu, pv, du, dv, divu, divv, live = edge
    full_u = loads[pu] >= lmax
    full_v = loads[pv] >= lmax
    # lanes past the traced live count (the k_max-padded sweep) must not
    # win the least-loaded fallback — they stay empty forever
    cand = (loads if k_real is None
            else jnp.where(jnp.arange(k) < k_real, loads,
                           jnp.iinfo(loads.dtype).max))
    least = jnp.argmin(cand).astype(jnp.int32)
    overflow_choice = jnp.where(~full_u, pu, jnp.where(~full_v, pv, least))
    same = pu == pv
    mirror_choice = jnp.where(divu.astype(bool), pv, pu)
    has_mirror = (divu > 0) | (divv > 0)
    degree_choice = jnp.where(dv > du, pu, pv)
    normal = jnp.where(same, pu,
                       jnp.where(has_mirror, mirror_choice, degree_choice))
    p = jnp.where(full_u | full_v, overflow_choice, normal).astype(jnp.int32)
    p = jnp.where(live.astype(bool), p, 0)
    # arithmetic one-hot instead of a scatter: XLA:CPU pays a buffer copy
    # + kernel call per computed-index scatter inside a loop body, and a
    # (k,)-wide fused select is far cheaper; padded edges carry no load
    loads = loads + jnp.where(jnp.arange(k) == p, live, 0)
    return loads, p


def transform_jax(src, dst, vertex_part, deg, divided, k: int,
                  tau: float = 1.0, mask=None, lmax=None, k_real=None):
    """lax.scan form of Alg. 1 (used inside the jitted pipeline).

    ``mask`` marks live edges (the sharded backend pads each device's
    stream slice to a static length; padded rows get partition 0 and add
    no load).  ``lmax`` overrides the balance cap — per-device slices use
    τ·|E_local|/k with the *real* (masked) edge count, which is a traced
    scalar.  ``k_real`` (traced) restricts the balance cap and the
    least-loaded fallback to the live lanes of a k_max-padded sweep
    step.

    The per-edge recurrence runs as ``kernels.greedy_transform`` on the
    TPU's scalar unit where the call is lowered for a TPU (bit-identical,
    tested), as a ``lax.scan`` elsewhere and for traced ``k_real``."""
    E = src.shape[0]
    src = jnp.asarray(src, jnp.int32)
    dst = jnp.asarray(dst, jnp.int32)
    live = (jnp.ones((E,), jnp.int32) if mask is None
            else jnp.asarray(mask, jnp.int32))
    if lmax is None:
        lmax = (tau * E / float(k) if k_real is None
                else tau * E / k_real.astype(jnp.float32))
    vp = jnp.asarray(vertex_part, jnp.int32)
    deg = jnp.asarray(deg, jnp.int32)
    divided = jnp.asarray(divided, jnp.int32)
    edges = (vp[src], vp[dst], deg[src], deg[dst], divided[src],
             divided[dst], live)
    scan = partial(_transform_scan, k=k, k_real=k_real)
    if k_real is not None:
        return scan(*edges, lmax)
    return jax.lax.platform_dependent(
        *edges, jnp.asarray(lmax, jnp.float32),
        tpu=partial(greedy_transform, k=k, interpret=False), default=scan)


def _transform_scan(*cols, k: int, k_real=None):
    *edges, lmax = cols
    step = partial(_transform_step, lmax=lmax, k=k, k_real=k_real)
    _, assign = jax.lax.scan(step, jnp.zeros((k,), jnp.int32),
                             tuple(edges))
    return assign


# ---------------------------------------------------------------------------
# Restreaming (beyond the paper; Awadelkarim & Ugander's prioritized
# restreaming): re-consume the stream with the *realized* vertex→partition
# majority of the previous pass as the prior.  The transform pass then
# reuses free cuts (divided flags) and reassigns load-aware against fresh
# load counters — each extra pass measurably cuts RF (EXPERIMENTS.md
# §Perf-partitioner).
# ---------------------------------------------------------------------------

def majority_vertex_map_np(src, dst, assign, num_vertices: int,
                           k: int) -> np.ndarray:
    """Per vertex, the partition holding most of its edges in the previous
    pass (ties → lowest partition id, matching jnp.argmax)."""
    key = (np.concatenate([src, dst]).astype(np.int64) * k
           + np.tile(assign, 2))
    cnt = np.bincount(key, minlength=num_vertices * k)
    return cnt.reshape(num_vertices, k).argmax(axis=1).astype(np.int32)


def majority_vertex_map_jax(src, dst, assign, num_vertices: int, k: int,
                            mask=None, axis: str | None = None):
    """jit/shard_map form of ``majority_vertex_map_np``.  Under ``axis``
    each device counts its local slice and the (V, k) tables are psum'd —
    the restream prior is global even though streams stay device-local."""
    src = jnp.asarray(src, jnp.int32)
    dst = jnp.asarray(dst, jnp.int32)
    if mask is not None:
        drop = jnp.int32(num_vertices)
        src = jnp.where(mask, src, drop)
        dst = jnp.where(mask, dst, drop)
    cnt = (jnp.zeros((num_vertices, k), jnp.int32)
           .at[src, assign].add(1, mode="drop")
           .at[dst, assign].add(1, mode="drop"))
    cnt = coll.psum(cnt, axis)
    return jnp.argmax(cnt, axis=1).astype(jnp.int32)
