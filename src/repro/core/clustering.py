"""Pass 1 — streaming clustering (paper Alg. 2).

The *allocation–splitting–migration* framework.  Two interchangeable
implementations with identical semantics (tested against each other):

- ``streaming_clustering_np``  : host fast path (the partitioner runs on the
  host, like the paper's Java pipeline; the stream is inherently sequential).
- ``streaming_clustering_jax`` : ``jax.lax.scan`` over the edge stream with a
  dense carried state — the JAX-native form used under jit and in the
  multi-device pipeline (each distributed node clusters its local stream,
  paper §III-C last paragraph).

State per paper: ``clu[v]`` vertex→cluster, ``deg[v]`` streamed degree,
``vol[c]`` cluster volume (sum of member degrees), ``divided[v]`` mark.
Splitting (lines 9–18) fires when a cluster overflows ``V_max``: the
triggering vertex moves to a fresh cluster, leaving a mirror behind.
Migration (lines 20–26) pulls one endpoint into the larger cluster.

``allow_split=False`` degrades CLUGP to Hollocou et al.'s allocation–
migration (the paper's Holl baseline and the CLUGP-S ablation).
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp

from ..kernels.cluster_scatter import cluster_scatter, edge_decisions


@dataclass
class ClusteringResult:
    clu: np.ndarray            # vertex -> compact cluster id, int32[V]
    deg: np.ndarray            # streamed degree, int32[V]
    divided: np.ndarray        # bool[V], vertex was split at least once
    replicas: np.ndarray       # int32[V], #mirrors created during clustering
    num_clusters: int

    def cluster_rf(self, num_vertices: int) -> float:
        """Replication factor at cluster granularity (Fig. 2 accounting)."""
        active = self.deg > 0
        return float((active.sum() + self.replicas[active].sum())
                     / max(1, active.sum()))


def _compact_labels(raw: np.ndarray) -> tuple[np.ndarray, int]:
    used, inv = np.unique(raw[raw >= 0], return_inverse=True)
    out = np.full(raw.shape[0], -1, dtype=np.int32)
    out[raw >= 0] = inv.astype(np.int32)
    return out, int(used.shape[0])


def streaming_clustering_np(src: np.ndarray, dst: np.ndarray,
                            num_vertices: int, vmax: float,
                            allow_split: bool = True,
                            split_degree_factor: float = 0.0) -> ClusteringResult:
    """``split_degree_factor`` is a beyond-paper damping knob: a split of
    vertex x only fires if ``deg(x) ≥ factor × mean_streamed_degree`` — the
    replica is only paid when the volume drained (deg x) is worth it.  The
    paper-faithful setting is 0 (always split on overflow, Alg. 2 verbatim);
    the optimized profile uses 4 (see EXPERIMENTS.md §Perf-partitioner)."""
    V = num_vertices
    clu = np.full(V, -1, dtype=np.int64)
    deg = np.zeros(V, dtype=np.int64)
    divided = np.zeros(V, dtype=bool)
    replicas = np.zeros(V, dtype=np.int64)
    # worst case ids: one per vertex + one per split (≤ 2 per edge)
    vol = np.zeros(V + 2 * src.shape[0] + 2, dtype=np.int64)
    next_id = 0
    seen_deg = 0
    seen_v = 0

    cl = clu  # local aliases (python-loop hot path)
    dg = deg
    vl = vol
    for i in range(src.shape[0]):
        u = int(src[i]); v = int(dst[i])
        if u == v:
            continue
        cu = cl[u]
        if cu < 0:                       # allocation (lines 3-5)
            cu = next_id; next_id += 1
            cl[u] = cu
            seen_v += 1
        cv = cl[v]
        if cv < 0:
            cv = next_id; next_id += 1
            cl[v] = cv
            seen_v += 1
        dg[u] += 1; dg[v] += 1           # line 6
        vl[cu] += 1; vl[cv] += 1         # line 7
        seen_deg += 2
        if allow_split:
            dthresh = split_degree_factor * seen_deg / seen_v
            if cu == cv:
                # same-cluster overflow: split only the higher-degree
                # endpoint and keep the edge with the lower-degree one
                # (paper §IV-A divided-vertex tie rule) — splitting both
                # would add a replica for nothing.
                if vl[cu] >= vmax:
                    x = u if dg[u] >= dg[v] else v
                    if dg[x] >= dthresh:
                        nc = next_id; next_id += 1
                        cl[x] = nc
                        divided[x] = True
                        replicas[x] += 1
                        vl[cu] -= dg[x]
                        vl[nc] += dg[x]
            else:
                if vl[cu] >= vmax and dg[u] >= dthresh:   # split u (8-13)
                    nc = next_id; next_id += 1
                    cl[u] = nc
                    divided[u] = True
                    replicas[u] += 1
                    vl[cu] -= dg[u]
                    vl[nc] += dg[u]
                cv = cl[v]
                if vl[cv] >= vmax and dg[v] >= dthresh:   # split v (14-18)
                    nc = next_id; next_id += 1
                    cl[v] = nc
                    divided[v] = True
                    replicas[v] += 1
                    vl[cv] -= dg[v]
                    vl[nc] += dg[v]
        cu = cl[u]; cv = cl[v]           # line 19
        if cu != cv and vl[cu] < vmax and vl[cv] < vmax:   # migration 20-26
            # post-guard: a migration must not overflow the target — an
            # over-full cluster would shred its members via later splits.
            if vl[cu] <= vl[cv]:
                if vl[cv] + dg[u] < vmax:
                    cl[u] = cv
                    vl[cu] -= dg[u]; vl[cv] += dg[u]
            else:
                if vl[cu] + dg[v] < vmax:
                    cl[v] = cu
                    vl[cv] -= dg[v]; vl[cu] += dg[v]

    compact, m = _compact_labels(clu)
    return ClusteringResult(compact, deg.astype(np.int32), divided,
                            replicas.astype(np.int32), m)


# ---------------------------------------------------------------------------
# JAX scan version — identical transition function, device-resident.
#
# Engineered around XLA:CPU's copy-insertion for loop-carried buffers: a
# scatter whose indices are *computed* (data-dependent) copies the whole
# buffer every step (and any cross-buffer dependence does too), so a naive
# per-edge scan over (V,)/(id_cap,) state costs a full memcpy per edge
# (measured ~480 µs/edge at scale 13; a register-tracked variant with one
# fused scatter still ~10-15 µs/edge).  The stream is therefore processed
# in BLOCKS of ``block_size`` edges: per block, the ≤2B touched vertices
# and their ≤2B current clusters are gathered into KB-sized local tables
# once (vectorized sort-unique), an inner scan runs the exact per-edge
# transition on local indices (fresh ids get local slots 2B..6B-1 in
# creation order, so global ids stay monotone), and the block's deltas
# scatter back to the global ``clu``/``deg``/``vol`` in one shot — the
# big-buffer copies amortize over B edges.  Split events are emitted as
# scan outputs (→ divided/replicas), so the carried state is just the
# tables, the id counter, and the two streamed-count scalars.
# ---------------------------------------------------------------------------

def _edge_step_local(carry, x, *, vmax: float, allow_split: bool,
                     split_degree_factor: float, B: int):
    """One streamed edge on the block-local tables, all decisions in
    scalar registers (pure fusable arithmetic — XLA:CPU pays a kernel-call
    per gather/scatter inside a loop body, so the step does exactly two
    fused gathers and one fused scatter and keeps everything else
    elementwise).

    ``buf`` layout: [0, 2B) vertex slot → local cluster slot (-1
    unallocated); [2B, 4B) vertex slot → streamed degree; [4B, 10B) local
    cluster volumes (slots 0..2B-1 = clusters present at block start,
    2B..6B-1 = fresh, in creation order so local slot ``2B + (nid -
    nid0)`` ↔ global id ``nid``).  The ≤4 cluster slots an edge can touch
    hold volumes in registers v0..v3; ``pu``/``pv`` point at the register
    of u's/v's current cluster.  Dead edges (self-loops / padding) zero
    every delta and write slots back unchanged."""
    buf, nid, nid0, seen_v, seen_deg = carry
    ints = x
    lu, lv_ = ints[0], ints[1]
    live = ints[2] != 0
    scrap = 6 * B - 1                 # top fresh slot absorbs dead writes

    # one fused gather: both endpoints' cluster slots + streamed degrees
    g = buf[jnp.stack([lu, lv_, 2 * B + lu, 2 * B + lv_])]
    cu0, cv0 = g[0], g[1]
    # second fused gather: the two clusters' volumes
    vg = buf[jnp.stack([4 * B + jnp.clip(cu0, 0, scrap),
                        4 * B + jnp.clip(cv0, 0, scrap)])]
    # the decision math is shared verbatim with the Pallas fused-scatter
    # kernel (kernels.cluster_scatter) — both strategies are bit-identical
    # by construction
    (nid, seen_v, seen_deg, newu, newv, vol_ids, vol_deltas,
     packed) = edge_decisions(
        cu0, cv0, g[2], g[3], vg[0], vg[1], live, nid, nid0, seen_v,
        seen_deg, vmax=vmax, allow_split=allow_split,
        split_degree_factor=split_degree_factor, B=B)

    # end-of-step write: ONE fused 8-lane scatter-add — the two vertex
    # cluster-pointer deltas, the two degree increments, and the ≤4
    # touched volume slots.  Inside a loop body every scatter at computed
    # indices costs XLA:CPU a buffer copy + kernel call (~1.3 µs), so the
    # step does exactly one.
    lvflag = live.astype(jnp.int32)
    ids = jnp.stack([
        lu, lv_,
        2 * B + lu, 2 * B + lv_,
        4 * B + vol_ids[0], 4 * B + vol_ids[1],
        4 * B + vol_ids[2], 4 * B + vol_ids[3]])
    d = jnp.stack([jnp.where(lu != lv_, newu - cu0, 0),
                   newv - cv0,
                   lvflag, lvflag,
                   vol_deltas[0], vol_deltas[1],
                   vol_deltas[2], vol_deltas[3]])
    buf = buf.at[ids].add(d)
    return (buf, nid, nid0, seen_v, seen_deg), packed


_BIG_ID = np.int32(2 ** 31 - 1)


def _block_step(carry, x, *, vmax: float, allow_split: bool,
                split_degree_factor: float, cap: int, num_vertices: int,
                B: int, unroll: int = 1, kernel: str = "xla",
                interpret: bool | None = None):
    """Process one block of B edges: localize → inner scan → write back."""
    clu, deg, vol, nid, seen_v, seen_deg = carry
    bu, bv = x
    scrap = cap - 1

    # local vertex table: dense slots for the ≤2B distinct endpoints
    verts = jnp.concatenate([bu, bv])
    perm = jnp.argsort(verts)
    svert = verts[perm]
    firstv = jnp.concatenate([jnp.ones((1,), bool),
                              svert[1:] != svert[:-1]])
    lidx_sorted = (jnp.cumsum(firstv.astype(jnp.int32)) - 1)
    lv_of_pos = jnp.zeros((2 * B,), jnp.int32).at[perm].set(lidx_sorted)
    uvg = jnp.full((2 * B,), num_vertices, jnp.int32).at[
        lidx_sorted].set(svert)
    lu, lv_ = lv_of_pos[:B], lv_of_pos[B:]

    # local cluster table: dense slots for those vertices' current clusters
    cids = clu[jnp.clip(uvg, 0, num_vertices - 1)]
    validc = (uvg < num_vertices) & (cids >= 0)
    keyc = jnp.where(validc, cids, _BIG_ID)
    ucl = jnp.sort(keyc)
    # local cluster slot of each vertex's current cluster (or -1)
    lc = jnp.where(validc,
                   jnp.searchsorted(ucl, keyc).astype(jnp.int32), -1)
    lvol0 = jnp.where(ucl < _BIG_ID,
                      vol[jnp.clip(ucl, 0, scrap)], 0).astype(jnp.int32)
    ldeg0 = deg[jnp.clip(uvg, 0, num_vertices - 1)]

    # fused local state: [0, 2B) vertex → cluster slot, [2B, 4B) vertex
    # degree, [4B, 10B) cluster volumes
    buf = jnp.concatenate([lc, ldeg0, lvol0,
                           jnp.zeros((4 * B,), jnp.int32)])
    nid0 = nid
    live = (bu != bv).astype(jnp.int32)
    ints = jnp.stack([lu, lv_, live], axis=1)   # one slice per step
    if kernel == "pallas":
        # the whole block table stays resident in kernel memory for the
        # full edge loop — no per-step buffer copies (the XLA scan's
        # ~1.3 µs/scatter floor); off the TPU the interpreter runs the
        # same kernel body for correctness (bit-identical, tested)
        scal0 = jnp.stack([nid, nid0, seen_v, seen_deg])
        buf, scal, fires = cluster_scatter(
            ints, buf, scal0, vmax, allow_split=allow_split,
            split_degree_factor=split_degree_factor, interpret=interpret)
        nid, seen_v, seen_deg = scal[0], scal[2], scal[3]
    else:
        inner = partial(_edge_step_local, vmax=vmax,
                        allow_split=allow_split,
                        split_degree_factor=split_degree_factor, B=B)
        # ``unroll`` replicates the per-edge transition body (2-edge
        # unroll = the ROADMAP headroom knob): XLA sees consecutive edges'
        # fused scatters back to back and can coalesce their buffer
        # traffic.  Pure lowering choice — the transition semantics are
        # bit-identical.
        (buf, nid, _, seen_v, seen_deg), fires = jax.lax.scan(
            inner, (buf, nid, nid0, seen_v, seen_deg), ints, unroll=unroll)
    lclu, ldeg, lvol = buf[:2 * B], buf[2 * B:4 * B], buf[4 * B:]

    # write back: vertex → global cluster id (fresh slots map to the ids
    # they were created under) + degrees, then one fused delta scatter
    # into vol
    glob_of = jnp.concatenate([ucl, nid0 + jnp.arange(4 * B, dtype=jnp.int32)])
    newclu = jnp.where(lclu >= 0,
                       glob_of[jnp.clip(lclu, 0, 6 * B - 1)], -1)
    uvg_safe = jnp.clip(uvg, 0, num_vertices)
    clu = clu.at[uvg_safe].set(newclu, mode="drop")
    deg = deg.at[uvg_safe].set(ldeg, mode="drop")
    dvol = lvol - jnp.concatenate([lvol0, jnp.zeros((4 * B,), jnp.int32)])
    ids = jnp.where(jnp.concatenate([ucl < _BIG_ID,
                                     dvol[2 * B:] != 0]),
                    jnp.clip(glob_of, 0, scrap), scrap)
    vol = vol.at[ids].add(dvol)
    return (clu, deg, vol, nid, seen_v, seen_deg), fires


def streaming_clustering_jax(src, dst, num_vertices: int, vmax: float,
                             allow_split: bool = True,
                             split_degree_factor: float = 0.0,
                             id_cap: int | None = None,
                             block_size: int = 128, unroll: int = 1,
                             kernel: str = "xla",
                             interpret: bool | None = None):
    """Blocked lax.scan form; returns raw (non-compacted) labels + state
    arrays (clu, deg, divided, replicas, next_id) — bit-identical to
    ``streaming_clustering_np``.

    ``id_cap`` bounds the cluster-id space (the global volume table,
    copied once per *block*).  The worst case is ``num_vertices + 2·E +
    2`` (the default); callers that can retry (the partitioner backends)
    pass a tight guess and re-run with a doubled cap iff the returned
    ``next_id`` hits it — an overflowed run clips fresh ids into the
    scrap slot, so its labels are invalid but the overflow is detectable.

    ``unroll`` unrolls the inner per-edge scan by that many edges
    (``CLUGPConfig.unroll``); results are bit-identical at any setting.

    ``kernel`` picks the inner-loop strategy: ``"xla"`` = the lax.scan
    over ``_edge_step_local`` (the fused-scatter scan), ``"pallas"`` = the
    ``kernels.cluster_scatter`` fused table-update kernel (interpret mode
    unless lowered for a TPU; ``interpret`` forces one mode).  Both share
    ``edge_decisions`` so results are bit-identical; ``unroll`` only
    applies to the XLA scan.
    """
    E = src.shape[0]
    cap = int(id_cap) if id_cap is not None else num_vertices + 2 * E + 2
    B = int(block_size)
    src = jnp.asarray(src, jnp.int32)
    dst = jnp.asarray(dst, jnp.int32)
    # pad to whole blocks with dead (self-loop) edges
    nb = max(1, -(-E // B))
    pad = nb * B - E
    def pad_to_blocks(a, fill):
        return jnp.concatenate(
            [a, jnp.full((pad,), fill, a.dtype)]).reshape(nb, B)
    xs = (pad_to_blocks(src, 0), pad_to_blocks(dst, 0))
    carry = (jnp.full((num_vertices,), -1, dtype=jnp.int32),
             jnp.zeros((num_vertices,), dtype=jnp.int32),
             jnp.zeros((cap,), dtype=jnp.int32),
             jnp.int32(0), jnp.int32(0), jnp.int32(0))
    # vmax may be a python float or a traced scalar (the sharded backend
    # derives each device's V_max from its slice's real edge count)
    step = partial(_block_step, vmax=jnp.float32(vmax),
                   allow_split=allow_split,
                   split_degree_factor=float(split_degree_factor),
                   cap=cap, num_vertices=num_vertices, B=B,
                   unroll=int(unroll), kernel=kernel, interpret=interpret)
    (clu, deg, _, next_id, _, _), fires = jax.lax.scan(step, carry, xs)
    fires = fires.reshape(-1)[:E]
    fire_u = (fires & 1) > 0
    fire_v = (fires & 2) > 0
    divided = (jnp.zeros((num_vertices,), bool)
               .at[src].max(fire_u).at[dst].max(fire_v))
    replicas = (jnp.zeros((num_vertices,), jnp.int32)
                .at[src].add(fire_u.astype(jnp.int32))
                .at[dst].add(fire_v.astype(jnp.int32)))
    return clu, deg, divided, replicas, next_id


def compact_labels_jax(clu, cap: int):
    """In-graph equivalent of ``_compact_labels``: raw cluster ids (< cap)
    → dense 0..m-1 ids in ascending raw-id order (the same order
    ``np.unique`` produces, so the jit pipeline's labels are bit-identical
    to the host path's).  Returns (compact int32[V] with -1 preserved, m).
    """
    valid = clu >= 0
    used = jnp.zeros((cap,), jnp.bool_).at[
        jnp.where(valid, clu, cap)].set(True, mode="drop")
    ranks = (jnp.cumsum(used.astype(jnp.int32)) - 1)
    compact = jnp.where(valid, ranks[jnp.clip(clu, 0, cap - 1)], -1)
    return compact.astype(jnp.int32), used.sum().astype(jnp.int32)


def clustering_result_from_jax(clu, deg, divided, replicas) -> ClusteringResult:
    compact, m = _compact_labels(np.asarray(clu))
    return ClusteringResult(compact, np.asarray(deg), np.asarray(divided),
                            np.asarray(replicas), m)


def default_vmax(num_edges: int, k: int) -> float:
    """Paper §VI-A: V_max = |E| / k."""
    return max(2.0, num_edges / float(k))
