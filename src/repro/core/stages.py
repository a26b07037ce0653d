"""The CLUGP pipeline as a stage protocol — ONE parametric body.

The paper's pipeline is three composable passes (§III): streaming
clustering → cluster partitioning (the game) → partition transformation,
plus optional prioritized-restream passes (Awadelkarim & Ugander).  PR 4
gave the pipeline three backends but expressed the pass sequence three
times (`_partition_np_nodes`, `_jit_pipeline`, `_make_sharded_fn`), each
re-plumbing mask/axis/vmax by hand.  This module is the fix the ROADMAP
named: the pass structure is the stable abstraction, so the API exposes
**stages**, not backends.

- ``StageCtx`` carries everything that distinguishes a strategy run:
  the live-edge ``mask`` (sharded padding), the mesh ``axis`` for psum
  hooks (None = local), the per-slice ``vmax`` (float or traced scalar),
  the transform balance-cap override ``lmax``, the resolved game kernel,
  and the static id/m/nnz caps of the device paths.
- ``ClusterStage`` / ``ContractStage`` / ``GameStage`` /
  ``TransformStage`` / ``RestreamLoop`` are the pure, jit-able stage
  callables; a ``StageSet`` bundles one implementation of each.
- ``run_clugp_body(src, dst, ctx, cfg, stages)`` is the ONE pipeline
  body.  ``"np"`` executes it with ``HOST_STAGES`` (the interpreted
  host adapters, kept as the equivalence oracle), ``"jit"`` and
  ``"sharded"`` with ``JAX_STAGES`` — the sharded strategy only differs
  by what it puts in the ctx (mask, ``axis="stream"``, traced vmax,
  per-slice lmax), exactly the way PR 3's ``_gas_body`` unified the GAS
  drivers.

Strategy wrappers (jit entry, shard_map entry, host combine, adaptive
cap retries) live in ``repro.core.partitioner``; the façade over
partition → layout → GAS is ``repro.session.GraphSession``.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Callable, NamedTuple, Protocol

import numpy as np

import jax
import jax.numpy as jnp

from . import metrics
from .clustering import (compact_labels_jax, streaming_clustering_jax,
                         streaming_clustering_np)
from .game import (ClusterGraph, best_response_rounds, contract,
                   greedy_assign, jax_cluster_csr, jax_game_rounds,
                   jax_game_rounds_gs, jax_greedy_assign, lambda_from_weight,
                   lambda_max, pair_keys_fit, raw_cluster_pairs)
from .transform import (majority_vertex_map_jax, majority_vertex_map_np,
                        transform_jax, transform_np)


# ----------------------------------------------------------------- context

@dataclass(frozen=True)
class StageCtx:
    """Per-run stage context: everything the three strategies used to
    re-plumb by hand.  Host runs only need ``num_vertices`` and ``vmax``;
    device runs add the static caps; sharded runs add mask/axis/lmax
    (traced values are fine — the ctx never crosses a jit boundary)."""
    num_vertices: int
    vmax: Any                  # float (host/jit) or traced scalar (sharded)
    mask: Any = None           # live-edge mask; None = every lane is real
    axis: str | None = None    # mesh axis for psum hooks; None = local
    lmax: Any = None           # transform balance-cap override (per slice)
    game_mode: str = "scan"    # resolved kernel: "scan" | "xla" | "pallas"
    id_cap: int = 0            # cluster-id space (jax clustering scan)
    m_cap: int = 0             # compacted-cluster cap (game tables)
    nnz_cap: int = 0           # aggregated cluster-pair lanes (game)
    k_real: Any = None         # traced live-partition count of a k_max-
    #                            padded sweep step; None = cfg.k is real


# ------------------------------------------------------------- stage protocol

class ClusterStage(Protocol):
    """Pass 1: edge stream → clustering state (labels, degrees, marks)."""
    def __call__(self, src, dst, ctx: StageCtx, cfg) -> Any: ...


class ContractStage(Protocol):
    """Streamed graph × labels → cluster-graph state for the game."""
    def __call__(self, src, dst, cstate, ctx: StageCtx, cfg) -> Any: ...


class GameStage(Protocol):
    """Pass 2: cluster graph → (cluster→partition, rounds, pairs)."""
    def __call__(self, gstate, ctx: StageCtx, cfg) -> tuple: ...


class TransformStage(Protocol):
    """Pass 3: stream × vertex→partition prior → edge→partition."""
    def __call__(self, src, dst, vertex_part, cstate, ctx: StageCtx,
                 cfg) -> Any: ...


class RestreamLoop(Protocol):
    """Prioritized restreams over (possibly sliced) streams — the shape of
    ``restream_loop`` below."""
    def __call__(self, src, dst, assign, parts, ctx: StageCtx, cfg,
                 stages) -> tuple: ...


@dataclass(frozen=True)
class StageSet:
    """One implementation of every stage.  ``vertex_part`` joins passes 1
    and 2 (cluster assignment → vertex prior); ``prior`` is the restream
    majority map; ``trace`` (host only) samples RF before each restream
    pass for the ``restream_rf_trace`` stat."""
    cluster: Callable
    contract: Callable
    game: Callable
    vertex_part: Callable
    transform: Callable
    prior: Callable
    trace: Callable | None = None


# ------------------------------------------------------------- stage states

class JaxCluster(NamedTuple):
    compact: Any               # int32[V] dense labels, -1 = never streamed
    deg: Any                   # int32[V] streamed degree
    divided: Any               # bool[V] split at least once
    replicas: Any              # int32[V] mirrors created while clustering
    m: Any                     # traced cluster count (≤ m_cap or overflowed)
    next_id: Any               # traced raw-id high-water mark (cap retry)


class JaxGraph(NamedTuple):
    sizes: Any                 # (m_cap,) game sizes (intra [+ boundary])
    row_tot: Any               # (m_cap,) boundary row totals
    xs: Any                    # cross-edge cluster endpoints (pad: m_cap)
    xd: Any
    n_cross: Any               # traced cross-edge count (λ_max)


class HostGraph(NamedTuple):
    cg: ClusterGraph           # the contraction (result object)
    game_cg: ClusterGraph      # what the game balances (effective sizes)


class PipelineOut(NamedTuple):
    assign: Any
    cluster: Any               # ClusteringResult (host) / JaxCluster (jax)
    graph: Any                 # HostGraph / JaxGraph
    cluster_assign: Any
    rounds: Any
    pairs: Any                 # distinct cluster pairs of the game's list
    #                            (> nnz_cap: overflow; 0: raw list, host)
    trace: tuple               # pre-pass RF per restream (host runs only)


# ----------------------------------------------------------------- the body

def run_clugp_body(src, dst, ctx: StageCtx, cfg, stages: StageSet
                   ) -> PipelineOut:
    """THE pipeline body — the only place the cluster → contract → game →
    transform (→ restream) sequence exists.  Every backend strategy runs
    this exact function; they differ only in the ``stages`` adapters and
    what they put in ``ctx``."""
    # each stage under its own named scope, which the device ops of a
    # profile carry (on the host stages the scopes cost nothing)
    with jax.named_scope("clugp/cluster"):
        cstate = stages.cluster(src, dst, ctx, cfg)
    with jax.named_scope("clugp/contract"):
        gstate = stages.contract(src, dst, cstate, ctx, cfg)
    with jax.named_scope("clugp/game"):
        cluster_assign, rounds, pairs = stages.game(gstate, ctx, cfg)
    with jax.named_scope("clugp/vertex_part"):
        vp = stages.vertex_part(cluster_assign, cstate, ctx)
    with jax.named_scope("clugp/transform"):
        assign = stages.transform(src, dst, vp, cstate, ctx, cfg)
    with jax.named_scope("clugp/restream"):
        assign, trace = restream_loop(src, dst, assign,
                                      [(None, cstate, ctx)], ctx, cfg,
                                      stages)
    return PipelineOut(assign, cstate, gstate, cluster_assign, rounds,
                       pairs, trace)


def restream_loop(src, dst, assign, parts, ctx: StageCtx, cfg,
                  stages: StageSet) -> tuple:
    """The RestreamLoop stage: ``cfg.restream`` prioritized passes — the
    previous pass's realized majority becomes the prior, the transform
    re-runs per stream slice.

    ``parts`` is ``[(sl, cstate, ctx_slice), …]``: one entry covering the
    whole stream (``sl=None`` — the in-body form every backend uses) or
    one per contiguous host-combine slice (``sl`` a python ``slice``; the
    prior then spans all slices while each transform sees only its own —
    the §III-C combine's host twin of the sharded psum'd prior)."""
    trace = []
    for _ in range(int(cfg.restream)):
        if stages.trace is not None:
            trace.append(stages.trace(src, dst, assign, ctx, cfg))
        vp = stages.prior(src, dst, assign, ctx, cfg)
        if len(parts) == 1 and parts[0][0] is None:
            _, cstate, pctx = parts[0]
            assign = stages.transform(src, dst, vp, cstate, pctx, cfg)
        else:
            assign = np.concatenate([
                stages.transform(src[sl], dst[sl], vp, cstate, pctx, cfg)
                for sl, cstate, pctx in parts])
    return assign, tuple(trace)


# ------------------------------------------------------------ host adapters

def _host_cluster(src, dst, ctx, cfg):
    return streaming_clustering_np(
        src, dst, ctx.num_vertices, ctx.vmax, allow_split=cfg.split,
        split_degree_factor=cfg.split_degree_factor)


def _host_contract(src, dst, cstate, ctx, cfg):
    cg = contract(src, dst, cstate.clu)
    game_cg = cg
    if cfg.effective_sizes:
        boundary = np.asarray(cg.adj.sum(axis=1)).ravel()
        game_cg = ClusterGraph(cg.sizes + boundary, cg.adj,
                               cg.vertex_cluster, cg.m)
    return HostGraph(cg, game_cg)


def _host_game(gstate, ctx, cfg):
    if not cfg.game:
        return greedy_assign(gstate.game_cg, cfg.k), 0, 0
    lam = (lambda_max(gstate.game_cg, cfg.k)
           if cfg.relative_weight is None
           else lambda_from_weight(gstate.game_cg, cfg.k,
                                   cfg.relative_weight))
    game = best_response_rounds(gstate.game_cg, cfg.k, lam=lam,
                                batch_size=cfg.batch_size,
                                max_rounds=cfg.max_rounds, seed=cfg.seed)
    return game.assign, game.rounds, 0


def _host_vertex_part(cluster_assign, cstate, ctx):
    return cluster_assign[np.maximum(cstate.clu, 0)].astype(np.int32)


def _host_transform(src, dst, vp, cstate, ctx, cfg):
    return transform_np(src, dst, vp, cstate.deg, cstate.divided,
                        cfg.k, cfg.tau)


def _host_prior(src, dst, assign, ctx, cfg):
    return majority_vertex_map_np(src, dst, assign, ctx.num_vertices, cfg.k)


def _host_trace(src, dst, assign, ctx, cfg):
    return metrics.replication_factor(src, dst, assign, ctx.num_vertices,
                                      cfg.k)


HOST_STAGES = StageSet(cluster=_host_cluster, contract=_host_contract,
                       game=_host_game, vertex_part=_host_vertex_part,
                       transform=_host_transform, prior=_host_prior,
                       trace=_host_trace)


# ------------------------------------------------------------- jax adapters

def resolve_game_mode(kernel: str, m_cap: int) -> str:
    """Resolve the game sweep implementation.  ``scan`` = Gauss–Seidel
    over clusters (the CPU-fast host-exact form), ``pallas`` / ``xla`` =
    batched-Jacobi rounds on the ``game_bestresponse`` kernel / its XLA
    fallback (the MXU-shaped form).  ``auto`` picks pallas on TPU and the
    scan everywhere else.  Every mode plays on the aggregated pair list
    where ``m_cap``'s int32 pair keys fit (~46k clusters, see
    ``game_list``); above that pallas and xla play on the raw cross-edge
    list, and the scan falls back to ``xla``."""
    if kernel not in ("auto", "scan", "pallas", "xla"):
        raise ValueError(f"unknown game kernel {kernel!r}; expected "
                         "'auto', 'scan', 'pallas' or 'xla'")
    mode = kernel
    if kernel == "auto":
        mode = "pallas" if jax.default_backend() == "tpu" else "scan"
    if mode == "scan" and not pair_keys_fit(m_cap):
        return "xla"
    return mode


def game_list(cfg, m_cap: int) -> str:
    """Which list the device game builds its cut-mass tables from, known
    before the body runs: ``"pairs"`` (the aggregated distinct cluster
    pairs, ``jax_cluster_csr``) where ``m_cap``'s pair keys fit int32,
    ``"edges"`` (the raw cross-edge list) above that, ``"none"`` for the
    greedy ablation, which plays no game."""
    if not cfg.game:
        return "none"
    return "pairs" if pair_keys_fit(m_cap) else "edges"


def resolve_cluster_kernel(kernel: str) -> str:
    """Resolve the clustering fused-scatter strategy.  ``xla`` = the
    lax.scan inner loop (one fused 8-lane ``.at[].add`` per edge),
    ``pallas`` = ``kernels.cluster_scatter`` keeping the block table
    resident in kernel memory (bit-identical — both compose
    ``edge_decisions``).  ``auto`` picks pallas on TPU and the XLA scan
    everywhere else (interpret-mode Pallas is a correctness path, not a
    fast path, on CPU)."""
    if kernel not in ("auto", "pallas", "xla"):
        raise ValueError(f"unknown cluster kernel {kernel!r}; expected "
                         "'auto', 'pallas' or 'xla'")
    if kernel == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "xla"
    return kernel


def cluster_graph_arrays(src, dst, compact, m_cap: int, effective: bool,
                         mask=None):
    """Contract the streamed graph against compacted labels, all in-graph:
    per-cluster intra sizes, boundary row totals, and the cross-edge
    cluster endpoints (padded with the drop sentinel ``m_cap``).

    Matches ``contract`` exactly: self-loop edges of clustered vertices
    COUNT toward their cluster's intra size (cs == cd); ``mask`` excludes
    the sharded backend's padding lanes, which are fake self-loops."""
    cs, cd = compact[src], compact[dst]
    ok = (cs >= 0) & (cd >= 0)
    if mask is not None:
        ok = ok & mask
    sent = jnp.int32(m_cap)
    intra = ok & (cs == cd)
    cross = ok & (cs != cd)
    sizes = jnp.zeros((m_cap,), jnp.float32).at[
        jnp.where(intra, cs, sent)].add(1.0, mode="drop")
    xs = jnp.where(cross, cs, sent)
    xd = jnp.where(cross, cd, sent)
    row_tot = (jnp.zeros((m_cap,), jnp.float32)
               .at[xs].add(1.0, mode="drop")
               .at[xd].add(1.0, mode="drop"))
    game_sizes = sizes + row_tot if effective else sizes
    n_cross = cross.sum().astype(jnp.float32)
    return JaxGraph(game_sizes, row_tot, xs, xd, n_cross)


def lambda_jax(total, n_cross, k: int, relative_weight, k_real=None):
    """λ_max (Thm 5) / relative-weight λ from traced cluster-graph totals
    (Σ game sizes, #cross edges) — matches ``lambda_max``/
    ``lambda_from_weight`` (adj.sum()/2 == n_cross).  ``k_real`` (traced)
    substitutes the live partition count of a k_max-padded sweep step."""
    kf = jnp.float32(k) if k_real is None else k_real.astype(jnp.float32)
    lam_max = jnp.where(total > 0,
                        (kf * kf) * n_cross / jnp.maximum(total * total,
                                                          1.0),
                        1.0)
    if relative_weight is None:
        return lam_max
    w = min(max(relative_weight, 1e-3), 1 - 1e-3)
    lam = lam_max * (w / (1 - w))
    return jnp.where((total > 0) & (n_cross > 0), lam, 1.0)


def _jax_cluster(src, dst, ctx, cfg):
    clu_raw, deg, divided, replicas, next_id = streaming_clustering_jax(
        src, dst, ctx.num_vertices, ctx.vmax, allow_split=cfg.split,
        split_degree_factor=cfg.split_degree_factor, id_cap=ctx.id_cap,
        unroll=cfg.unroll,
        kernel=resolve_cluster_kernel(cfg.cluster_kernel))
    compact, m = compact_labels_jax(clu_raw, ctx.id_cap)
    return JaxCluster(compact, deg, divided, replicas, m, next_id)


def _jax_contract(src, dst, cstate, ctx, cfg):
    return cluster_graph_arrays(src, dst, cstate.compact, ctx.m_cap,
                                cfg.effective_sizes, mask=ctx.mask)


def _jax_game(gstate, ctx, cfg):
    if not cfg.game:
        return (jax_greedy_assign(gstate.sizes, cfg.k, k_real=ctx.k_real),
                jnp.int32(0), jnp.int32(0))
    # λ from the LOCAL cluster graph on every strategy: Thm 5's feasible
    # range is a per-id-space quantity (sharded global totals under-weight
    # the balance term by ~n — measured +22% RF at n=4); the load vector
    # the game plays against is still psum'd under ctx.axis.
    lam = lambda_jax(gstate.sizes.sum(), gstate.n_cross, cfg.k,
                     cfg.relative_weight, k_real=ctx.k_real)
    # the Pallas game kernel bakes k into its grid, so traced-k sweep
    # steps play the identical XLA fallback math instead
    mode = ("xla" if ctx.game_mode == "pallas" and ctx.k_real is not None
            else ctx.game_mode)
    # the list is built once; every round's cut-mass tables walk it
    if game_list(cfg, ctx.m_cap) == "pairs":
        row, col, w, pairs = jax_cluster_csr(gstate.xs, gstate.xd,
                                             ctx.m_cap, ctx.nnz_cap)
    else:
        row, col, w = raw_cluster_pairs(gstate.xs, gstate.xd)
        pairs = jnp.int32(0)
    if mode == "scan":
        cluster_assign, rounds = jax_game_rounds_gs(
            row, col, w, gstate.sizes, gstate.row_tot, cfg.k, lam,
            max_rounds=cfg.max_rounds, seed=cfg.seed, axis=ctx.axis,
            k_real=ctx.k_real)
    else:
        cluster_assign, rounds = jax_game_rounds(
            row, col, w, gstate.sizes, gstate.row_tot, cfg.k, lam,
            batch_size=cfg.batch_size, max_rounds=cfg.max_rounds,
            seed=cfg.seed, use_pallas=mode == "pallas",
            axis=ctx.axis, k_real=ctx.k_real)
    return cluster_assign, rounds, pairs


def _jax_vertex_part(cluster_assign, cstate, ctx):
    return cluster_assign[jnp.clip(cstate.compact, 0, ctx.m_cap - 1)]


def _jax_transform(src, dst, vp, cstate, ctx, cfg):
    return transform_jax(src, dst, vp, cstate.deg, cstate.divided, cfg.k,
                         cfg.tau, mask=ctx.mask, lmax=ctx.lmax,
                         k_real=ctx.k_real)


def _jax_prior(src, dst, assign, ctx, cfg):
    return majority_vertex_map_jax(src, dst, assign, ctx.num_vertices,
                                   cfg.k, mask=ctx.mask, axis=ctx.axis)


JAX_STAGES = StageSet(cluster=_jax_cluster, contract=_jax_contract,
                      game=_jax_game, vertex_part=_jax_vertex_part,
                      transform=_jax_transform, prior=_jax_prior)


# -------------------------------------------------------------- serving
# Incremental window assignment + warm restream — the partitioning-as-a-
# service entry points (``repro.serve``).  Window-based streaming
# partitioning (PAPERS.md) absorbs live edge arrivals by assigning a
# buffered window greedily against the loads the resident partition
# already carries; when quality drifts past a watermark, a prioritized
# restream seeded by the current assignment rebuilds it (Awadelkarim &
# Ugander's warm prior, the same ``restream_loop`` every backend runs).

class StreamState(NamedTuple):
    """The duck-typed ``(deg, divided)`` pair the host transform stage
    reads off its cluster state — here derived from a RESIDENT partition
    instead of a clustering pass: ``deg`` is streamed endpoint degree,
    ``divided`` marks vertices already replicated across ≥ 2 partitions
    (cutting them again is free, Alg. 1 lines 17-19)."""
    deg: np.ndarray
    divided: np.ndarray


def stream_state(src, dst, assign, num_vertices: int,
                 k: int) -> StreamState:
    """Derive the transform stage's per-vertex state from an existing
    edge→partition assignment (no re-clustering)."""
    src = np.asarray(src)
    dst = np.asarray(dst)
    assign = np.asarray(assign)
    ends = np.concatenate([src, dst]).astype(np.int64)
    deg = np.bincount(ends, minlength=num_vertices).astype(np.int32)
    cnt = np.bincount(ends * k + np.tile(assign, 2),
                      minlength=num_vertices * k)
    divided = (cnt.reshape(num_vertices, k) > 0).sum(axis=1) > 1
    return StreamState(deg, divided)


def incremental_assign(src, dst, new_src, new_dst, assign,
                       num_vertices: int, cfg, *, state=None,
                       prior=None) -> np.ndarray:
    """Assign a NEW edge window against the resident partition: one
    greedy Alg. 1 pass over the window only, primed with the majority
    vertex map of the current assignment and seeded with the current
    per-partition loads; the balance cap covers the grown stream
    (τ·(E_old+E_new)/k).  Returns the window's edge→partition slice —
    the resident assignment is untouched.  ``state``/``prior`` can be
    passed in to amortize across windows."""
    src = np.asarray(src)
    dst = np.asarray(dst)
    assign = np.asarray(assign)
    if prior is None:
        prior = majority_vertex_map_np(src, dst, assign, num_vertices,
                                       cfg.k)
    if state is None:
        state = stream_state(src, dst, assign, num_vertices, cfg.k)
    loads = np.bincount(assign, minlength=cfg.k).astype(np.int64)
    total = src.shape[0] + np.asarray(new_src).shape[0]
    lmax = cfg.tau * total / float(cfg.k)
    return transform_np(np.asarray(new_src), np.asarray(new_dst), prior,
                        state.deg, state.divided, cfg.k, cfg.tau,
                        loads=loads, lmax=lmax)


def restream_assign(src, dst, assign, num_vertices: int, cfg, *,
                    passes: int = 1, stages: StageSet = HOST_STAGES
                    ) -> tuple:
    """Full prioritized restream seeded by the CURRENT assignment — the
    drift-repair path: ``passes`` extra Alg. 1 passes over the whole
    stream, each primed with the previous pass's realized majority (one
    ``restream_loop`` pass at a time).  MONOTONE: returns the best-RF
    assignment seen, the input included — a repair pass can never leave
    the resident partition worse than the drift it was asked to fix.
    Returns ``(best_assign, rf_trace)`` where ``rf_trace[i]`` is the RF
    before pass ``i`` (entry 0 = the drifted RF)."""
    src = np.asarray(src)
    dst = np.asarray(dst)
    cur = np.asarray(assign)
    st = stream_state(src, dst, cur, num_vertices, cfg.k)
    ctx = StageCtx(num_vertices=num_vertices, vmax=0.0)
    rcfg = dataclasses.replace(cfg, restream=1)

    def rf(a):
        return metrics.replication_factor(src, dst, a, num_vertices,
                                          cfg.k)

    best, best_rf = cur, rf(cur)
    trace = []
    for _ in range(int(passes)):
        trace.append(rf(cur))
        cur, _ = restream_loop(src, dst, cur, [(None, st, ctx)], ctx,
                               rcfg, stages)
        r = rf(cur)
        if r < best_rf:
            best, best_rf = cur, r
    return best, tuple(trace)
