"""Backend-parametric CLUGP partitioner — thin strategies over ONE body.

The paper's §III-C scalability claim is about the *partitioner's own
runtime*: the three passes parallelize across nodes and restreaming
recovers the quality one-pass streaming leaves behind.  The pass sequence
itself lives in ``repro.core.stages.run_clugp_body``; this module holds
the public API and the per-backend strategy wrappers:

    partition(src, dst, num_vertices, cfg, backend=..., nodes=..., mesh=...)

Three backends share one ``CLUGPConfig`` and one ``CLUGPResult``:

- ``"np"``      — the interpreted host path (``HOST_STAGES`` adapters),
                  kept as the equivalence oracle.  With ``nodes > 1`` it
                  is the host reference of the sharded combine: the
                  stream splits into contiguous slices, each slice runs
                  the body in a private cluster-id space, and the
                  per-slice edge assignments concatenate (paper §III-C
                  "combine partial partitioning results").
- ``"jit"``     — single-device fused pipeline: the body under ONE jit
                  with ``JAX_STAGES`` (blocked clustering scan →
                  in-graph contraction → game → transform scan), so the
                  host never touches per-edge state.
- ``"sharded"`` — true §III-C: the SAME body with the SAME ``JAX_STAGES``
                  runs per device inside shard_map over a ``stream`` mesh
                  axis (specs resolved through ``repro.dist.sharding``
                  rule tables); the only difference is the ctx — mask,
                  ``axis="stream"``, traced per-slice vmax, per-slice
                  balance cap.

``cfg.restream`` adds that many prioritized-restream passes on every
backend (Awadelkarim & Ugander).  Measured effect in EXPERIMENTS.md
§Perf-partitioner.  The one-object façade over partition → layout → GAS
is ``repro.session.GraphSession``.
"""
from __future__ import annotations

import dataclasses
import itertools
from functools import lru_cache, partial
from typing import NamedTuple

import numpy as np

import jax
import jax.numpy as jnp

from .clustering import ClusteringResult, default_vmax
from .game import contract
from .pipeline import CLUGPConfig, CLUGPResult
from .stages import (HOST_STAGES, JAX_STAGES, StageCtx, game_list,
                     resolve_game_mode, restream_loop, run_clugp_body)
from . import metrics
from .. import obs

BACKENDS = ("np", "jit", "sharded")
_BLOCK = 256          # game-kernel block: m_cap pads to a multiple of this


def _check_stream(src: np.ndarray) -> None:
    if src.shape[0] == 0:
        raise ValueError(
            "partition: the edge stream is empty (0 edges); there is "
            "nothing to partition")


def _pad_to(n: int, mult: int) -> int:
    return -(-max(n, 1) // mult) * mult


def partition(src: np.ndarray, dst: np.ndarray, num_vertices: int,
              cfg: CLUGPConfig, *, backend: str = "np", nodes: int = 1,
              mesh=None) -> CLUGPResult:
    """Run the CLUGP pipeline on the chosen backend.

    ``nodes`` is the §III-C stream-split width (np reference combine /
    sharded mesh size).  ``mesh`` overrides the sharded backend's mesh
    (must carry a ``stream`` axis); otherwise one is built over
    ``nodes`` devices."""
    if backend not in BACKENDS:
        raise ValueError(
            f"unknown backend {backend!r}; expected one of {BACKENDS}")
    _check_stream(src)
    with obs.span("partition", backend=backend):
        if backend == "np":
            if nodes <= 1:
                return _run_np(src, dst, num_vertices, cfg)
            return _run_np_nodes(src, dst, num_vertices, cfg, nodes)
        if backend == "jit":
            return _run_jit(src, dst, num_vertices, cfg)
        return _run_sharded(src, dst, num_vertices, cfg, nodes, mesh)


# ------------------------------------------------------------- np strategy

def _resolve_vmax(cfg: CLUGPConfig, num_edges: int) -> float:
    """The §VI-A default cap over the edges the strategy actually
    streams — the slice count for host-combine nodes, |E| otherwise (the
    sharded node_fn derives the same rule from its traced mask count)."""
    return cfg.vmax if cfg.vmax is not None else default_vmax(num_edges,
                                                              cfg.k)


def _host_ctx(num_vertices: int, num_edges: int, cfg: CLUGPConfig
              ) -> StageCtx:
    return StageCtx(num_vertices=num_vertices,
                    vmax=_resolve_vmax(cfg, num_edges))


def _run_np(src: np.ndarray, dst: np.ndarray, num_vertices: int,
            cfg: CLUGPConfig) -> CLUGPResult:
    ctx = _host_ctx(num_vertices, src.shape[0], cfg)
    out = run_clugp_body(src, dst, ctx, cfg, HOST_STAGES)
    res = CLUGPResult(out.assign, out.cluster, out.graph.cg,
                      out.cluster_assign, out.rounds)
    res.stats = metrics.summarize(src, dst, out.assign, num_vertices, cfg.k)
    res.stats["num_clusters"] = out.cluster.num_clusters
    res.stats["game_rounds"] = out.rounds
    res.stats["backend"] = "np"
    if cfg.restream:
        trace = list(out.trace) + [res.stats["rf"]]
        res.stats["restream_rf_trace"] = [round(r, 4) for r in trace]
    return res


def _run_np_nodes(src: np.ndarray, dst: np.ndarray, num_vertices: int,
                  cfg: CLUGPConfig, nodes: int) -> CLUGPResult:
    """Host reference of the sharded combine: contiguous ceil(E/n) slices
    (the same chunking shard_map uses), private id spaces per node,
    concatenated edge assignments, then *global* restream passes whose
    majority prior spans all slices (the psum'd table's host twin).

    The merged result is explicit about what it is: per-node clustering /
    cluster-graph objects are not stitched into one fake global object —
    ``clustering``/``cluster_graph``/``cluster_assign`` are None and
    ``stats["per_node"]`` carries each node's private-space summary."""
    E = src.shape[0]
    e_per = -(-E // nodes)
    sub_cfg = dataclasses.replace(cfg, restream=0)
    parts, per_node, pieces = [], [], []
    rounds = 0
    clusters = 0
    for i in range(nodes):
        lo, hi = i * e_per, min(E, (i + 1) * e_per)
        if hi <= lo:
            continue
        ctx = _host_ctx(num_vertices, hi - lo, sub_cfg)
        out = run_clugp_body(src[lo:hi], dst[lo:hi], ctx, sub_cfg,
                             HOST_STAGES)
        pieces.append(out.assign)
        rounds = max(rounds, out.rounds)
        clusters += out.cluster.num_clusters
        per_node.append({"node": i, "edges": int(hi - lo),
                         "clusters": out.cluster.num_clusters,
                         "game_rounds": out.rounds})
        parts.append((slice(lo, hi), out.cluster, ctx))
    assign = np.concatenate(pieces)
    gctx = StageCtx(num_vertices=num_vertices, vmax=None)
    assign, trace = restream_loop(src, dst, assign, parts, gctx, cfg,
                                  HOST_STAGES)
    res = CLUGPResult(assign, None, None, None, rounds)
    res.stats = metrics.summarize(src, dst, assign, num_vertices, cfg.k)
    res.stats["num_clusters"] = clusters   # sum over private id spaces
    res.stats["game_rounds"] = rounds
    res.stats["backend"] = "np"
    res.stats["nodes"] = nodes
    res.stats["per_node"] = per_node
    if cfg.restream:
        res.stats["restream_rf_trace"] = [
            round(r, 4) for r in list(trace) + [res.stats["rf"]]]
    return res


# ----------------------------------------------------------- adaptive caps

class Caps(NamedTuple):
    id_cap: int
    m_cap: int
    nnz_cap: int


def _id_cap_guess(num_vertices: int, num_edges: int) -> int:
    """Initial cluster-id-space guess: ids = allocations (≤ V) + splits
    (usually a fraction of V).  The pipeline re-runs with a doubled cap
    iff the returned next_id hits it — the table is copied per scan block,
    so a tight cap is worth the rare retry."""
    return _pad_to(min(2 * num_vertices + 2048,
                       num_vertices + 2 * num_edges + 2), 1024)


def _m_cap_guess(num_vertices: int) -> int:
    """Initial compacted-cluster-count guess: real streams end with
    m ≪ V (clusters ≈ V_max-sized communities), and the game's per-round
    cost is O(m_cap·k), so guess small and retry on overflow."""
    return _pad_to(min(num_vertices, max(_BLOCK, num_vertices // 4)),
                   _BLOCK)


def _init_caps(num_vertices: int, e_per: int) -> Caps:
    m_cap = _m_cap_guess(num_vertices)
    return Caps(_id_cap_guess(num_vertices, e_per), m_cap, 8 * m_cap)


def _grow_caps(caps: Caps, *, next_id: int, m: int, overflow: bool,
               num_vertices: int, e_per: int) -> tuple:
    """One retry step of the adaptive caps shared by the device
    strategies: double whichever cap the run overflowed (bounded by its
    worst case) and report whether the run was already clean."""
    id_cap, m_cap, nnz_cap = caps
    ok = True
    if next_id > id_cap - 2:
        id_cap = min(2 * id_cap, num_vertices + 2 * e_per + 2)
        ok = False
    if m > m_cap:
        m_cap = min(2 * m_cap, _pad_to(num_vertices, _BLOCK))
        ok = False
    if overflow:
        nnz_cap = min(2 * nnz_cap, m_cap * m_cap)
        ok = False
    return Caps(id_cap, m_cap, nnz_cap), ok


# ------------------------------------------------------------ jit strategy

@partial(jax.jit, static_argnames=("num_vertices", "cfg", "vmax",
                                   "game_mode", "id_cap", "m_cap",
                                   "nnz_cap"))
def _jit_body(src, dst, *, num_vertices: int, cfg: CLUGPConfig, vmax: float,
              game_mode: str, id_cap: int, m_cap: int, nnz_cap: int):
    """The whole stage body (+ restreams) under one jit — the host sees
    only the final arrays, never per-edge state."""
    ctx = StageCtx(num_vertices=num_vertices, vmax=vmax,
                   game_mode=game_mode, id_cap=id_cap, m_cap=m_cap,
                   nnz_cap=nnz_cap)
    out = run_clugp_body(src, dst, ctx, cfg, JAX_STAGES)
    return (out.assign, out.cluster.compact, out.cluster.deg,
            out.cluster.divided, out.cluster.replicas, out.cluster.m,
            out.rounds, out.cluster_assign, out.pairs,
            out.cluster.next_id)


def _run_jit(src: np.ndarray, dst: np.ndarray, num_vertices: int,
             cfg: CLUGPConfig) -> CLUGPResult:
    E = src.shape[0]
    vmax = _resolve_vmax(cfg, E)
    caps = _init_caps(num_vertices, E)
    for attempt in itertools.count():
        # a span per run of the body: it ends where the caps are read
        # back, which waits for the device
        with obs.span("partition.attempt", attempt=attempt,
                      game_list=game_list(cfg, caps.m_cap),
                      **caps._asdict()):
            out = _jit_body(
                jnp.asarray(src, jnp.int32), jnp.asarray(dst, jnp.int32),
                num_vertices=num_vertices, cfg=cfg, vmax=float(vmax),
                game_mode=resolve_game_mode(cfg.kernel, caps.m_cap),
                id_cap=caps.id_cap, m_cap=caps.m_cap, nnz_cap=caps.nnz_cap)
            pairs = int(out[-2])
            caps, ok = _grow_caps(caps, next_id=int(out[-1]), m=int(out[5]),
                                  overflow=pairs > caps.nnz_cap,
                                  num_vertices=num_vertices, e_per=E)
        if ok:
            break
    with obs.span("partition.fetch"):
        (assign, compact, deg, divided, replicas, m, rounds,
         cluster_assign) = (np.asarray(x) for x in out[:-2])
    m = int(m)
    rounds = int(rounds)
    clus = ClusteringResult(compact, deg, divided, replicas, m)
    with obs.span("partition.contract"):
        cg = contract(src, dst, compact)
    res = CLUGPResult(assign, clus, cg, cluster_assign[:m], rounds)
    with obs.span("partition.summary"):
        res.stats = metrics.summarize(src, dst, assign, num_vertices, cfg.k)
    res.stats["num_clusters"] = m
    res.stats["game_rounds"] = rounds
    res.stats["game_list"] = game_list(cfg, caps.m_cap)
    res.stats["game_pairs"] = pairs
    res.stats["backend"] = "jit"
    return res


# ------------------------------------------------------- compile-once sweep

_SWEEP_TRACES = {"count": 0}


def sweep_trace_count() -> int:
    """How many times the stacked sweep body has been traced (== jit
    compiles) in this process — the bench/CI compile-once assertion."""
    return _SWEEP_TRACES["count"]


@partial(jax.jit, static_argnames=("num_vertices", "cfg", "game_mode",
                                   "id_cap", "m_cap", "nnz_cap"))
def _jit_sweep_body(src, dst, ks, vmaxs, *, num_vertices: int,
                    cfg: CLUGPConfig, game_mode: str, id_cap: int,
                    m_cap: int, nnz_cap: int):
    """A whole k-sweep under ONE jit: ``lax.scan`` stacks N homogeneous
    stage bodies, every lane-carrying table padded to ``cfg.k == k_max``
    while the traced per-step ``k_real`` masks the live partitions
    (argmin/cost lanes past it cost 3e38, λ and the balance cap use the
    real count).  Sweeping k therefore compiles once instead of once per
    k — the static args no longer include k itself."""
    _SWEEP_TRACES["count"] += 1

    def body(carry, per_k):
        k_real, vmax = per_k
        ctx = StageCtx(num_vertices=num_vertices, vmax=vmax,
                       game_mode=game_mode, id_cap=id_cap, m_cap=m_cap,
                       nnz_cap=nnz_cap, k_real=k_real)
        out = run_clugp_body(src, dst, ctx, cfg, JAX_STAGES)
        return carry, (out.assign, out.cluster.m, out.rounds,
                       out.pairs, out.cluster.next_id)

    _, outs = jax.lax.scan(body, 0, (ks, vmaxs))
    return outs


def partition_sweep(src: np.ndarray, dst: np.ndarray, num_vertices: int,
                    cfg: CLUGPConfig, ks) -> list:
    """Run the jit pipeline at every ``k`` in ``ks`` under one compiled
    body (``_jit_sweep_body``) and return one ``CLUGPResult`` per k, in
    input order.  Repeat sweeps over same-shaped streams reuse the cached
    executable whatever the k values are — ``sweep_trace_count()`` exposes
    the compile count.  The adaptive caps retry the WHOLE sweep (caps are
    k-independent, so one clean set serves every step)."""
    _check_stream(src)
    ks = tuple(int(k) for k in ks)
    if not ks or min(ks) < 1:
        raise ValueError(f"partition_sweep: need at least one k >= 1, "
                         f"got {ks!r}")
    k_max = max(ks)
    sweep_cfg = dataclasses.replace(cfg, k=k_max)
    E = src.shape[0]
    vmaxs = np.array([_resolve_vmax(dataclasses.replace(cfg, k=k), E)
                      for k in ks], np.float32)
    ks_arr = np.array(ks, np.int32)
    caps = _init_caps(num_vertices, E)
    while True:
        assigns, ms, rounds, pairs, next_ids = _jit_sweep_body(
            jnp.asarray(src, jnp.int32), jnp.asarray(dst, jnp.int32),
            jnp.asarray(ks_arr), jnp.asarray(vmaxs),
            num_vertices=num_vertices, cfg=sweep_cfg,
            game_mode=resolve_game_mode(cfg.kernel, caps.m_cap),
            id_cap=caps.id_cap, m_cap=caps.m_cap, nnz_cap=caps.nnz_cap)
        pairs = np.asarray(pairs)
        caps, ok = _grow_caps(caps, next_id=int(np.asarray(next_ids).max()),
                              m=int(np.asarray(ms).max()),
                              overflow=int(pairs.max()) > caps.nnz_cap,
                              num_vertices=num_vertices, e_per=E)
        if ok:
            break
    results = []
    for i, k in enumerate(ks):
        assign = np.asarray(assigns[i])
        res = CLUGPResult(assign, None, None, None, int(rounds[i]))
        res.stats = metrics.summarize(src, dst, assign, num_vertices, k)
        res.stats["num_clusters"] = int(ms[i])
        res.stats["game_rounds"] = int(rounds[i])
        res.stats["game_list"] = game_list(sweep_cfg, caps.m_cap)
        res.stats["game_pairs"] = int(pairs[i])
        res.stats["backend"] = "jit"
        res.stats["sweep"] = True
        res.stats["k_max"] = k_max
        results.append(res)
    return results


# ----------------------------------------------------------- sharded backend

def _stream_spec(mesh, shape: tuple):
    """Resolve the edge-stream PartitionSpec through the dist.sharding
    rule table (the partitioner never names mesh axes directly)."""
    from ..dist.sharding import PARTITIONER_RULES, resolve_spec
    return resolve_spec(shape, ("stream",), PARTITIONER_RULES,
                        dict(mesh.shape))


@lru_cache(maxsize=32)
def _make_sharded_fn(mesh, e_per: int, num_vertices: int,
                     cfg: CLUGPConfig, game_mode: str, id_cap: int,
                     m_cap: int, nnz_cap: int):
    """Build (and cache, keyed by mesh + the frozen cfg + caps) the jitted
    shard_map pipeline: one stream slice per device along the ``stream``
    axis, each running the SAME stage body as the jit strategy — only the
    ctx differs."""
    n = mesh.shape["stream"]
    spec = _stream_spec(mesh, (n * e_per,))

    def node_fn(src_b, dst_b, mask_b):
        # padded lanes become self-loops: the clustering scan freezes on
        # them and the transform scan skips them via the mask
        s = jnp.where(mask_b, src_b, 0).astype(jnp.int32)
        d = jnp.where(mask_b, dst_b, 0).astype(jnp.int32)
        e_real = mask_b.sum().astype(jnp.float32)
        # V_max from the slice's REAL edge count — each node derives its
        # own cap from its sub-stream, exactly like the np combine (a
        # global-|E| cap grows node-local clusters 4× too fat at n=4 and
        # costs ~40% RF)
        vmax = (jnp.maximum(2.0, e_real / cfg.k) if cfg.vmax is None
                else jnp.float32(cfg.vmax))
        ctx = StageCtx(num_vertices=num_vertices, vmax=vmax, mask=mask_b,
                       axis="stream",
                       # per-slice balance cap (§III-C)
                       lmax=cfg.tau * e_real / cfg.k,
                       game_mode=game_mode, id_cap=id_cap, m_cap=m_cap,
                       nnz_cap=nnz_cap)
        out = run_clugp_body(s, d, ctx, cfg, JAX_STAGES)
        return (out.assign, out.cluster.m[None], out.rounds[None],
                out.cluster.next_id[None], out.pairs[None])

    # check_vma=False: the stage body is the one the single-device jit
    # strategy runs, and its scan/while carries start from constants
    # (empty tables, zero counters) that turn per-device on the first
    # step.  Under the check every such carry would need a pcast to
    # 'varying' inside code that also runs outside shard_map.  Every
    # output is per-slice (out_specs all shard), so nothing relies on
    # the check to prove an output replicated.
    mapped = jax.shard_map(node_fn, mesh=mesh, in_specs=(spec, spec, spec),
                           out_specs=(spec, spec, spec, spec, spec),
                           check_vma=False)
    return jax.jit(mapped)


def _run_sharded(src: np.ndarray, dst: np.ndarray, num_vertices: int,
                 cfg: CLUGPConfig, nodes: int, mesh) -> CLUGPResult:
    E = src.shape[0]
    if mesh is None:
        if jax.device_count() < nodes:
            raise RuntimeError(
                f"sharded backend needs {nodes} devices but only "
                f"{jax.device_count()} are visible; set XLA_FLAGS="
                f"--xla_force_host_platform_device_count={nodes} before "
                f"the first jax import (launch.partition does this for "
                f"--backend sharded)")
        from ..launch.mesh import make_stream_mesh
        mesh = make_stream_mesh(nodes)
    n = int(mesh.shape["stream"])
    e_per = -(-E // n)
    e_pad = e_per * n
    src_p = np.zeros(e_pad, dtype=np.int32)
    dst_p = np.zeros(e_pad, dtype=np.int32)
    mask = np.zeros(e_pad, dtype=bool)
    src_p[:E], dst_p[:E], mask[:E] = src, dst, True
    caps = _init_caps(num_vertices, e_per)
    for attempt in itertools.count():
        with obs.span("partition.attempt", attempt=attempt,
                      game_list=game_list(cfg, caps.m_cap),
                      **caps._asdict()):
            run = _make_sharded_fn(
                mesh, e_per, num_vertices, cfg,
                resolve_game_mode(cfg.kernel, caps.m_cap),
                caps.id_cap, caps.m_cap, caps.nnz_cap)
            with mesh:
                assign_p, m_locals, rounds_arr, next_ids, pairs = run(
                    jnp.asarray(src_p), jnp.asarray(dst_p),
                    jnp.asarray(mask))
            pairs = np.asarray(pairs)
            caps, ok = _grow_caps(
                caps, next_id=int(np.asarray(next_ids).max()),
                m=int(np.asarray(m_locals).max()),
                overflow=int(pairs.max()) > caps.nnz_cap,
                num_vertices=num_vertices, e_per=e_per)
        if ok:
            break
    with obs.span("partition.fetch"):
        assign = np.asarray(assign_p)[:E]
        m_locals = np.asarray(m_locals)
        rounds = int(np.asarray(rounds_arr).max())
    res = CLUGPResult(assign, None, None, None, rounds)
    with obs.span("partition.summary"):
        res.stats = metrics.summarize(src, dst, assign, num_vertices, cfg.k)
    res.stats["num_clusters"] = int(m_locals.sum())
    res.stats["game_rounds"] = rounds
    res.stats["game_list"] = game_list(cfg, caps.m_cap)
    res.stats["game_pairs"] = int(pairs.sum())   # over private id spaces
    res.stats["backend"] = "sharded"
    res.stats["nodes"] = n
    res.stats["per_node"] = [
        {"node": i, "clusters": int(c)} for i, c in enumerate(m_locals)]
    return res
