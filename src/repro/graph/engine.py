"""Distributed vertex-cut GAS engine (PowerGraph semantics) on shard_map.

Per iteration (paper §II-B): local gather/combine over the partition's
edges, mirror partials reduced to masters, masters apply, new values
broadcast back to mirrors.  The local combine runs over each partition's
edges sorted by destination slot as a segmented scan (``_edge_reduce``):
the sorted views are built once per run, before the loop, so no iteration
scatters per edge.  The two
mirror-sync phases go through the pluggable exchange layer
(``repro.dist.halo``):

- ``exchange="dense"``: two all_gathers of (k, L_max) values — simple, but
  bytes scale with k²·L_max regardless of partition quality (the seed wire
  format).
- ``exchange="halo"``: two all_to_alls over the layout's static mirror
  routing tables — bytes scale with the mirror count (RF−1)·|V|, the
  quantity the partitioner optimizes, so Fig. 8's mechanism shows up on
  the wire.
- ``exchange="quantized"``: halo routing with int8 delta-coded lanes +
  per-lane-group scales and an error-feedback residual threaded through
  the iteration carry — ~4× fewer payload bytes for fp32 programs, exact
  int32 passthrough for ``combine="min"`` programs (CC labels).
- ``exchange="ragged"`` / ``"ragged_quantized"``: the all_to_all's
  cross-pair H_max padding replaced by k−1 ppermute ring hops, each
  padded only to its own distance's lane population (the layout's
  ``halo_schedule()``, baked into the exchange instance as a static
  tuple — which is why the jitted drivers below key their caches on the
  exchange *instance*, not its name).  The quantized variant ships only
  the top-Δ largest error-feedback deltas per hop (int16 index + int8
  code pairs).

The engine is **program-parametric**: a ``GASProgram`` bundles the four
per-device callables (init / local gather-scatter / apply / optional
global aux) plus the combine op and wire dtype, and one pair of drivers
runs any program:

- ``simulate_gas(program, …)``   : stacked (k, …) arrays on one device —
                                   tests and host-side benchmarks.
- ``shard_map_gas(program, …)``  : k/D partitions on each of the D mesh
                                   devices over axis ``parts``, compiled
                                   once per shape — the production path.

``simulate_pagerank`` / ``shard_map_pagerank`` / ``simulate_cc`` /
``shard_map_cc`` are thin instantiations of ``pagerank_program()`` /
``CC_PROGRAM`` over those two drivers, so the simulated and shard_map
paths run the same per-device math by construction and can't drift.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import lru_cache, partial
from typing import Any, Callable

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .partition import PartitionLayout
from .. import obs
from ..dist import collectives as coll
from ..dist.halo import (RAGGED_EXCHANGES, _pad_value, get_exchange,
                         lossy_payload)

DAMPING = 0.85
# CC labels are int32 vertex ids; the min-identity sentinel marks padded /
# non-master slots and can never win a minimum against a real id
CC_SENTINEL = int(np.iinfo(np.int32).max)


# ----------------------------------------------------------- program spec

@dataclass(frozen=True)
class GASProgram:
    """One GAS computation as per-device callables over the layout's
    ``device_arrays()`` pytree (all (L_max,)-shaped per device):

      init(dev)               -> initial per-slot values
      local(value, dev)       -> gather/scatter partials over local edges
      apply(total, aux, dev)  -> new master-slot values (others get the
                                 combine identity / sentinel)
      aux(value, dev)         -> optional per-device scalar, reduced
                                 globally (psum / stacked sum) before
                                 ``apply`` — pagerank's dangling mass

    ``combine`` ("sum" | "min") and ``dtype`` fix the mirror-sync wire
    semantics; the quantized exchange uses them to decide whether the
    payload may be lossily delta-coded (fp32 sum) or must ship exact
    (int32 min).  ``edges`` names the sorted edge view ``local`` reads:
    "in" the directed edges by destination, "both" each edge in both
    directions (undirected programs)."""
    name: str
    combine: str
    dtype: Any
    init: Callable
    local: Callable
    apply: Callable
    aux: Callable | None = None
    edges: str = "in"


# ----------------------------------------------------------- sorted edge views
#
# ``local`` combines, per local slot, one value from each edge that targets
# it.  Each view holds a partition's edge lanes sorted by target slot,
# built once per run before the loop (``_with_edge_views``), so the
# combine is a segmented scan over contiguous runs of equal targets and
# one gather at each slot's last lane: dense vector work, with no
# read-modify-write per lane.  Pad and masked lanes target slot L_max: they
# sort last, into a run that no slot reads.


def _view_edges(kind: str, dev):
    """(target, source, mask) lanes of one partition's view ``kind``."""
    s, d, m = dev["edge_src"], dev["edge_dst"], dev["edge_mask"]
    if kind == "in":
        return d, s, m
    return (jnp.concatenate([d, s]), jnp.concatenate([s, d]),
            jnp.concatenate([m, m]))


def _edge_view(kind: str, dev) -> dict:
    """One partition's edges of view ``kind`` sorted by target slot:
    ``tgt``/``src`` per lane, and per local slot the lane that ends its
    run (``last``) and whether it has one (``has``).

    Two sorts and no gather or scatter: the first orders the edge lanes
    together with one marker per slot, each marker right after its
    slot's edges; the second moves the markers behind the edges, in slot
    order, keeping their positions — slot v's edges end before the
    position of its marker less v."""
    l_max = dev["vert_gid"].shape[0]
    tgt, src, mask = _view_edges(kind, dev)
    n = tgt.shape[0]
    slots = jnp.arange(l_max, dtype=jnp.int32)
    # masked lanes join the pad lanes' run (target L_MAX), after every marker
    key = jnp.concatenate([2 * jnp.where(mask, tgt, l_max), 2 * slots + 1])
    key, src = jax.lax.sort(
        (key, jnp.concatenate([src, jnp.zeros_like(slots)])),
        num_keys=1, is_stable=True)
    pos = jnp.arange(n + l_max, dtype=jnp.int32)
    split, tgt, src = jax.lax.sort(
        (jnp.where((key & 1) == 1, n + l_max + pos, pos), key >> 1, src),
        num_keys=1)
    ends = split[n:] - (n + l_max) - slots       # lanes with target <= v
    starts = jnp.concatenate([jnp.zeros((1,), ends.dtype), ends[:-1]])
    return {"tgt": tgt[:n], "src": src[:n],
            "last": jnp.maximum(ends - 1, 0), "has": ends > starts}


def _with_edge_views(programs, dev, batched: bool = True) -> dict:
    """``dev`` plus the sorted views (``view_<kind>``) that the programs'
    local phases read; ``batched`` vmaps the build over the leading
    partition axis."""
    kinds = sorted({p.edges for p in programs})

    def build(d):
        return {"view_" + kind: _edge_view(kind, d) for kind in kinds}

    return {**dev, **(jax.vmap(build)(dev) if batched else build(dev))}


def _sorted_lanes(programs, layout: PartitionLayout, parts: int) -> int:
    """Edge lanes that the local phases of one iteration reduce by the
    segmented scan on one device, summed over its ``parts`` partitions:
    the ``sorted_lanes`` attribute of ``gas.run``."""
    e_max = layout.edge_src.shape[1]
    return parts * sum(e_max * (2 if p.edges == "both" else 1)
                       for p in programs)


def _segmented_scan(vals, keys, op):
    """Inclusive scan of ``op`` over runs of equal ``keys`` (sorted):
    log2(n) doubling steps, each a shifted, masked combine."""
    n = vals.shape[0]
    step = 1
    while step < n:
        prev_v = jnp.concatenate([vals[:step], vals[:-step]])
        prev_k = jnp.concatenate([jnp.full((step,), -1, keys.dtype),
                                  keys[:-step]])
        vals = jnp.where(prev_k == keys, op(vals, prev_v), vals)
        step *= 2
    return vals


def _edge_reduce(per_edge, view, combine: str):
    """``combine`` of ``per_edge`` (lanes in the order of ``view``) per
    local slot: (L_max,), the combine identity where no edge targets a
    slot — what ``segment_sum``/``segment_min`` over the unsorted edges
    gives, up to the order of a float sum.  Pad lanes may hold anything:
    no slot reads their run."""
    op = jnp.add if combine == "sum" else jnp.minimum
    vals = _segmented_scan(per_edge, view["tgt"], op)
    return jnp.where(view["has"], vals[view["last"]],
                     _pad_value(combine, per_edge.dtype))


# ----------------------------------------------------------- per-device math

def _local_rank_partial(rank, dev):
    """Σ_{(u,w)∈E_p, w=v} rank[u]/outdeg[u] per local slot."""
    safe_deg = jnp.maximum(dev["out_deg"], 1)
    contrib = jnp.where(dev["vert_mask"] & (dev["out_deg"] > 0),
                        rank / safe_deg, 0.0)
    contrib = jnp.concatenate([contrib, jnp.zeros((1,), contrib.dtype)])
    view = dev["view_in"]
    return _edge_reduce(contrib[view["src"]], view, "sum")


def _local_dangle(rank, dev):
    """Rank mass sitting on dangling masters (out_deg == 0)."""
    m = dev["vert_mask"] & dev["is_master"] & (dev["out_deg"] == 0)
    return jnp.sum(jnp.where(m, rank, 0.0))


def _pagerank_apply(total_in, dangle, dev, num_vertices):
    base = (1.0 - DAMPING) / num_vertices
    new = base + DAMPING * (total_in + dangle / num_vertices)
    return jnp.where(dev["vert_mask"] & dev["is_master"], new, 0.0)


@lru_cache(maxsize=None)
def pagerank_program(num_vertices: int) -> GASProgram:
    """Damped pagerank with dangling-mass redistribution (fp32, sum
    combine — the quantized exchange may delta-code its mirror lanes).
    Cached per vertex count so repeated layouts hit the same jit cache."""
    def init(dev):
        return jnp.where(dev["vert_mask"], 1.0 / num_vertices, 0.0)

    def apply(total, dangle, dev):
        return _pagerank_apply(total, dangle, dev, num_vertices)

    return GASProgram(name="pagerank", combine="sum", dtype=jnp.float32,
                      init=init, local=_local_rank_partial, apply=apply,
                      aux=_local_dangle)


def _cc_init(dev):
    return jnp.where(dev["vert_mask"], dev["vert_gid"].astype(jnp.int32),
                     CC_SENTINEL)


def _cc_local_min(label, dev):
    """Edge-wise min exchange in both directions (undirected semantics),
    one segmented min over the undirected view."""
    lab = jnp.concatenate([jnp.where(dev["vert_mask"], label, CC_SENTINEL),
                           jnp.full((1,), CC_SENTINEL, label.dtype)])
    view = dev["view_both"]
    out = _edge_reduce(lab[view["src"]], view, "min")
    cur = jnp.where(dev["vert_mask"], label, CC_SENTINEL)
    return jnp.minimum(cur, out)


def _cc_apply(total, aux, dev):
    return jnp.where(dev["vert_mask"] & dev["is_master"], total,
                     CC_SENTINEL)


# label propagation / connected components: int32 labels are exact on the
# wire, so every exchange (incl. "quantized") ships them unquantized
CC_PROGRAM = GASProgram(name="cc", combine="min", dtype=jnp.int32,
                        init=_cc_init, local=_cc_local_min, apply=_cc_apply,
                        edges="both")


# ------------------------------------------------------- program library
#
# The engine's whole point is program-parametric multi-tenant analytics:
# each program below is a thin GASProgram instantiation with a NumPy
# ``reference_*`` oracle, spanning every wire-semantics cell the exchange
# layer distinguishes — (sum, f32) lossy delta-coded payloads with error
# feedback (pagerank / ppr / centrality), (min, i32) exact label/distance
# lattices (cc / labelprop / sssp / bfs), and (sum, i32) exact counters
# (degree).  Source / seed-set parameters are derived deterministically
# from the vertex-id space so no extra layout tables are needed.

DEFAULT_SOURCE = 0


def default_num_seeds(num_vertices: int) -> int:
    """Seed-set size for labelprop/ppr: ~V/256, at least 2."""
    return max(2, num_vertices // 256)


def _masked_ext(values, mask, fill):
    """(L_max,) values → (L_max+1,) with invalid slots and the trailing
    pad bucket forced to ``fill`` (what edge endpoint gathers read)."""
    safe = jnp.where(mask, values, fill)
    return jnp.concatenate([safe, jnp.full((1,), fill, safe.dtype)])


def _sssp_weight(gu, gv):
    """Deterministic positive edge weight from the endpoint gids (1..11)
    — gives SSSP a genuinely weighted metric with no edge-weight table."""
    return 1 + (3 * gu + 7 * gv) % 11


def _edge_gids(dev, view):
    gid_ext = jnp.concatenate([dev["vert_gid"],
                               jnp.full((1,), -1, jnp.int32)])
    return gid_ext[view["src"]], gid_ext[view["tgt"]]


def _relax_local(dist, dev, weight_fn):
    """One Bellman-Ford relaxation over the local directed edges:
    min over incoming (u → v) of dist[u] + w(u, v), min'd with current."""
    view = dev["view_in"]
    d_ext = _masked_ext(dist, dev["vert_mask"], CC_SENTINEL)
    du = d_ext[view["src"]]
    w = weight_fn(*_edge_gids(dev, view))
    # clamping before the add keeps sentinel+w from wrapping int32
    cand = jnp.where(du < CC_SENTINEL,
                     jnp.minimum(du, CC_SENTINEL - 64) + w, CC_SENTINEL)
    relaxed = _edge_reduce(cand, view, "min")
    cur = jnp.where(dev["vert_mask"], dist, CC_SENTINEL)
    return jnp.minimum(cur, relaxed)


def _distance_program(name: str, source: int, weight_fn) -> GASProgram:
    def init(dev):
        at_src = dev["vert_mask"] & (dev["vert_gid"] == source)
        return jnp.where(at_src, 0, CC_SENTINEL).astype(jnp.int32)

    def local(dist, dev):
        return _relax_local(dist, dev, weight_fn)

    def apply(total, aux, dev):
        clamped = jnp.where(dev["vert_gid"] == source, 0, total)
        return jnp.where(dev["vert_mask"] & dev["is_master"], clamped,
                         CC_SENTINEL)

    return GASProgram(name=name, combine="min", dtype=jnp.int32,
                      init=init, local=local, apply=apply)


@lru_cache(maxsize=None)
def sssp_program(source: int = DEFAULT_SOURCE) -> GASProgram:
    """Single-source shortest paths (Bellman-Ford relaxations) under the
    deterministic gid-hash weights — (min, i32), exact on every wire."""
    return _distance_program("sssp", source, _sssp_weight)


@lru_cache(maxsize=None)
def bfs_program(source: int = DEFAULT_SOURCE) -> GASProgram:
    """BFS levels from ``source`` (unit-weight min-plus) — (min, i32)."""
    return _distance_program("bfs", source, lambda gu, gv: 1)


@lru_cache(maxsize=None)
def labelprop_program(num_vertices: int,
                      num_seeds: int | None = None) -> GASProgram:
    """Seeded directed label propagation — the paper's own motivating
    workload: vertices with gid < num_seeds hold their own gid as a fixed
    label; everything else takes the min label over in-neighbors each
    round.  Directed propagation + clamped seeds distinguish it from CC's
    undirected min-label contagion.  (min, i32), exact on every wire."""
    ns = default_num_seeds(num_vertices) if num_seeds is None else num_seeds

    def init(dev):
        seeded = dev["vert_mask"] & (dev["vert_gid"] < ns)
        return jnp.where(seeded, dev["vert_gid"].astype(jnp.int32),
                         CC_SENTINEL)

    def local(label, dev):
        lab_ext = _masked_ext(label, dev["vert_mask"], CC_SENTINEL)
        view = dev["view_in"]
        out = _edge_reduce(lab_ext[view["src"]], view, "min")
        cur = jnp.where(dev["vert_mask"], label, CC_SENTINEL)
        return jnp.minimum(cur, out)

    def apply(total, aux, dev):
        seeded = dev["vert_gid"] < ns
        clamped = jnp.where(seeded, dev["vert_gid"].astype(jnp.int32),
                            total)
        return jnp.where(dev["vert_mask"] & dev["is_master"], clamped,
                         CC_SENTINEL)

    return GASProgram(name="labelprop", combine="min", dtype=jnp.int32,
                      init=init, local=local, apply=apply)


def _degree_local(value, dev):
    """Per-slot incident-edge count (out at src + in at dst: the lanes of
    the undirected view); ignores the carried value, so any iteration
    count ≥ 1 yields the same answer."""
    view = dev["view_both"]
    return _edge_reduce(jnp.ones(view["tgt"].shape, jnp.int32), view, "sum")


# total degree: the (sum, i32) wire cell — an integer sum combine ships
# exact on the quantized backend (lossy_payload is False)
DEGREE_PROGRAM = GASProgram(
    name="degree", combine="sum", dtype=jnp.int32,
    init=lambda dev: jnp.zeros(dev["vert_gid"].shape, jnp.int32),
    local=_degree_local,
    apply=lambda total, aux, dev: jnp.where(
        dev["vert_mask"] & dev["is_master"], total, 0),
    edges="both")


def _cent_local(value, dev):
    """In-neighbor sum without degree normalization (A^T x)."""
    contrib = _masked_ext(value, dev["vert_mask"],
                          jnp.zeros((), value.dtype))
    view = dev["view_in"]
    return _edge_reduce(contrib[view["src"]], view, "sum")


def _cent_aux(value, dev):
    """Global L1 mass of the current iterate (masters only)."""
    m = dev["vert_mask"] & dev["is_master"]
    return jnp.sum(jnp.where(m, value, 0.0))


@lru_cache(maxsize=None)
def centrality_program(num_vertices: int) -> GASProgram:
    """Approximate (eigenvector-style) centrality: damped power iteration
    x ← (1−d)/V + d·(Aᵀx)/‖x‖₁, the L1-normalized Katz/eigenvector hybrid
    — the normalization rides the engine's global-aux reduction.  (sum,
    f32): the quantized wire delta-codes it with error feedback."""
    base = (1.0 - DAMPING) / num_vertices

    def init(dev):
        return jnp.where(dev["vert_mask"], 1.0 / num_vertices, 0.0)

    def apply(total, norm, dev):
        new = base + DAMPING * total / jnp.maximum(norm, 1e-30)
        return jnp.where(dev["vert_mask"] & dev["is_master"], new, 0.0)

    return GASProgram(name="centrality", combine="sum", dtype=jnp.float32,
                      init=init, local=_cent_local, apply=apply,
                      aux=_cent_aux)


@lru_cache(maxsize=None)
def ppr_program(num_vertices: int,
                num_seeds: int | None = None) -> GASProgram:
    """Personalized pagerank: teleport (and dangling) mass lands on the
    seed set {gid < num_seeds} instead of uniformly — same local
    scatter/aux as pagerank, different apply.  (sum, f32) lossy wire."""
    ns = default_num_seeds(num_vertices) if num_seeds is None else num_seeds

    def init(dev):
        seeded = dev["vert_mask"] & (dev["vert_gid"] < ns)
        return jnp.where(seeded, 1.0 / ns, 0.0)

    def apply(total, dangle, dev):
        seeded = dev["vert_gid"] < ns
        teleport = jnp.where(seeded,
                             (1.0 - DAMPING) / ns + DAMPING * dangle / ns,
                             0.0)
        return jnp.where(dev["vert_mask"] & dev["is_master"],
                         DAMPING * total + teleport, 0.0)

    return GASProgram(name="ppr", combine="sum", dtype=jnp.float32,
                      init=init, local=_local_rank_partial, apply=apply,
                      aux=_local_dangle)


PROGRAM_NAMES = ("pagerank", "cc", "labelprop", "sssp", "bfs", "degree",
                 "centrality", "ppr")


def get_program(name: str, num_vertices: int) -> GASProgram:
    """Program registry: name → GASProgram with the library defaults
    (source vertex 0, ~V/256 seeds).  Factories are lru-cached so
    repeated lookups share one program instance (and its jit cache)."""
    if name == "pagerank":
        return pagerank_program(num_vertices)
    if name == "cc":
        return CC_PROGRAM
    if name == "labelprop":
        return labelprop_program(num_vertices)
    if name == "sssp":
        return sssp_program()
    if name == "bfs":
        return bfs_program()
    if name == "degree":
        return DEGREE_PROGRAM
    if name == "centrality":
        return centrality_program(num_vertices)
    if name == "ppr":
        return ppr_program(num_vertices)
    raise ValueError(f"unknown program {name!r}; expected one of "
                     f"{PROGRAM_NAMES}")


# ----------------------------------------------------------- shared body

def _check_overlap(exchange: str, overlap: bool) -> None:
    """The overlapped body needs per-hop partial combine + the layout's
    interior/frontier split — only the ragged ring exchanges provide
    both (dense/halo sync in one monolithic collective, so there is
    nothing to overlap against)."""
    if overlap and exchange not in RAGGED_EXCHANGES:
        raise ValueError(
            f"overlap=True needs a ragged ring exchange "
            f"{RAGGED_EXCHANGES}; got {exchange!r}")


def _gas_body(program: GASProgram, ex, dev, axis: str | None = None,
              overlap: bool = False):
    """One GAS iteration as a ``fori_loop`` body over (value, state).

    The per-partition callables vmap over the leading axis of ``dev``.
    ``axis=None`` is the stacked form: ``dev`` holds full (k, …) stacks
    and the exchange's ``*_stacked`` halves model the collectives.  With
    a mesh axis it is the per-device form run inside shard_map: ``dev``
    holds the device's local stacks (its k/D partitions), the global aux
    is psum'd over the axis after a sum over them, and the exchange's
    per-device halves run the collectives.  Both forms call the same
    ``program`` callables, so the simulated and production paths cannot
    drift.

    ``overlap=True`` (ragged exchanges only) restructures the reduce →
    apply dependency chain: the ring reduce folds each hop's lanes into
    the master accumulator as it lands (``hopwise``), and the apply of
    **interior** vertices (``~dev["frontier"]`` — single-replica, so
    their aggregate has no mirror contribution) is computed from the
    local partial alone, with no data dependence on any ppermute.  The
    scheduler is therefore free to run the interior gather/apply while
    the ring is still in flight; frontier slots select the exchanged
    total.  Interior slots satisfy total == partial bit-exactly (the
    hop accumulator holds the combine identity there), so the overlapped
    body is bit-identical to the phase-ordered one — same collectives,
    same values, shorter critical path."""
    stacked = axis is None
    reduce = ex.reduce_stacked if stacked else ex.reduce_to_masters
    broadcast = ex.broadcast_stacked if stacked else ex.broadcast_from_masters
    # built here, outside the loop: the loop body only reads the views
    local_dev = _with_edge_views((program,), dev)

    def step(carry):
        value, state = carry
        aux = (coll.psum(jnp.sum(jax.vmap(program.aux)(value, dev)), axis)
               if program.aux is not None else None)
        apply = jax.vmap(lambda t, d: program.apply(t, aux, d))
        partial_ = jax.vmap(program.local)(value, local_dev)
        if overlap:
            total, state = reduce(partial_, dev, program.combine, state,
                                  hopwise=True)
            new_master = jnp.where(dev["frontier"], apply(total, dev),
                                   apply(partial_, dev))
        else:
            total, state = reduce(partial_, dev, program.combine, state)
            new_master = apply(total, dev)
        value, state = broadcast(new_master, dev, program.combine, state)
        return value, state

    return _scoped_body(step, program.name)


def _scoped_body(step, name: str):
    """A ``fori_loop`` body running ``step`` under the named scope
    ``gas/<name>``, which the device ops of a profile carry."""
    def body(_, carry):
        with jax.named_scope("gas/" + name):
            return step(carry)
    return body


# --------------------------------------------------- early-exit residual

def _residual(new, old, mask, axis: str | None = None):
    """Masked max-norm residual between iterates, as f32.  Integer
    (min/counter) programs difference in int64 first — any real change
    is ≥ 1 and survives the f32 cast, so ``res > tol`` at tol ≥ 0 means
    "not yet at the fixed point" exactly; f32 programs use |Δ| directly.
    With a mesh ``axis`` the result is pmax'd so every device sees the
    same residual and the while_loop trip count stays lockstep."""
    if jnp.issubdtype(jnp.asarray(new).dtype, jnp.integer):
        # |Δ| without widening: values live in [0, iinfo.max] (labels /
        # distances / counters), so max−min is exact in the native dtype
        d = jnp.maximum(new, old) - jnp.minimum(new, old)
    else:
        d = jnp.abs(new - old)
    r = jnp.max(jnp.where(mask, d, 0)).astype(jnp.float32)
    return coll.pmax(r, axis)


def _converge_loop(body, value, state, iters: int, tol: float, mask,
                   axis: str | None = None):
    """``lax.while_loop`` form of the GAS iteration: ``iters`` becomes a
    cap and the loop exits once the masked master residual drops to
    ``tol``.  Returns (value, iters_run).  Running the fixed-``iters``
    path for exactly ``iters_run`` iterations reproduces the same value
    bit-for-bit — the body is shared, only the trip count differs."""
    def cond(carry):
        i, _, _, res = carry
        return (i < iters) & (res > tol)

    def wbody(carry):
        i, v, st, _ = carry
        nv, nst = body(i, (v, st))
        return i + 1, nv, nst, _residual(nv, v, mask, axis)

    i, value, _, _ = jax.lax.while_loop(
        cond, wbody,
        (jnp.int32(0), value, state, jnp.float32(jnp.inf)))
    return value, i


def _warm_tables(layout: PartitionLayout, dtype, init_values):
    """Host-side dense (V_old,) warm vector → per-slot (k, L_max) value
    and validity tables.  Vertices the old fixed point knew (gid <
    len(init_values)) seed from it; everything else keeps ``program.
    init``.  An empty vector yields an all-False mask — the cold run —
    so warm and cold share ONE compiled loop (same trace shapes)."""
    dense = (np.zeros(0) if init_values is None
             else np.asarray(init_values))
    n = dense.shape[0]
    gid = layout.vert_gid
    known = layout.vert_mask & (gid < n)
    safe = np.clip(gid, 0, max(n - 1, 0))
    vals = np.where(known, dense[safe] if n else 0, 0)
    vals = vals.astype(np.dtype(jnp.dtype(dtype).name))
    return jnp.asarray(vals), jnp.asarray(known)


# ----------------------------------------------------------- simulated driver

def _stack_dev(layout: PartitionLayout, exchange: str | None = None):
    return jax.tree_util.tree_map(jnp.asarray,
                                  layout.device_arrays(exchange))


@partial(jax.jit,
         static_argnames=("program", "iters", "ex", "tol", "overlap"))
def _sim_gas(program: GASProgram, dev, iters: int, ex,
             tol: float | None = None, overlap: bool = False, warm=None):
    # ``ex`` is the exchange INSTANCE (frozen dataclass, hashable): the
    # ragged formats carry their per-layout lane schedule in the
    # instance, so the instance — not the exchange name — is the cache key
    value = jax.vmap(program.init)(dev)
    if warm is not None:
        wvals, wmask = warm
        value = jnp.where(wmask, wvals, value)
    # iters == 0 must return init values without even tracing the loop
    # body — a trip-count-0 fori_loop still bakes its collectives into
    # the HLO, which the dry-run byte parser would then count
    if not iters:
        return value if tol is None else (value, jnp.int32(0))
    state = ex.init_state(dev, program.dtype, program.combine)
    body = _gas_body(program, ex, dev, overlap=overlap)
    if tol is None:
        value, _ = jax.lax.fori_loop(0, iters, body, (value, state))
        return value
    mask = dev["vert_mask"] & dev["is_master"]
    return _converge_loop(body, value, state, iters, tol, mask)


def _master_values(layout: PartitionLayout, vals: np.ndarray) -> np.ndarray:
    """(k, L_max) per-device values on the host → dense (V,) using master
    slots."""
    out = np.zeros(layout.num_vertices, dtype=vals.dtype)
    gid = layout.vert_gid
    sel = layout.is_master & layout.vert_mask
    out[gid[sel]] = vals[sel]
    return out


def simulate_gas(program: GASProgram, layout: PartitionLayout,
                 iters: int = 30, exchange: str = "dense", *,
                 tol: float | None = None, overlap: bool = False,
                 init_values=None, return_iters: bool = False):
    """Stacked one-device driver for any GAS program (bit-identical math
    to ``shard_map_gas`` — the collectives become transposes/gathers).

    ``tol`` switches the loop to convergence early exit: ``iters``
    becomes a cap and the run stops once the master-slot residual
    max-norm drops to ``tol`` (``return_iters=True`` also returns the
    executed iteration count).  ``overlap`` runs the interleaved
    interior/frontier body (ragged exchanges only — bit-identical, see
    ``_gas_body``).  ``init_values`` warm-starts from a dense (V_old,)
    value vector, e.g. a previously converged fixed point."""
    _check_overlap(exchange, overlap)
    with obs.span("gas." + program.name) as run_span:
        with obs.span("gas.upload"):
            dev = _stack_dev(layout, exchange)
            ex = get_exchange(exchange, layout)
            warm = (None if init_values is None
                    else _warm_tables(layout, program.dtype, init_values))
        with obs.span("gas.run", sorted_lanes=_sorted_lanes(
                (program,), layout, layout.k)):
            out = _sim_gas(program, dev, iters, ex, tol, overlap, warm)
            values, iters_run = (out, iters) if tol is None else out
            vals, iters_run = np.asarray(values), int(iters_run)
        with obs.span("gas.collect"):
            dense = _master_values(layout, vals)
        run_span.attrs["iters"] = iters_run
    return (dense, iters_run) if return_iters else dense


def simulate_pagerank(layout: PartitionLayout, iters: int = 30,
                      exchange: str = "dense", **kw):
    return simulate_gas(pagerank_program(layout.num_vertices), layout,
                        iters, exchange, **kw)


def simulate_cc(layout: PartitionLayout, iters: int = 30,
                exchange: str = "dense", **kw):
    out = simulate_gas(CC_PROGRAM, layout, iters, exchange, **kw)
    if kw.get("return_iters"):
        value, iters_run = out
        return value.astype(np.int64), iters_run
    return out.astype(np.int64)


# ----------------------------------------------------------- shard_map driver

# traces of the mesh GAS loop: ``_mesh_gas`` bumps it while jax traces
# it, never when a cached executable runs (the ``traced`` attribute of
# ``gas.run``)
_MESH_TRACES = [0]


def mesh_traces() -> int:
    """Traces of the mesh GAS loop in this process so far."""
    return _MESH_TRACES[0]


def _parts_per_device(layout: PartitionLayout, mesh: Mesh,
                      axis: str) -> int:
    devices = mesh.shape[axis]
    if layout.k % devices:
        raise ValueError(f"mesh axis {axis!r} has {devices} devices, which "
                         f"do not divide k = {layout.k} partitions")
    return layout.k // devices


def _mesh_dev(layout: PartitionLayout, exchange: str, mesh: Mesh,
              axis: str):
    """The layout's tables, each device holding its k/D partitions."""
    return jax.device_put(layout.device_arrays(exchange),
                          NamedSharding(mesh, P(axis)))


def shard_map_gas(program: GASProgram, layout: PartitionLayout, mesh: Mesh,
                  iters: int = 30, axis: str = "parts",
                  exchange: str = "dense", *, tol: float | None = None,
                  overlap: bool = False, init_values=None,
                  return_iters: bool = False):
    """Production path: the k partitions spread over the D devices of
    mesh ``axis``, k/D to a device (D must divide k); each device runs
    the GAS body over its partitions as one batch.  Several partitions
    per device need the ``halo`` or ``dense`` wire; the others route one
    partition per device and refuse more (``dist.halo``).  ``exchange``
    picks the mirror wire format (see module docstring).  Returns (V,)
    master values.  ``tol`` / ``overlap`` / ``init_values`` /
    ``return_iters`` as in ``simulate_gas`` — the residual is a max over
    the device's partitions, pmax'd across the mesh so every device
    exits the while_loop on the same iteration.

    The loop is jitted and cached per (program, wire, mesh, static
    shapes), like ``_sim_gas``: a second run of the same shapes traces
    and compiles nothing (``_mesh_run``)."""
    _check_overlap(exchange, overlap)
    parts = _parts_per_device(layout, mesh, axis)
    with obs.span("gas." + program.name) as run_span:
        with obs.span("gas.upload"):
            dev = _mesh_dev(layout, exchange, mesh, axis)
            ex = get_exchange(exchange, layout, axis=axis)
            warm = (None if init_values is None
                    else _warm_tables(layout, program.dtype, init_values))
        vals, iters_run = _mesh_run(program, layout, exchange, dev, warm,
                                    ex, mesh, axis, iters, tol, overlap)
        with obs.span("gas.collect"):
            dense = _master_values(layout, vals)
        run_span.attrs["iters"] = iters_run
    return (dense, iters_run) if return_iters else dense


def _mesh_run(program, layout: PartitionLayout, exchange: str, dev, warm,
              ex, mesh: Mesh, axis: str, iters: int, tol: float | None,
              overlap: bool) -> tuple:
    """``gas.run`` around the cached mesh loop of a program or a fused
    bundle: (host values, iterations run) once both are back on the
    host.  The span records ``devices``, ``parts_per_device``,
    ``ici_bytes`` (what one chip sends over the interconnect per
    iteration, both phases, as padded on the wire: the layout's
    ``comm_bytes(parts_per_device=…)``), ``sorted_lanes`` (as in
    ``_sorted_lanes``) and ``traced`` (traces of the loop in this
    call)."""
    parts = _parts_per_device(layout, mesh, axis)
    fused = isinstance(program, FusedGAS)
    programs = program.programs if fused else (program,)
    ici = layout.comm_bytes(
        exchange, programs=len(programs),
        fused=fused, lossy=lossy_payload(program.combine, program.dtype),
        value_bytes=jnp.dtype(program.dtype).itemsize,
        parts_per_device=parts)
    with obs.span("gas.run", devices=mesh.shape[axis],
                  parts_per_device=parts, ici_bytes=ici,
                  sorted_lanes=_sorted_lanes(programs, layout, parts)
                  ) as gas_run:
        traced = mesh_traces()
        out = _mesh_gas(program, dev, iters, ex, mesh, axis, tol, overlap,
                        warm)
        values, iters_run = (out, iters) if tol is None else out
        vals = np.asarray(values)
        iters_run = int(np.asarray(iters_run).reshape(-1)[0])
        gas_run.attrs["traced"] = mesh_traces() - traced
    return vals, iters_run


def _mesh_loop(program: GASProgram, ex, iters: int, axis: str,
               tol: float | None, overlap: bool):
    """The per-device GAS loop inside shard_map, over the device's local
    stacks: (m, L_max) values out, and with ``tol`` the (1,) iteration
    count."""
    def run(dev, *warm_arg):
        value = jax.vmap(program.init)(dev)
        if warm_arg:
            wvals, wmask = warm_arg[0]
            value = jnp.where(wmask, wvals, value)
        # a program whose init ignores the layout (degree's zeros) starts
        # the same on every device; the loop carry must vary from step 0
        value = coll.varying(value, axis)
        if not iters:
            return (value if tol is None
                    else (value, jnp.zeros((1,), jnp.int32)))
        state = ex.init_state(dev, program.dtype, program.combine)
        body = _gas_body(program, ex, dev, axis, overlap=overlap)
        if tol is None:
            value, _ = jax.lax.fori_loop(0, iters, body, (value, state))
            return value
        mask = dev["vert_mask"] & dev["is_master"]
        value, i = _converge_loop(body, value, state, iters, tol, mask,
                                  axis)
        return value, i[None]

    return run


@partial(jax.jit, static_argnames=("program", "iters", "ex", "mesh", "axis",
                                   "tol", "overlap"))
def _mesh_gas(program, dev, iters: int, ex, mesh: Mesh, axis: str,
              tol: float | None = None, overlap: bool = False, warm=None):
    """The shard_map'd loop of a program or a fused bundle, compiled once
    per (program, wire instance, mesh, static shapes) as ``_sim_gas`` is:
    (k, L_max) values ((k, N, L_max) fused), and with ``tol`` the
    iterations run, one entry per device."""
    _MESH_TRACES[0] += 1
    spec = P(axis)
    args = (dev,) if warm is None else (dev, warm)
    loop = (_mesh_loop_many if isinstance(program, FusedGAS)
            else _mesh_loop)
    run = jax.shard_map(loop(program, ex, iters, axis, tol, overlap),
                        mesh=mesh, in_specs=(spec,) * len(args),
                        out_specs=spec if tol is None else (spec, spec))
    return run(*args)


def shard_map_pagerank(layout: PartitionLayout, mesh: Mesh,
                       iters: int = 30, axis: str = "parts",
                       exchange: str = "dense") -> np.ndarray:
    return shard_map_gas(pagerank_program(layout.num_vertices), layout,
                         mesh, iters=iters, axis=axis, exchange=exchange)


def shard_map_cc(layout: PartitionLayout, mesh: Mesh, iters: int = 30,
                 axis: str = "parts", exchange: str = "dense") -> np.ndarray:
    return shard_map_gas(CC_PROGRAM, layout, mesh, iters=iters, axis=axis,
                         exchange=exchange).astype(np.int64)


# ------------------------------------------------- fused multi-program driver

@dataclass(frozen=True)
class FusedGAS:
    """N homogeneous GAS programs executed as one fused iteration over a
    shared ``PartitionLayout``: per-program local/apply math runs stacked
    along a leading program axis, and the mirror sync ships **one**
    collective per phase with all programs' lanes concatenated (per-
    program scale groups on the quantized wire — see
    ``repro.dist.halo``'s ``*_multi`` ops).  Programs must share one
    (combine, dtype) wire cell; hashable so it can be a jit static."""
    programs: tuple[GASProgram, ...]

    def __post_init__(self):
        if not self.programs:
            raise ValueError("FusedGAS needs at least one program")
        combines = {p.combine for p in self.programs}
        dtypes = {np.dtype(p.dtype).name for p in self.programs}
        if len(combines) > 1 or len(dtypes) > 1:
            raise ValueError(
                "fused programs must share one (combine, dtype) wire "
                f"cell; got combines {sorted(combines)} and dtypes "
                f"{sorted(dtypes)}")

    @property
    def combine(self) -> str:
        return self.programs[0].combine

    @property
    def dtype(self):
        return self.programs[0].dtype

    @property
    def name(self) -> str:
        return "+".join(p.name for p in self.programs)


def fuse_programs(programs) -> FusedGAS:
    """Coerce a GASProgram sequence (or an existing FusedGAS) to FusedGAS."""
    if isinstance(programs, FusedGAS):
        return programs
    return FusedGAS(tuple(programs))


def _gas_body_multi(fused: FusedGAS, ex, dev, axis: str | None = None,
                    overlap: bool = False):
    """One fused GAS iteration over (values, state) where values carry a
    program axis: (N, L_max) per device, (k, N, L_max) stacked.  The
    per-program math is a python loop over traced stacks (unrolled at
    trace time — N is small), but each mirror-sync phase is a single
    ``*_multi`` exchange call, i.e. one collective for all N programs.
    ``overlap`` interleaves interior apply with the ragged ring exactly
    like ``_gas_body`` (the frontier mask broadcasts over the program
    axis)."""
    stacked = axis is None
    programs = fused.programs
    n = len(programs)
    local_dev = _with_edge_views(programs, dev, batched=stacked)

    def global_aux(value):
        idx = [i for i, p in enumerate(programs) if p.aux is not None]
        auxes: list = [None] * n
        if idx:
            if stacked:
                per = jnp.stack([
                    jnp.sum(jax.vmap(programs[i].aux)(value[:, i], dev))
                    for i in idx])
            else:
                per = coll.psum(
                    jnp.stack([programs[i].aux(value[i], dev)
                               for i in idx]), axis)
            for j, i in enumerate(idx):
                auxes[i] = per[j]
        return auxes

    def step(carry):
        value, state = carry
        auxes = global_aux(value)
        if stacked:
            partials = jnp.stack(
                [jax.vmap(programs[i].local)(value[:, i], local_dev)
                 for i in range(n)], axis=1)

            def apply_all(tot):
                return jnp.stack(
                    [jax.vmap(lambda t, d, i=i: programs[i].apply(
                        t, auxes[i], d))(tot[:, i], dev)
                     for i in range(n)], axis=1)

            if overlap:
                total, state = ex.reduce_stacked_multi(
                    partials, dev, fused.combine, state, hopwise=True)
                new_master = jnp.where(dev["frontier"][:, None, :],
                                       apply_all(total),
                                       apply_all(partials))
            else:
                total, state = ex.reduce_stacked_multi(
                    partials, dev, fused.combine, state)
                new_master = apply_all(total)
            value, state = ex.broadcast_stacked_multi(new_master, dev,
                                                      fused.combine, state)
        else:
            partials = jnp.stack([programs[i].local(value[i], local_dev)
                                  for i in range(n)])

            def apply_all(tot):
                return jnp.stack(
                    [programs[i].apply(tot[i], auxes[i], dev)
                     for i in range(n)])

            if overlap:
                total, state = ex.reduce_to_masters_multi(
                    partials, dev, fused.combine, state, hopwise=True)
                new_master = jnp.where(dev["frontier"][None, :],
                                       apply_all(total),
                                       apply_all(partials))
            else:
                total, state = ex.reduce_to_masters_multi(
                    partials, dev, fused.combine, state)
                new_master = apply_all(total)
            value, state = ex.broadcast_from_masters_multi(
                new_master, dev, fused.combine, state)
        return value, state

    return _scoped_body(step, fused.name)


@partial(jax.jit,
         static_argnames=("fused", "iters", "ex", "tol", "overlap"))
def _sim_gas_many(fused: FusedGAS, dev, iters: int, ex,
                  tol: float | None = None, overlap: bool = False,
                  warm=None):
    value = jnp.stack([jax.vmap(p.init)(dev) for p in fused.programs],
                      axis=1)
    if warm is not None:
        wvals, wmask = warm
        value = jnp.where(wmask, wvals, value)
    if not iters:
        return value if tol is None else (value, jnp.int32(0))
    state = ex.init_state_multi(dev, fused.dtype, fused.combine,
                                len(fused.programs))
    body = _gas_body_multi(fused, ex, dev, overlap=overlap)
    if tol is None:
        value, _ = jax.lax.fori_loop(0, iters, body, (value, state))
        return value
    mask = (dev["vert_mask"] & dev["is_master"])[:, None, :]
    return _converge_loop(body, value, state, iters, tol, mask)


def _warm_tables_many(layout: PartitionLayout, fused: FusedGAS,
                      init_values):
    """Per-program warm tables stacked along the program axis:
    ``init_values`` is one dense (V_old,) vector or None per program
    (None → all-False mask, i.e. that program starts cold)."""
    pairs = [_warm_tables(layout, fused.dtype, iv) for iv in init_values]
    return (jnp.stack([v for v, _ in pairs], axis=1),
            jnp.stack([m for _, m in pairs], axis=1))


def simulate_gas_many(programs, layout: PartitionLayout, iters: int = 30,
                      exchange: str = "dense", *,
                      tol: float | None = None, overlap: bool = False,
                      init_values=None, return_iters: bool = False):
    """Stacked one-device driver for a fused program bundle; returns one
    dense (V,) master-value array per program, in bundle order.  ``tol``
    (early exit; residual = max over all programs), ``overlap``, and
    per-program ``init_values`` as in ``simulate_gas``."""
    _check_overlap(exchange, overlap)
    fused = fuse_programs(programs)
    with obs.span("gas." + fused.name) as run_span:
        with obs.span("gas.upload"):
            dev = _stack_dev(layout, exchange)
            ex = get_exchange(exchange, layout)
            warm = (None if init_values is None
                    else _warm_tables_many(layout, fused, init_values))
        with obs.span("gas.run", sorted_lanes=_sorted_lanes(
                fused.programs, layout, layout.k)):
            out = _sim_gas_many(fused, dev, iters, ex, tol, overlap, warm)
            values, iters_run = (out, iters) if tol is None else out
            vals, iters_run = np.asarray(values), int(iters_run)
        with obs.span("gas.collect"):
            dense = [_master_values(layout, vals[:, i])
                     for i in range(len(fused.programs))]
        run_span.attrs["iters"] = iters_run
    return (dense, iters_run) if return_iters else dense


def shard_map_gas_many(programs, layout: PartitionLayout, mesh: Mesh,
                       iters: int = 30, axis: str = "parts",
                       exchange: str = "dense", *,
                       tol: float | None = None, overlap: bool = False,
                       init_values=None, return_iters: bool = False):
    """Production fused path: N programs on one partition per device
    along ``axis`` (mesh axis size == k; more partitions per device are
    refused), one mirror-sync collective per phase for the whole bundle.
    ``tol`` / ``overlap`` / ``init_values`` / ``return_iters`` as in
    ``simulate_gas_many``."""
    _check_overlap(exchange, overlap)
    if _parts_per_device(layout, mesh, axis) != 1:
        raise ValueError(
            f"the fused mesh driver runs one partition per device; mesh "
            f"axis {axis!r} has {mesh.shape[axis]} devices for k = "
            f"{layout.k}: run the programs one by one (shard_map_gas), or "
            "on a mesh of k devices")
    fused = fuse_programs(programs)
    with obs.span("gas." + fused.name) as run_span:
        with obs.span("gas.upload"):
            dev = _mesh_dev(layout, exchange, mesh, axis)
            ex = get_exchange(exchange, layout, axis=axis)
            warm = (None if init_values is None
                    else _warm_tables_many(layout, fused, init_values))
        vals, iters_run = _mesh_run(fused, layout, exchange, dev, warm, ex,
                                    mesh, axis, iters, tol, overlap)
        with obs.span("gas.collect"):
            dense = [_master_values(layout, vals[:, i])
                     for i in range(len(fused.programs))]
        run_span.attrs["iters"] = iters_run
    return (dense, iters_run) if return_iters else dense


def _mesh_loop_many(fused: FusedGAS, ex, iters: int, axis: str,
                    tol: float | None, overlap: bool):
    """The per-device fused loop inside shard_map, over the device's one
    partition: (1, N, L_max) values out, and with ``tol`` the (1,)
    iteration count."""
    def run(dev, *warm_arg):
        dev = jax.tree_util.tree_map(lambda x: x[0], dev)
        value = jnp.stack([p.init(dev) for p in fused.programs])
        if warm_arg:
            wvals, wmask = jax.tree_util.tree_map(lambda x: x[0],
                                                  warm_arg[0])
            value = jnp.where(wmask, wvals, value)
        # a program whose init ignores the layout (degree's zeros) starts
        # the same on every device; the loop carry must vary from step 0
        value = coll.varying(value, axis)
        if not iters:
            return (value[None] if tol is None
                    else (value[None], jnp.zeros((1,), jnp.int32)))
        state = ex.init_state_multi(dev, fused.dtype, fused.combine,
                                    len(fused.programs))
        body = _gas_body_multi(fused, ex, dev, axis, overlap=overlap)
        if tol is None:
            value, _ = jax.lax.fori_loop(0, iters, body, (value, state))
            return value[None]
        mask = (dev["vert_mask"] & dev["is_master"])[None, :]
        value, i = _converge_loop(body, value, state, iters, tol, mask,
                                  axis)
        return value[None], i[None]

    return run


def gas_step_for_dryrun(program, layout: PartitionLayout,
                        mesh: Mesh, axis: str = "parts", iters: int = 1,
                        exchange: str = "dense", overlap: bool = False):
    """Returns (jitted_fn, example_args) whose .lower() the dry-run compiles
    — the graph dry-run parses each backend's collective bytes out of the
    post-SPMD HLO (``launch/dryrun.py --graph``).

    ``program`` may be a single ``GASProgram``, or a program sequence /
    ``FusedGAS``, in which case the compiled step is the fused
    multi-program iteration (one collective per phase for the bundle) so
    the dry-run can compare fused vs. separate wire bytes.  ``overlap``
    compiles the interleaved interior/frontier body (ragged exchanges
    only) — the dry-run gates that its wire bytes and collective-permute
    count match the phase-ordered step exactly."""
    _check_overlap(exchange, overlap)
    dev = _stack_dev(layout, exchange)
    ex = get_exchange(exchange, layout, axis=axis)
    spec = P(axis)
    if isinstance(program, GASProgram):
        step = jax.shard_map(
            _mesh_loop(program, ex, iters, axis, None, overlap), mesh=mesh,
            in_specs=(spec,), out_specs=spec)
        return jax.jit(step), (dev,)
    fused = fuse_programs(program)

    @partial(jax.shard_map, mesh=mesh, in_specs=(spec,), out_specs=spec)
    def step_many(dev):
        dev = jax.tree_util.tree_map(lambda x: x[0], dev)
        value = jnp.stack([p.init(dev) for p in fused.programs])
        if iters:
            state = ex.init_state_multi(dev, fused.dtype, fused.combine,
                                        len(fused.programs))
            body = _gas_body_multi(fused, ex, dev, axis, overlap=overlap)
            value, _ = jax.lax.fori_loop(0, iters, body, (value, state))
        return value[None]

    return jax.jit(step_many), (dev,)


def pagerank_step_for_dryrun(layout: PartitionLayout, mesh: Mesh,
                             axis: str = "parts", iters: int = 1,
                             exchange: str = "dense"):
    return gas_step_for_dryrun(pagerank_program(layout.num_vertices),
                               layout, mesh, axis=axis, iters=iters,
                               exchange=exchange)


# ----------------------------------------------------------- oracles

def reference_pagerank(src, dst, num_vertices, iters: int = 30) -> np.ndarray:
    """Dense single-machine oracle with identical dangling handling."""
    outdeg = np.zeros(num_vertices, dtype=np.int64)
    np.add.at(outdeg, src, 1)
    rank = np.full(num_vertices, 1.0 / num_vertices)
    base = (1.0 - DAMPING) / num_vertices
    for _ in range(iters):
        contrib = np.where(outdeg > 0, rank / np.maximum(outdeg, 1), 0.0)
        s = np.zeros(num_vertices)
        np.add.at(s, dst, contrib[src])
        dangle = rank[outdeg == 0].sum()
        rank = base + DAMPING * (s + dangle / num_vertices)
    return rank


def reference_cc(src, dst, num_vertices) -> np.ndarray:
    import scipy.sparse as sp
    from scipy.sparse.csgraph import connected_components
    A = sp.coo_matrix((np.ones(len(src)), (src, dst)),
                      shape=(num_vertices, num_vertices))
    _, comp = connected_components(A, directed=False)
    # canonical label: min vertex id of the component (what min-label finds)
    mins = np.full(comp.max() + 1, num_vertices, dtype=np.int64)
    np.minimum.at(mins, comp, np.arange(num_vertices))
    return mins[comp]


def _reference_relax(src, dst, num_vertices, iters, source, weights):
    """Shared Bellman-Ford oracle: iterates the exact per-round relaxation
    the engine runs, so it matches at any iteration count (converged or
    not) — unreachable vertices keep CC_SENTINEL."""
    src = np.asarray(src, dtype=np.int64)
    dst = np.asarray(dst, dtype=np.int64)
    dist = np.full(num_vertices, CC_SENTINEL, dtype=np.int64)
    dist[source] = 0
    for _ in range(iters):
        du = dist[src]
        cand = np.where(du < CC_SENTINEL,
                        np.minimum(du, CC_SENTINEL - 64) + weights,
                        CC_SENTINEL)
        new = dist.copy()
        np.minimum.at(new, dst, cand)
        new[source] = 0
        dist = new
    return dist


def reference_sssp(src, dst, num_vertices, iters: int = 40,
                   source: int = DEFAULT_SOURCE) -> np.ndarray:
    """SSSP under the deterministic gid-hash weights w(u,v)=1+(3u+7v)%11."""
    s = np.asarray(src, dtype=np.int64)
    d = np.asarray(dst, dtype=np.int64)
    w = 1 + (3 * s + 7 * d) % 11
    return _reference_relax(s, d, num_vertices, iters, source, w)


def reference_bfs(src, dst, num_vertices, iters: int = 40,
                  source: int = DEFAULT_SOURCE) -> np.ndarray:
    """BFS levels from ``source`` over directed edges."""
    s = np.asarray(src, dtype=np.int64)
    return _reference_relax(s, dst, num_vertices, iters, source,
                            np.ones(len(s), dtype=np.int64))


def reference_labelprop(src, dst, num_vertices, iters: int = 40,
                        num_seeds: int | None = None) -> np.ndarray:
    """Seeded directed min-label propagation; non-seeds that no seed ever
    reaches keep CC_SENTINEL."""
    ns = default_num_seeds(num_vertices) if num_seeds is None else num_seeds
    src = np.asarray(src, dtype=np.int64)
    dst = np.asarray(dst, dtype=np.int64)
    lab = np.full(num_vertices, CC_SENTINEL, dtype=np.int64)
    lab[:ns] = np.arange(ns)
    for _ in range(iters):
        new = lab.copy()
        np.minimum.at(new, dst, lab[src])
        new[:ns] = np.arange(ns)
        lab = new
    return lab


def reference_degree(src, dst, num_vertices) -> np.ndarray:
    """Total (in+out) degree, counting duplicate edges like the engine."""
    deg = np.zeros(num_vertices, dtype=np.int64)
    np.add.at(deg, np.asarray(src, dtype=np.int64), 1)
    np.add.at(deg, np.asarray(dst, dtype=np.int64), 1)
    return deg


def reference_centrality(src, dst, num_vertices,
                         iters: int = 30) -> np.ndarray:
    """L1-normalized damped power iteration x ← (1−d)/V + d·(Aᵀx)/‖x‖₁."""
    src = np.asarray(src, dtype=np.int64)
    dst = np.asarray(dst, dtype=np.int64)
    x = np.full(num_vertices, 1.0 / num_vertices)
    base = (1.0 - DAMPING) / num_vertices
    for _ in range(iters):
        s = np.zeros(num_vertices)
        np.add.at(s, dst, x[src])
        x = base + DAMPING * s / max(x.sum(), 1e-30)
    return x


def reference_ppr(src, dst, num_vertices, iters: int = 30,
                  num_seeds: int | None = None) -> np.ndarray:
    """Personalized pagerank with teleport + dangling mass on the seeds."""
    ns = default_num_seeds(num_vertices) if num_seeds is None else num_seeds
    src = np.asarray(src, dtype=np.int64)
    dst = np.asarray(dst, dtype=np.int64)
    outdeg = np.zeros(num_vertices, dtype=np.int64)
    np.add.at(outdeg, src, 1)
    e = np.zeros(num_vertices)
    e[:ns] = 1.0 / ns
    rank = e.copy()
    for _ in range(iters):
        contrib = np.where(outdeg > 0, rank / np.maximum(outdeg, 1), 0.0)
        s = np.zeros(num_vertices)
        np.add.at(s, dst, contrib[src])
        dangle = rank[outdeg == 0].sum()
        rank = DAMPING * s + (1.0 - DAMPING) * e + DAMPING * dangle * e
    return rank
