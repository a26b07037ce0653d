"""Pass 2 — game-theoretic cluster partitioning (paper §V, Alg. 3).

Each cluster is a selfish player choosing one of k partitions to minimize

    φ(a_i) = (λ/k)·|c_i|·|a_i|  +  ½·(|e(c_i, V\\a_i)| + |e(V\\a_i, c_i)|)

This is an exact potential game (Thm 4) with potential

    Φ(Λ)  = (λ/2k)·Σ|p_i|²  +  ½·Σ|e(p_i, V\\p_i)|

so sequential best response converges to a Nash equilibrium; the paper
parallelizes by batching clusters (contiguous IDs — BFS locality, §V-D) and
running batches concurrently against a shared snapshot.  We reproduce both:
``best_response_rounds`` (host, vectorized-Jacobi-within-batch /
Gauss–Seidel-across-batches) and a jitted JAX variant used by shard_map
(one batch per device) and by the Pallas ``game_bestresponse`` kernel.

λ defaults to its maximum feasible value (Thm 5), the paper's §VI setting:
    λ_max = k²·Σ|e(c_i, V\\c_i)|  /  (Σ|c_i|)²
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np
import scipy.sparse as sp

import jax
import jax.numpy as jnp

from ..dist import collectives as coll


@dataclass
class ClusterGraph:
    """Contracted graph: vertices = clusters."""
    sizes: np.ndarray          # |c_i| = intra-cluster edge counts, int64[m]
    adj: sp.csr_matrix         # symmetrized inter-cluster edge counts, m×m
    vertex_cluster: np.ndarray  # original vertex -> cluster id
    m: int

    @property
    def total_cut_capacity(self) -> int:
        """Σ_i |e(c_i, V\\c_i)| — Thm 5/6 constant (each directed cross edge
        counted once per incident cluster, i.e. adj.sum() counts it twice
        after symmetrization... adj already = W + Wᵀ so row sums are it)."""
        return int(self.adj.sum()) // 1  # Σ_i row_sum = Σ_i |e(c_i,·)|+|e(·,c_i)|


def contract(src: np.ndarray, dst: np.ndarray, clu: np.ndarray) -> ClusterGraph:
    """Build the cluster multigraph from the vertex→cluster table."""
    cs, cd = clu[src], clu[dst]
    m = int(clu.max()) + 1 if clu.size else 0
    intra = cs == cd
    sizes = np.bincount(cs[intra], minlength=m).astype(np.int64)
    xs, xd = cs[~intra], cd[~intra]
    w = np.ones(xs.shape[0], dtype=np.int64)
    W = sp.coo_matrix((w, (xs, xd)), shape=(m, m)).tocsr()
    S = (W + W.T).tocsr()
    S.sum_duplicates()
    return ClusterGraph(sizes, S, clu, m)


def lambda_max(cg: ClusterGraph, k: int) -> float:
    """Thm 5 upper end of the feasible λ range (paper's default)."""
    total_sizes = float(cg.sizes.sum())
    if total_sizes <= 0:
        return 1.0
    # Σ_i |e(c_i,V\c_i)| with both directions = adj row sums / but each
    # directed edge contributes to exactly two clusters' boundaries; the
    # paper's Σ counts per-cluster boundary edges, i.e. adj.sum()/2 per
    # direction pair — use the symmetric total/2 (per-cluster out+in)/2.
    total_cut = float(cg.adj.sum()) / 2.0
    return (k * k) * total_cut / (total_sizes * total_sizes)


def lambda_from_weight(cg: ClusterGraph, k: int, weight: float) -> float:
    """Relative-weight parameterization (paper Fig. 11b): weight∈(0,1) is
    the share of the load-balance term; 0.5 ⇒ the Eq. 15 equal-importance
    setting scaled so both terms match at a uniform random assignment."""
    total_sizes = float(cg.sizes.sum())
    total_cut = float(cg.adj.sum()) / 2.0
    if total_sizes <= 0 or total_cut <= 0:
        return 1.0
    base = k * total_cut / (total_sizes * total_sizes / k)
    w = min(max(weight, 1e-3), 1 - 1e-3)
    return base * (w / (1 - w))


@dataclass
class GameResult:
    assign: np.ndarray         # cluster -> partition, int32[m]
    rounds: int
    potential_trace: list
    moves: int


def potential(cg: ClusterGraph, assign: np.ndarray, k: int,
              lam: float) -> float:
    """Φ(Λ) (Definition 4)."""
    loads = np.bincount(assign, weights=cg.sizes, minlength=k)
    load_term = lam / (2.0 * k) * float((loads ** 2).sum())
    A = cg.adj.tocoo()
    cross = float(A.data[assign[A.row] != assign[A.col]].sum()) / 2.0
    # cross counts each undirected-symmetrized pair once ⇒ Σ_p |e(p,V\p)| =
    # (directed cross edges) = cross  (adj = W+Wᵀ, /2 restores W totals)
    return load_term + 0.5 * cross


def global_cost(cg: ClusterGraph, assign: np.ndarray, k: int,
                lam: float) -> float:
    """φ(Λ) (Eq. 10)."""
    loads = np.bincount(assign, weights=cg.sizes, minlength=k)
    load_term = lam / k * float((loads ** 2).sum())
    A = cg.adj.tocoo()
    cross = float(A.data[assign[A.row] != assign[A.col]].sum()) / 2.0
    return load_term + cross


def best_response_rounds(cg: ClusterGraph, k: int, lam: float | None = None,
                         batch_size: int | None = None,
                         max_rounds: int = 64, seed: int = 0,
                         track_potential: bool = False,
                         base_loads: np.ndarray | None = None) -> GameResult:
    """Alg. 3 with the paper's §V-D batching.

    Batches are the parallel unit (one per thread/device).  A batch plays
    *sequentially* (Gauss–Seidel) against the live load table; the cut-mass
    table ``A`` is refreshed per batch (threads see a per-batch snapshot of
    other players' choices — the paper's shared-nothing approximation).
    ``batch_size=None`` ⇒ one batch = fully sequential best response with a
    guaranteed monotone potential (exact potential game, Thm 4).

    ``base_loads`` adds exogenous per-partition load (used by the Mint-like
    baseline's sliding window and by the distributed pipeline where other
    nodes' loads are synced in).
    """
    m = cg.m
    if m == 0:
        return GameResult(np.zeros(0, np.int32), 0, [], 0)
    if lam is None:
        lam = lambda_max(cg, k)
    rng = np.random.default_rng(seed)
    assign = rng.integers(0, k, size=m).astype(np.int64)   # Alg.3 line 2
    sizes = cg.sizes.astype(np.float64)
    loads = np.bincount(assign, weights=sizes, minlength=k)
    if base_loads is not None:
        loads = loads + base_loads.astype(np.float64)
    S = cg.adj.astype(np.float64)
    indptr, indices, data = S.indptr, S.indices, S.data
    row_tot = np.asarray(S.sum(axis=1)).ravel().astype(np.float64)
    if batch_size is None:
        batch_size = m
    trace = []
    total_moves = 0
    ar = np.arange(k)
    for rnd in range(max_rounds):
        moved = 0
        for lo in range(0, m, batch_size):
            hi = min(m, lo + batch_size)
            for i in range(lo, hi):          # Gauss–Seidel sweep (live state)
                sz = sizes[i]
                cur = assign[i]
                nbrs = indices[indptr[i]:indptr[i + 1]]
                w = data[indptr[i]:indptr[i + 1]]
                # cut mass into each partition: A[p] = Σ_{j: a_j=p} S[i,j]
                aff = np.bincount(assign[nbrs], weights=w, minlength=k)
                loads_ex = loads - sz * (ar == cur)
                cost = (lam / k) * sz * (loads_ex + sz) \
                    + 0.5 * (row_tot[i] - aff)
                best = int(np.argmin(cost))
                if cost[best] + 1e-9 < cost[cur]:
                    loads[cur] -= sz
                    loads[best] += sz
                    assign[i] = best
                    moved += 1
        total_moves += moved
        if track_potential:
            trace.append(potential(cg, assign, k, lam))
        if moved == 0:
            return GameResult(assign.astype(np.int32), rnd + 1, trace,
                              total_moves)
    return GameResult(assign.astype(np.int32), max_rounds, trace, total_moves)


def greedy_assign(cg: ClusterGraph, k: int) -> np.ndarray:
    """CLUGP-G ablation (§VI-B): big clusters → least-loaded partitions.
    Stable sort so ties break by cluster id — the jit backend's
    ``jax_greedy_assign`` (jnp.argsort is stable) then matches bit-for-bit.
    """
    order = np.argsort(-cg.sizes, kind="stable")
    loads = np.zeros(k, dtype=np.int64)
    assign = np.zeros(cg.m, dtype=np.int32)
    for c in order:
        p = int(np.argmin(loads))
        assign[c] = p
        loads[p] += int(cg.sizes[c])
    return assign


# ---------------------------------------------------------------------------
# JAX batched best-response round (dense adjacency) — jit/shard_map building
# block; the Pallas kernel in repro.kernels.game_bestresponse implements the
# same contraction with CSR tiles.
# ---------------------------------------------------------------------------

_LANE_BIG = np.float32(3e38)    # masks partition lanes >= the traced k_real


def _mask_lanes(cost, k_real, lanes=None):
    """Disable partition lanes past the traced live count ``k_real`` (the
    compile-once k-sweep pads every per-k problem to k_max lanes).  With
    ``k_real=None`` (the static-k strategies) this is the identity."""
    if k_real is None:
        return cost
    if lanes is None:
        lanes = jax.lax.broadcasted_iota(jnp.int32, cost.shape,
                                         cost.ndim - 1)
    return jnp.where(lanes < k_real, cost, _LANE_BIG)


def jax_greedy_assign(sizes, k: int, k_real=None):
    """jit/shard_map form of ``greedy_assign`` over padded (m_cap,) sizes.
    Bit-identical to the host version: both sort stably by (-size, id) and
    break load ties toward the lowest partition id.  Padded clusters have
    size 0 — they land wherever argmin points but carry no vertices and
    add no load.  ``k_real`` (traced) restricts the argmin to the live
    lanes of a k_max-padded sweep step."""
    m_cap = sizes.shape[0]
    order = jnp.argsort(-sizes)                 # jnp.argsort is stable

    def body(i, carry):
        loads, assign = carry
        c = order[i]
        p = jnp.argmin(_mask_lanes(loads, k_real)).astype(jnp.int32)
        return loads.at[p].add(sizes[c]), assign.at[c].set(p)

    loads0 = jnp.zeros((k,), sizes.dtype)
    assign0 = jnp.zeros((m_cap,), jnp.int32)
    _, assign = jax.lax.fori_loop(0, m_cap, body, (loads0, assign0))
    return assign


def jax_game_rounds(row, col, w, sizes, row_tot, k: int, lam, *,
                    batch_size: int, max_rounds: int, seed: int,
                    use_pallas: bool = False, block_m: int = 256,
                    axis: str | None = None, damping: float = 0.5,
                    k_real=None):
    """Batched best-response rounds (Alg. 3 + §V-D) as a pure jax program.

    The cluster graph arrives as a pair list (``row``, ``col``, ``w``;
    pad row ``m_cap`` drops): the aggregated distinct pairs of
    ``jax_cluster_csr`` where ``pair_keys_fit(m_cap)``, else the raw
    cross-edge list of ``raw_cluster_pairs``.  Both give the same
    ``cut_mass`` table bit for bit, so the game plays the same rounds on
    either, and the aggregated one has far fewer lanes.  Each
    batch recomputes its cut-mass rows from the live assignment (the
    host's per-batch snapshot refresh), plays Jacobi *within* the batch,
    and updates the load table between batches (Gauss–Seidel across
    batches).  Under ``axis`` (shard_map) each device owns a private id
    space and acts as one §V-D batch: load deltas are psum'd after every
    batch so remote players see a fresh global load vector, and the
    convergence test is the psum'd move count.

    Jacobi-within-batch needs ``damping``: unlike the host's Gauss–Seidel
    sweep, simultaneous best responses herd toward the currently
    least-loaded partitions and oscillate, so each round only a random
    ``damping`` fraction of improving players actually moves (the standard
    parallel-local-search fix).  Damped Jacobi plateaus rather than
    reaching an exact Nash point (a small cycle of players keeps wanting
    to chase each other), so termination uses the game's own potential
    Φ (Thm 4): the round loop tracks the best-Φ assignment seen and stops
    once Φ has not improved for ``stall_rounds`` consecutive rounds —
    returning the best snapshot, not the last thrash.

    ``lam`` is a traced scalar (λ_max of the streamed cluster graph).
    With ``use_pallas`` the per-batch argmin sweep runs on the
    ``game_bestresponse`` Pallas kernel (k padded to a 128-lane multiple);
    otherwise the identical XLA fallback math.  ``k_real`` (traced, XLA
    path only — the Pallas kernel bakes k in) plays the game on the live
    lanes of a k_max-padded sweep step.  Returns (assign (m_cap,) int32,
    rounds)."""
    if k_real is not None and use_pallas:
        raise ValueError("jax_game_rounds: the Pallas kernel needs a "
                         "static k; run traced-k sweeps on the xla/scan "
                         "game modes")
    m_cap = sizes.shape[0]
    kpad = ((k + 127) // 128) * 128 if use_pallas else k
    sizes = sizes.astype(jnp.float32)
    row_tot = row_tot.astype(jnp.float32)
    lam = jnp.asarray(lam, jnp.float32)
    kf = (jnp.float32(k) if k_real is None
          else k_real.astype(jnp.float32))
    n_batches = max(1, -(-m_cap // batch_size))
    ar = jnp.arange(m_cap)
    # compact cluster ids fill [0, m); the padding past m never moves (no
    # size, no cut mass), so batches wholly inside it are skipped — with
    # m ≪ m_cap that is most of them.  The pmax keeps every device's
    # trip count, and so its collectives, in step.
    live = (sizes > 0) | (row_tot > 0)
    m_live = coll.pmax(jnp.max(jnp.where(live, ar + 1, 0)), axis)
    live_batches = jnp.maximum(1, (m_live + batch_size - 1) // batch_size)

    key = jax.random.PRNGKey(seed)
    if axis is not None:
        key = jax.random.fold_in(key, jax.lax.axis_index(axis))
    assign0 = jax.random.randint(key, (m_cap,), 0,
                                 k if k_real is None else k_real,
                                 dtype=jnp.int32)
    loads0 = jnp.zeros((kpad,), jnp.float32).at[assign0].add(sizes)
    loads0 = coll.psum(loads0, axis)

    def psum_(x):
        return coll.psum(x, axis)

    def batch_body(b, carry):
        assign, loads, moved, rnd = carry
        aff = cut_mass(row, col, w, assign, kpad)
        if use_pallas:
            from ..kernels.game_bestresponse import game_bestresponse
            best, best_cost = game_bestresponse(
                aff, sizes, row_tot, assign, loads, lam=lam, k=k,
                block_m=block_m)
        else:
            pids = jax.lax.broadcasted_iota(jnp.int32, (m_cap, kpad), 1)
            own = (pids == assign[:, None]).astype(jnp.float32)
            loads_ex = loads[None, :] - sizes[:, None] * own
            cost = (lam / kf) * sizes[:, None] * (loads_ex + sizes[:, None]) \
                + 0.5 * (row_tot[:, None] - aff)
            cost = _mask_lanes(cost, k_real, pids)
            best = jnp.argmin(cost, axis=1).astype(jnp.int32)
            best_cost = jnp.min(cost, axis=1)
        cost_cur = (lam / kf) * sizes * loads[assign] \
            + 0.5 * (row_tot - aff[ar, assign])
        in_batch = (ar >= b * batch_size) & (ar < (b + 1) * batch_size)
        # strict improvement with an f32-relative margin: absolute 1e-9
        # (the host's f64 threshold) is below float32 resolution at
        # realistic cost magnitudes and lets cost ties flap forever
        margin = 1e-6 + 1e-5 * jnp.abs(cost_cur)
        wants = in_batch & (best_cost + margin < cost_cur)
        damp_key = jax.random.fold_in(key, rnd * n_batches + b + 1)
        # decay the move probability round by round: late-game herding of
        # small clusters between near-equal partitions is what keeps
        # Jacobi sweeps from settling
        p = jnp.maximum(damping * 0.92 ** rnd.astype(jnp.float32), 0.08)
        if axis is not None:
            # every device plays its batch at once against the same
            # global loads, so n devices herd n times as hard as one:
            # each moves 1/n as often, keeping the round's expected
            # moved mass that of a single device (measured on 4 devices
            # at scale 16, k=4: RF 1.28x the jit partition -> 1.06x)
            p = p / jax.lax.axis_size(axis)
        move = wants & jax.random.bernoulli(damp_key, p, (m_cap,))
        msz = jnp.where(move, sizes, 0.0)
        delta = (jnp.zeros((kpad,), jnp.float32)
                 .at[best].add(msz).at[assign].add(-msz))
        assign = jnp.where(move, best, assign)
        loads = loads + psum_(delta)
        moved = moved + psum_(wants.sum().astype(jnp.int32))
        return assign, loads, moved, rnd

    def potential(assign, loads):
        """Φ (Definition 4) from the live tables — the cut mass is
        recomputed from the pair list; Σ_i (row_tot − aff[i,a_i])
        double-counts each symmetrized pair, hence the 0.25."""
        aff = cut_mass(row, col, w, assign, kpad)
        cut = psum_(jnp.sum(row_tot - aff[ar, assign]))
        load_sq = jnp.sum(loads * loads)        # loads are already global
        return (lam / (2 * kf)) * load_sq + 0.25 * cut

    stall_rounds = 4

    def round_body(carry):
        assign, loads, rnd, _, best_assign, best_phi, stall = carry
        assign, loads, moved, _ = jax.lax.fori_loop(
            0, live_batches, batch_body,
            (assign, loads, jnp.int32(0), rnd))
        phi = potential(assign, loads)
        better = phi < best_phi - 1e-6 * jnp.abs(best_phi)
        best_assign = jnp.where(better, assign, best_assign)
        best_phi = jnp.minimum(phi, best_phi)
        stall = jnp.where(better, 0, stall + 1)
        return assign, loads, rnd + 1, moved, best_assign, best_phi, stall

    def cond(carry):
        _, _, rnd, moved, _, _, stall = carry
        return (moved > 0) & (rnd < max_rounds) & (stall < stall_rounds)

    # best_phi starts at a huge FINITE value: with inf the round-1
    # improvement test computes inf - inf = NaN, 'better' is False, and
    # best_assign would stay the random initial assignment
    _, _, rounds, _, best_assign, _, _ = jax.lax.while_loop(
        cond, round_body,
        (assign0, loads0, jnp.int32(0), jnp.int32(1), assign0,
         jnp.float32(3e38), jnp.int32(0)))
    return best_assign, rounds


def pair_keys_fit(m_cap: int) -> bool:
    """Whether the symmetric pair keys ``row·m_cap + col`` of an
    ``m_cap``-cluster id space fit int32 (m_cap ≤ 46,340): the static
    test that puts the games on the aggregated pair list."""
    return m_cap * (m_cap + 1) < 2 ** 31


def raw_cluster_pairs(xs, xd):
    """The cross-edge list as an unaggregated pair list: both directions
    of every edge, weight one each (sentinel lanes keep row ``m_cap``
    and drop).  The games play on it where ``pair_keys_fit`` fails."""
    return (jnp.concatenate([xs, xd]), jnp.concatenate([xd, xs]),
            jnp.float32(1.0))


def cut_mass(row, col, w, assign, lanes: int):
    """The cut-mass table aff[i, p] = Σ_{j: a_j = p} S[i, j] of the pair
    list (``row``, ``col``, ``w``) under ``assign``: one gather and one
    weighted scatter-add over the list's lanes into (m_cap, lanes).
    Every entry is an integer count below 2²⁴, so float32 sums are exact
    in any order: the aggregated and the raw list give the same table
    bit for bit."""
    m_cap = assign.shape[0]
    return (jnp.zeros((m_cap, lanes), jnp.float32)
            .at[row, assign[jnp.clip(col, 0, m_cap - 1)]]
            .add(w, mode="drop"))


def jax_cluster_csr(xs, xd, m_cap: int, nnz_cap: int):
    """In-graph aggregated edge list of the cluster multigraph from its
    cross-edge endpoints (padded lanes = ``m_cap``): the distinct
    symmetrized (row, col) pairs in key order with their multiplicities,
    compacted into ``nnz_cap`` lanes (pad row = ``m_cap``, col 0, w 0).
    Returns (row, col, w, n_pairs): ``n_pairs`` counts every distinct
    pair, so ``n_pairs > nnz_cap`` is the overflow on which callers
    retry with a doubled ``nnz_cap``, like the partitioner's other
    adaptive caps.  Both games play on this list (``cut_mass``) where
    ``pair_keys_fit(m_cap)``: distinct pairs are ~10–20× fewer than the
    raw cross edges' two directions on web graphs, and every round's two
    cut-mass scatters walk the list's lanes.

    Built once per body run with two sorts and no scatter: the keys,
    then the positions of the runs' first lanes (the rest sort last), so
    each multiplicity is the distance to the next run's start."""
    if not pair_keys_fit(m_cap):
        raise ValueError(
            f"jax_cluster_csr: m_cap={m_cap} overflows the int32 "
            f"pair-key space (limit ~46340); play on the raw list of "
            f"raw_cluster_pairs instead")
    n = 2 * xs.shape[0]
    big = jnp.int32(m_cap * m_cap)
    ok = (xs < m_cap) & (xd < m_cap)
    key = jnp.concatenate([xs * m_cap + xd, xd * m_cap + xs])
    key = jnp.where(jnp.concatenate([ok, ok]), key, big)
    sk = jnp.sort(key)
    live = sk < big
    n_live = live.sum().astype(jnp.int32)
    first = jnp.concatenate([jnp.ones((1,), bool), sk[1:] != sk[:-1]])
    first = first & live
    n_pairs = first.sum().astype(jnp.int32)
    # run starts in order, then n_live: start[r + 1] - start[r] is run r's
    # length, and the lanes past the last run read 0
    pos = jnp.where(first, jnp.arange(n, dtype=jnp.int32), n_live)
    if n < nnz_cap + 1:
        pos = jnp.concatenate(
            [pos, jnp.broadcast_to(n_live, (nnz_cap + 1 - n,))])
    start = jnp.sort(pos)[:nnz_cap + 1]
    w = (start[1:] - start[:-1]).astype(jnp.float32)
    slot = jnp.arange(nnz_cap, dtype=jnp.int32)
    keys = jnp.where(slot < n_pairs, sk[jnp.clip(start[:-1], 0, n - 1)], big)
    return keys // m_cap, keys % m_cap, w, n_pairs


def jax_game_rounds_gs(row, col, w, sizes, row_tot, k: int, lam, *,
                       max_rounds: int, seed: int,
                       axis: str | None = None, k_real=None):
    """Gauss–Seidel-on-loads best response as a lax.scan over clusters —
    the CPU-fast form of Alg. 3 (the batched-Jacobi ``jax_game_rounds``
    needs damping and ~10× the rounds).  Per round the cut-mass table
    aff[i, p] is computed once from the round-start assignment (one
    ``cut_mass`` scatter over the pair list); the sweep then
    plays clusters sequentially against the LIVE load table, i.e. one
    round = one §V-D batch snapshot for the cut term with Gauss–Seidel
    load accounting.  The snapshot approximation can cycle instead of
    reaching an exact Nash point, so termination tracks the potential Φ
    (Thm 4): the loop keeps the best-Φ assignment seen and stops when a
    sweep moves nothing or Φ stalls for ``stall_rounds`` rounds.

    Under ``axis`` each device sweeps its private clusters (one batch
    per device) and loads/moves are psum'd between rounds.  ``k_real``
    (traced) plays on the live lanes of a k_max-padded sweep step."""
    m_cap = sizes.shape[0]
    sizes = sizes.astype(jnp.float32)
    row_tot = row_tot.astype(jnp.float32)
    lam = jnp.asarray(lam, jnp.float32)
    kf = (jnp.float32(k) if k_real is None
          else k_real.astype(jnp.float32))

    key = jax.random.PRNGKey(seed)
    if axis is not None:
        key = jax.random.fold_in(key, jax.lax.axis_index(axis))
    assign0 = jax.random.randint(key, (m_cap,), 0,
                                 k if k_real is None else k_real,
                                 dtype=jnp.int32)
    loads0 = jnp.zeros((k,), jnp.float32).at[assign0].add(sizes)
    loads0 = coll.psum(loads0, axis)

    lanes = jnp.arange(k)
    ar = jnp.arange(m_cap, dtype=jnp.int32)

    def cluster_step(carry, x):
        assign, loads, moved = carry
        i, aff, sz, rt = x
        cur = assign[i]
        own = (lanes == cur).astype(jnp.float32)
        loads_ex = loads - sz * own
        cost = (lam / kf) * sz * (loads_ex + sz) + 0.5 * (rt - aff)
        cost = _mask_lanes(cost, k_real, lanes)
        best = jnp.argmin(cost).astype(jnp.int32)
        move = cost[best] + 1e-6 + 1e-5 * jnp.abs(cost[cur]) < cost[cur]
        newa = jnp.where(move, best, cur)
        loads = loads + sz * ((lanes == newa).astype(jnp.float32) - own) \
            * move.astype(jnp.float32)
        assign = assign.at[i].set(newa)     # i is streamed in → in-place
        return (assign, loads, moved + move.astype(jnp.int32)), None

    def phi_of(assign, loads, aff):
        """Φ (Definition 4); Σ_i (row_tot − aff[i,a_i]) double-counts
        each symmetrized pair, hence the 0.25."""
        cut = coll.psum(jnp.sum(row_tot - aff[ar, assign]), axis)
        return (lam / (2 * kf)) * jnp.sum(loads * loads) + 0.25 * cut

    stall_rounds = 4

    def round_body(carry):
        assign, loads, rnd, _, best_assign, best_phi, stall = carry
        aff = cut_mass(row, col, w, assign, k)
        phi = phi_of(assign, loads, aff)
        better = phi < best_phi
        best_assign = jnp.where(better, assign, best_assign)
        stall = jnp.where(phi < best_phi - 1e-6 * jnp.abs(best_phi),
                          0, stall + 1)
        best_phi = jnp.minimum(phi, best_phi)
        (assign, loads, moved), _ = jax.lax.scan(
            cluster_step, (assign, loads, jnp.int32(0)),
            (ar, aff, sizes, row_tot))
        if axis is not None:
            # remote batches see this round's deltas only now (§V-D
            # shared-nothing approximation)
            local = jnp.zeros((k,), jnp.float32).at[assign].add(sizes)
            loads = coll.psum(local, axis)
            moved = coll.psum(moved, axis)
        return (assign, loads, rnd + 1, moved, best_assign, best_phi,
                stall)

    def cond(carry):
        _, _, rnd, moved, _, _, stall = carry
        return (moved > 0) & (rnd < max_rounds) & (stall < stall_rounds)

    # finite sentinel: an inf best_phi makes the stall margin NaN on
    # round 1 (inf - inf) and silently burns one stall round
    assign, loads, rounds, _, best_assign, best_phi, _ = jax.lax.while_loop(
        cond, round_body,
        (assign0, loads0, jnp.int32(0), jnp.int32(1), assign0,
         jnp.float32(3e38), jnp.int32(0)))
    # the final sweep's state was never Φ-checked inside the loop
    phi = phi_of(assign, loads, cut_mass(row, col, w, assign, k))
    best_assign = jnp.where(phi < best_phi, assign, best_assign)
    return best_assign, rounds


def jax_best_response_round(S, sizes, assign, loads, k: int, lam: float,
                            batch_slice=None):
    """One Jacobi batch update.  S: dense (b, m) adjacency rows of the batch,
    sizes: (b,), assign_all: (m,), loads: (k,). Returns new batch assign."""
    onehot = jax.nn.one_hot(assign, k, dtype=S.dtype)         # (m, k)
    A = S @ onehot                                            # (b, k)
    row_tot = S.sum(axis=1, keepdims=True)
    if batch_slice is None:
        cur = assign
        sz = sizes[:, None]
    else:
        cur = jax.lax.dynamic_slice_in_dim(assign, batch_slice, S.shape[0])
        sz = jax.lax.dynamic_slice_in_dim(sizes, batch_slice, S.shape[0])[:, None]
    loads_ex = loads[None, :] - sz * jax.nn.one_hot(cur, k, dtype=S.dtype)
    cost = (lam / k) * sz * (loads_ex + sz) + 0.5 * (row_tot - A)
    return jnp.argmin(cost, axis=1).astype(jnp.int32)
