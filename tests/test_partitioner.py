"""Cross-backend partitioner equivalence (repro.core.partitioner).

The "np" backend is the oracle; "jit" must match it bit-for-bit wherever
both sides are deterministic (clustering labels, greedy game, transform,
restream priors) and within tolerance where the game RNG differs;
"sharded" is exercised in a multi-device subprocess and judged against
the same-split-width np combine.
"""
import dataclasses

import numpy as np
import pytest

from repro.core import (CLUGPConfig, partition,
                        partition_sweep, sweep_trace_count, web_graph)


@pytest.fixture(scope="module")
def graph10():
    return web_graph(scale=10, edge_factor=6, seed=3)


# ------------------------------------------------------------- api basics

def test_unknown_backend_raises(graph10):
    g = graph10
    with pytest.raises(ValueError, match="unknown backend"):
        partition(g.src, g.dst, g.num_vertices, CLUGPConfig(k=4),
                  backend="cuda")


def test_unknown_kernel_raises(graph10):
    g = graph10
    with pytest.raises(ValueError, match="unknown game kernel"):
        partition(g.src, g.dst, g.num_vertices,
            CLUGPConfig(k=4, kernel="mxu"), backend="jit")


def test_empty_stream_raises_every_backend():
    empty = np.zeros(0, dtype=np.int64)
    for backend in ("np", "jit", "sharded"):
        with pytest.raises(ValueError, match="empty"):
            partition(empty, empty, 10, CLUGPConfig(k=4), backend=backend)


# ------------------------------------------------- np ↔ jit bit equivalence

def test_jit_clustering_labels_bit_identical(graph10):
    """Pass 1 parity: the fused jit pipeline's compacted labels equal the
    host oracle's exactly (same raw-id creation order, same compaction)."""
    g = graph10
    cfg = CLUGPConfig(k=8)
    r_np = partition(g.src, g.dst, g.num_vertices, cfg, backend="np")
    r_jit = partition(g.src, g.dst, g.num_vertices, cfg, backend="jit")
    np.testing.assert_array_equal(r_np.clustering.clu, r_jit.clustering.clu)
    np.testing.assert_array_equal(r_np.clustering.deg, r_jit.clustering.deg)
    np.testing.assert_array_equal(r_np.clustering.divided,
                                  r_jit.clustering.divided)
    assert r_np.clustering.num_clusters == r_jit.clustering.num_clusters


def test_jit_nogame_pipeline_bit_identical(graph10):
    """With the deterministic greedy game the WHOLE pipeline (clustering →
    greedy → transform → restream) is bit-identical np ↔ jit."""
    g = graph10
    cfg = CLUGPConfig(k=8, game=False, restream=1)
    a_np = partition(g.src, g.dst, g.num_vertices, cfg, backend="np").assign
    a_jit = partition(g.src, g.dst, g.num_vertices, cfg,
                      backend="jit").assign
    np.testing.assert_array_equal(a_np, a_jit)


def test_jit_game_rf_close_to_np(graph10):
    """Game RNG/sweep schedules differ, so quality (not bits) must match:
    RF within 10% of the host oracle."""
    g = graph10
    cfg = CLUGPConfig(k=8)
    rf_np = partition(g.src, g.dst, g.num_vertices, cfg,
                      backend="np").stats["rf"]
    rf_jit = partition(g.src, g.dst, g.num_vertices, cfg,
                       backend="jit").stats["rf"]
    assert rf_jit <= rf_np * 1.10


def test_jit_pallas_kernel_path(graph10):
    """The Pallas batched-Jacobi game (interpret mode on CPU) produces a
    valid partition of comparable quality."""
    g = graph10
    cfg = CLUGPConfig(k=8, kernel="pallas")
    res = partition(g.src, g.dst, g.num_vertices, cfg, backend="jit")
    assert res.assign.shape == (g.num_edges,)
    assert res.assign.min() >= 0 and res.assign.max() < 8
    rf_np = partition(g.src, g.dst, g.num_vertices, CLUGPConfig(k=8),
                      backend="np").stats["rf"]
    assert res.stats["rf"] <= rf_np * 1.25


def test_jit_balance_cap_respected(graph10):
    g = graph10
    for tau in (1.0, 1.5):
        res = partition(g.src, g.dst, g.num_vertices,
                  CLUGPConfig(k=8, tau=tau), backend="jit")
        sizes = np.bincount(res.assign, minlength=8)
        assert sizes.max() <= int(np.ceil(tau * g.num_edges / 8)) + 1


@pytest.fixture(scope="module")
def graph9():
    return web_graph(scale=9, edge_factor=6, seed=5)


@pytest.mark.parametrize("profile", ["paper", "optimized"])
@pytest.mark.parametrize("backend", ["np", "jit"])
@pytest.mark.parametrize("k", [2, 4, 8, 16, 32, 64])
def test_partition_invariants_across_k(graph9, k, backend, profile):
    """Every edge lands in one part of [0, k); no part holds more than
    ⌈τ·|E|/k⌉ + 1 edges; the reported RF is the host's recount.  k = 16
    is the benchmark cells' cut, 64 the paper's large-k regime."""
    g = graph9
    cfg = getattr(CLUGPConfig, profile)(k)
    res = partition(g.src, g.dst, g.num_vertices, cfg, backend=backend)
    assign = np.asarray(res.assign)
    assert assign.shape == (g.num_edges,)
    assert assign.min() >= 0 and assign.max() < k
    loads = np.bincount(assign, minlength=k)
    assert loads.max() <= int(np.ceil(cfg.tau * g.num_edges / k)) + 1
    replicas = sum(np.unique(np.concatenate([g.src[assign == p],
                                             g.dst[assign == p]])).size
                   for p in range(k))
    assert res.stats["rf"] == replicas / g.num_vertices


def test_cluster_csr_rejects_int32_overflow():
    """Backstop for the games' int32 pair-key space: above ~46k clusters
    the builder must refuse (the partitioner's games play on the raw
    cross-edge list there and never call it)."""
    import jax.numpy as jnp

    from repro.core.game import jax_cluster_csr

    xs = jnp.zeros((4,), jnp.int32)
    with pytest.raises(ValueError, match="overflows the int32"):
        jax_cluster_csr(xs, xs, 65536, 64)


# --------------------------------------------- the game's cluster-pair list

def _cross_list(seed, n, m_cap, live, n_distinct):
    """A padded cross-edge list: ``live`` lanes drawn from ``n_distinct``
    ordered cluster pairs (so pairs repeat, in both directions), the rest
    the drop sentinel ``m_cap``."""
    rng = np.random.default_rng(seed)
    m = min(m_cap, 64)
    pool = rng.integers(0, m, size=(n_distinct, 2))
    pool = pool[pool[:, 0] != pool[:, 1]]
    xs = np.full(n, m_cap, np.int32)
    xd = np.full(n, m_cap, np.int32)
    lanes = rng.choice(n, size=live, replace=False)
    pick = pool[rng.integers(0, len(pool), size=live)]
    xs[lanes], xd[lanes] = pick[:, 0], pick[:, 1]
    return xs, xd


def _pairs_reference(xs, xd, m_cap, nnz_cap):
    """``np.unique`` over the symmetric keys of the live lanes."""
    ok = (xs < m_cap) & (xd < m_cap)
    s, d = xs[ok].astype(np.int64), xd[ok].astype(np.int64)
    keys, counts = np.unique(np.concatenate([s * m_cap + d, d * m_cap + s]),
                             return_counts=True)
    row = np.full(nnz_cap, m_cap, np.int64)
    col = np.zeros(nnz_cap, np.int64)
    w = np.zeros(nnz_cap, np.float32)
    n = min(len(keys), nnz_cap)
    row[:n], col[:n], w[:n] = keys[:n] // m_cap, keys[:n] % m_cap, counts[:n]
    return row, col, w, len(keys)


@pytest.mark.parametrize("n,live,n_distinct,nnz_cap", [
    (4096, 1500, 300, 1024),    # sentinel padding, repeated pairs
    (4096, 4096, 300, 1024),    # every lane live
    (4096, 0, 300, 1024),       # every lane padding
    (4096, 1500, 3000, 256),    # more distinct pairs than lanes: overflow
    (64, 40, 8, 1024),          # fewer key lanes than nnz_cap + 1
], ids=["sentinel", "all-live", "all-pad", "overflow", "short"])
def test_cluster_csr_matches_unique_reference(n, live, n_distinct, nnz_cap):
    """The pair-list builder's (row, col, w) and distinct-pair count equal
    an ``np.unique`` reference; past ``nnz_cap`` it keeps the first pairs
    in key order and its count reports the overflow."""
    from repro.core.game import jax_cluster_csr
    m_cap = 256
    xs, xd = _cross_list(7, n, m_cap, live, n_distinct)
    row, col, w, n_pairs = jax_cluster_csr(xs, xd, m_cap, nnz_cap)
    want = _pairs_reference(xs, xd, m_cap, nnz_cap)
    np.testing.assert_array_equal(np.asarray(row), want[0])
    np.testing.assert_array_equal(np.asarray(col), want[1])
    np.testing.assert_array_equal(np.asarray(w), want[2])
    assert int(n_pairs) == want[3]
    assert (int(n_pairs) > nnz_cap) == (n_distinct == 3000)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_pair_list_cut_mass_equals_raw_list(seed):
    """The cut-mass table from the aggregated pair list equals the one
    from the raw cross-edge list to the bit (and a dense reference), on
    padded lists whose pairs repeat."""
    from repro.core.game import cut_mass, jax_cluster_csr, raw_cluster_pairs
    m_cap, k = 128, 8
    xs, xd = _cross_list(seed, 8192, m_cap, 5000, 400)
    assign = np.random.default_rng(seed).integers(0, k, m_cap).astype(
        np.int32)
    row, col, w, n_pairs = jax_cluster_csr(xs, xd, m_cap, 4096)
    assert int(n_pairs) <= 4096
    pairs = np.asarray(cut_mass(row, col, w, assign, k))
    raw = np.asarray(cut_mass(*raw_cluster_pairs(xs, xd), assign, k))
    np.testing.assert_array_equal(pairs, raw)
    want = np.zeros((m_cap, k), np.float32)
    ok = xs < m_cap
    np.add.at(want, (xs[ok], assign[xd[ok]]), 1.0)
    np.add.at(want, (xd[ok], assign[xs[ok]]), 1.0)
    np.testing.assert_array_equal(pairs, want)


@pytest.mark.parametrize("m_cap,want", [(256, "pairs"), (46340, "pairs"),
                                        (46341, "edges")])
def test_game_list_follows_the_int32_key_limit(m_cap, want):
    from repro.core.stages import game_list
    assert game_list(CLUGPConfig(k=4), m_cap) == want
    assert game_list(CLUGPConfig(k=4, game=False), m_cap) == "none"


@pytest.mark.parametrize("kernel", ["xla", "pallas"])
def test_jit_game_on_pair_list_matches_raw_list(graph10, kernel):
    """The jit backend's batched-Jacobi game (pallas in interpret mode on
    the CPU) plays on the pair list at graph10's m_cap; fed the raw list
    directly, ``jax_game_rounds`` gives the same assignment and rounds."""
    import jax

    from repro.core import partitioner as P
    from repro.core import stages as S
    from repro.core.game import jax_game_rounds, raw_cluster_pairs
    g = graph10
    cfg = CLUGPConfig(k=8, kernel=kernel)
    res = partition(g.src, g.dst, g.num_vertices, cfg, backend="jit")
    assert res.stats["game_list"] == "pairs"
    assert 0 < res.stats["game_pairs"]
    caps = P._init_caps(g.num_vertices, g.num_edges)
    ctx = S.StageCtx(num_vertices=g.num_vertices,
                     vmax=float(P._resolve_vmax(cfg, g.num_edges)),
                     game_mode=kernel, id_cap=caps.id_cap,
                     m_cap=caps.m_cap, nnz_cap=caps.nnz_cap)

    @jax.jit
    def raw_game(src, dst):
        cstate = S.JAX_STAGES.cluster(src, dst, ctx, cfg)
        gs = S.JAX_STAGES.contract(src, dst, cstate, ctx, cfg)
        lam = S.lambda_jax(gs.sizes.sum(), gs.n_cross, cfg.k,
                           cfg.relative_weight)
        return jax_game_rounds(
            *raw_cluster_pairs(gs.xs, gs.xd), gs.sizes, gs.row_tot, cfg.k,
            lam, batch_size=cfg.batch_size, max_rounds=cfg.max_rounds,
            seed=cfg.seed, use_pallas=kernel == "pallas")

    assign, rounds = raw_game(g.src.astype(np.int32),
                              g.dst.astype(np.int32))
    m = res.stats["num_clusters"]
    np.testing.assert_array_equal(np.asarray(assign)[:m], res.cluster_assign)
    assert int(rounds) == res.stats["game_rounds"]


@pytest.mark.parametrize("kernel", ["xla", "pallas"])
def test_jit_game_pair_overflow_retries(graph10, monkeypatch, kernel):
    """A pair list past ``nnz_cap`` makes the partitioner run its body
    again with the cap doubled; the second attempt's partition is the
    clean run's, bit for bit."""
    import time

    from repro import obs
    from repro.core import partitioner as P
    g = graph10
    cfg = CLUGPConfig(k=8, kernel=kernel)
    clean = partition(g.src, g.dst, g.num_vertices, cfg, backend="jit")
    pairs = clean.stats["game_pairs"]
    init = P._init_caps
    monkeypatch.setattr(P, "_init_caps", lambda v, e: init(v, e)._replace(
        nnz_cap=pairs - 1))
    t0 = time.perf_counter()
    res = partition(g.src, g.dst, g.num_vertices, cfg, backend="jit")
    attempts = [r[4] for r in obs.spans(t0) if r[0] == "partition.attempt"]
    assert [(a["attempt"], a["nnz_cap"], a["game_list"])
            for a in attempts] == [(0, pairs - 1, "pairs"),
                                   (1, 2 * pairs - 2, "pairs")]
    np.testing.assert_array_equal(res.assign, clean.assign)
    np.testing.assert_array_equal(res.cluster_assign, clean.cluster_assign)
    assert res.stats["game_rounds"] == clean.stats["game_rounds"]
    assert (res.stats["game_list"], res.stats["game_pairs"]) == (
        "pairs", pairs)


def test_jit_tiny_stream_with_self_loops_bit_identical():
    """Regression: self-loop edges of clustered vertices count toward
    their cluster's intra size in ``contract`` — the in-graph contraction
    must match (it once dropped them and diverged on greedy ties)."""
    src = np.array([0, 1, 2, 2, 3], dtype=np.int64)
    dst = np.array([1, 2, 2, 3, 0], dtype=np.int64)
    cfg = CLUGPConfig(k=2, game=False, restream=1)
    a_np = partition(src, dst, 5, cfg, backend="np").assign
    a_jit = partition(src, dst, 5, cfg, backend="jit").assign
    np.testing.assert_array_equal(a_np, a_jit)


# --------------------------------------------------------------- restream

def test_restream_strictly_improves_rf(graph10):
    """Regression for the PR's restreaming claim: one prioritized
    restream pass strictly cuts RF on the scale-10 web graph."""
    g = graph10
    base = partition(g.src, g.dst, g.num_vertices, CLUGPConfig(k=8),
                     backend="np")
    once = partition(g.src, g.dst, g.num_vertices,
               CLUGPConfig(k=8, restream=1), backend="np")
    assert once.stats["rf"] < base.stats["rf"]
    trace = once.stats["restream_rf_trace"]
    assert len(trace) == 2 and trace[1] < trace[0]


def test_restream_improves_jit_too(graph10):
    g = graph10
    base = partition(g.src, g.dst, g.num_vertices, CLUGPConfig(k=8),
                     backend="jit")
    once = partition(g.src, g.dst, g.num_vertices,
               CLUGPConfig(k=8, restream=1), backend="jit")
    assert once.stats["rf"] < base.stats["rf"]


# --------------------------------------------------- compile-once k-sweep

def test_sweep_matches_per_k_jit_bitwise(graph10):
    """The stacked k-sweep (every k under ONE ``lax.scan`` body, lanes
    padded to k_max with a traced live count) must reproduce the per-k
    jit backend BIT-FOR-BIT at every k — dead-lane masking may never
    leak into a live partition's argmin, λ, or balance cap."""
    g = graph10
    ks = (4, 8)
    cfg = CLUGPConfig(k=ks[-1])
    results = partition_sweep(g.src, g.dst, g.num_vertices, cfg, ks)
    for k, res in zip(ks, results):
        ref = partition(g.src, g.dst, g.num_vertices,
                        dataclasses.replace(cfg, k=k), backend="jit")
        np.testing.assert_array_equal(res.assign, ref.assign,
                                      err_msg=f"k={k}")
        assert res.assign.min() >= 0 and res.assign.max() < k
        assert res.stats["rf"] == ref.stats["rf"]
        assert res.stats["sweep"] and res.stats["k_max"] == ks[-1]
        assert (res.stats["game_list"], res.stats["game_pairs"]) == (
            ref.stats["game_list"], ref.stats["game_pairs"])


def test_sweep_repeat_adds_zero_compiles(graph10):
    """Compile-once contract: a warm repeat of the sweep (same stream
    shape, same ks) reuses the cached executable — the traced k_real /
    vmax inputs keep per-k variation out of the jit cache key."""
    g = graph10
    cfg = CLUGPConfig(k=8)
    partition_sweep(g.src, g.dst, g.num_vertices, cfg, (4, 8))
    before = sweep_trace_count()
    again = partition_sweep(g.src, g.dst, g.num_vertices, cfg, (4, 8))
    assert sweep_trace_count() == before
    assert len(again) == 2


def test_sweep_validates_ks(graph10):
    g = graph10
    for bad in ((), (0, 4), (-1,)):
        with pytest.raises(ValueError, match="at least one k"):
            partition_sweep(g.src, g.dst, g.num_vertices,
                            CLUGPConfig(k=4), bad)


# ------------------------------------------------------- np nodes combine

def test_np_nodes_combine_honest_stats(graph10):
    """Satellite regression: the merged result no longer masquerades the
    last node's clustering as global state — per-node summaries are
    explicit and the cluster count sums private id spaces."""
    g = graph10
    res = partition(g.src, g.dst, g.num_vertices, CLUGPConfig(k=8),
                    backend="np", nodes=3)
    assert res.clustering is None and res.cluster_graph is None
    per_node = res.stats["per_node"]
    assert len(per_node) == 3
    assert res.stats["num_clusters"] == sum(n["clusters"] for n in per_node)
    assert res.stats["nodes"] == 3
    assert sum(n["edges"] for n in per_node) == g.num_edges


def test_np_nodes_kwarg_combines(graph10):
    g = graph10
    res = partition(g.src, g.dst, g.num_vertices, CLUGPConfig(k=8),
                    nodes=4)
    assert res.assign.shape == (g.num_edges,)
    assert res.stats["nodes"] == 4


def test_np_nodes_restream_improves(graph10):
    g = graph10
    base = partition(g.src, g.dst, g.num_vertices, CLUGPConfig(k=8),
                     backend="np", nodes=4)
    once = partition(g.src, g.dst, g.num_vertices,
               CLUGPConfig(k=8, restream=1), backend="np", nodes=4)
    assert once.stats["rf"] < base.stats["rf"]


# ------------------------------------------------------- device residency

def test_build_layout_accepts_device_resident_assignment(graph10):
    """partition → build_layout without a host round-trip: jax arrays go
    straight in and every table matches the np-input build."""
    import jax.numpy as jnp

    from repro.graph import build_layout

    g = graph10
    res = partition(g.src, g.dst, g.num_vertices, CLUGPConfig(k=4),
                    backend="jit")
    lay_np = build_layout(g.src, g.dst, res.assign, g.num_vertices, 4)
    lay_dev = build_layout(jnp.asarray(g.src), jnp.asarray(g.dst),
                           jnp.asarray(res.assign), g.num_vertices, 4)
    for f in ("edge_src", "edge_dst", "vert_gid", "is_master", "owner",
              "own_slot", "halo_send", "halo_recv"):
        np.testing.assert_array_equal(getattr(lay_np, f),
                                      getattr(lay_dev, f))


# ------------------------------------------------------- sharded (8 dev)

SHARDED_CODE = """
import numpy as np
from repro.core import CLUGPConfig, partition, web_graph

g = web_graph(scale=10, edge_factor=6, seed=3)
k, nodes = 8, 4
cfg = CLUGPConfig(k=k, restream=1)
r_np = partition(g.src, g.dst, g.num_vertices, cfg, backend="np",
           nodes=nodes)
r_sh = partition(g.src, g.dst, g.num_vertices, cfg, backend="sharded",
           nodes=nodes)
assert r_sh.assign.shape == (g.num_edges,)
assert r_sh.assign.min() >= 0 and r_sh.assign.max() < k
# balance: every device respects its slice cap, so the global cap holds
assert r_sh.stats["balance"] <= cfg.tau + 0.05, r_sh.stats["balance"]
# quality within 10% of the same-split-width host combine
assert r_sh.stats["rf"] <= r_np.stats["rf"] * 1.10, (
    r_sh.stats["rf"], r_np.stats["rf"])
# honest merged stats: private-id-space cluster counts per node
assert len(r_sh.stats["per_node"]) == nodes
assert r_sh.stats["num_clusters"] == sum(
    n["clusters"] for n in r_sh.stats["per_node"])
# each device's game plays on its own pair list; the stat sums them
assert r_sh.stats["game_list"] == "pairs" and r_sh.stats["game_pairs"] > 0
# greedy path is bit-identical to the host combine on every device
cfg_g = CLUGPConfig(k=k, game=False)
a_np = partition(g.src, g.dst, g.num_vertices, cfg_g, backend="np",
           nodes=nodes).assign
a_sh = partition(g.src, g.dst, g.num_vertices, cfg_g, backend="sharded",
           nodes=nodes).assign
np.testing.assert_array_equal(a_np, a_sh)
print("SHARDED_OK", r_sh.stats["rf"])
"""


@pytest.mark.multidevice
def test_sharded_backend_multidevice(multidevice):
    out = multidevice(SHARDED_CODE, n_devices=8)
    assert "SHARDED_OK" in out


def test_sharded_raises_without_devices(graph10):
    g = graph10
    with pytest.raises(RuntimeError, match="devices"):
        partition(g.src, g.dst, g.num_vertices, CLUGPConfig(k=4),
                  backend="sharded", nodes=64)
