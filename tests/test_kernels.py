"""The partitioner's Pallas kernels (interpret=True) vs their oracles."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels import ops, ref


# ------------------------------------------------------------ game BR

@pytest.mark.parametrize("M,kpad,k", [(256, 128, 16), (512, 128, 128),
                                      (256, 256, 200)])
def test_game_bestresponse_matches_ref(M, kpad, k):
    rng = np.random.default_rng(0)
    aff = jnp.asarray(rng.random((M, kpad)) * 10, jnp.float32)
    sizes = jnp.asarray(rng.integers(1, 50, M), jnp.float32)
    row_tot = jnp.asarray(aff.sum(1) + rng.random(M), jnp.float32)
    cur = jnp.asarray(rng.integers(0, k, M), jnp.int32)
    loads = jnp.asarray(rng.random(kpad) * 100, jnp.float32)
    got_b, got_c = ops.game_best_response(aff, sizes, row_tot, cur, loads,
                                          lam=2.5, k=k, block_m=128,
                                          interpret=True)
    want_b, want_c = ref.game_bestresponse_ref(aff, sizes, row_tot, cur,
                                               loads, lam=2.5, k=k)
    np.testing.assert_array_equal(np.asarray(got_b), np.asarray(want_b))
    np.testing.assert_allclose(np.asarray(got_c), np.asarray(want_c),
                               rtol=1e-5)


def test_game_kernel_agrees_with_host_game_step():
    """Kernel best responses == the numpy Gauss–Seidel step's choices under
    a frozen snapshot (Jacobi semantics)."""
    from repro.core import web_graph, streaming_clustering_np, contract, \
        default_vmax, lambda_max
    g = web_graph(scale=9, edge_factor=6, seed=0)
    k = 8
    clus = streaming_clustering_np(g.src, g.dst, g.num_vertices,
                                   default_vmax(g.num_edges, k))
    cg = contract(g.src, g.dst, clus.clu)
    m = cg.m
    mpad = -(-m // 128) * 128
    kpad = 128
    lam = lambda_max(cg, k)
    rng = np.random.default_rng(1)
    assign = rng.integers(0, k, m)
    S = cg.adj.toarray().astype(np.float32)
    onehot = np.eye(k, dtype=np.float32)[assign]
    aff = S @ onehot                                      # (m, k)
    sizes = cg.sizes.astype(np.float32)
    row_tot = S.sum(1)
    loads = np.bincount(assign, weights=sizes, minlength=k)

    aff_p = np.zeros((mpad, kpad), np.float32)
    aff_p[:m, :k] = aff
    sz_p = np.zeros(mpad, np.float32); sz_p[:m] = sizes
    rt_p = np.zeros(mpad, np.float32); rt_p[:m] = row_tot
    cur_p = np.zeros(mpad, np.int32); cur_p[:m] = assign
    ld_p = np.zeros(kpad, np.float32); ld_p[:k] = loads

    got_b, _ = ops.game_best_response(
        jnp.asarray(aff_p), jnp.asarray(sz_p), jnp.asarray(rt_p),
        jnp.asarray(cur_p), jnp.asarray(ld_p), lam=float(lam), k=k,
        block_m=128, interpret=True)
    # oracle: same Jacobi snapshot cost in numpy
    ar = np.arange(k)
    for i in rng.choice(m, size=32, replace=False):
        loads_ex = loads - sizes[i] * (ar == assign[i])
        cost = (lam / k) * sizes[i] * (loads_ex + sizes[i]) \
            + 0.5 * (row_tot[i] - aff[i])
        assert int(got_b[i]) == int(np.argmin(cost))


# ----------------------------------------------------- cluster scatter

def _cluster_block_inputs(seed, B=128, sdf=0.0):
    """A localized clustering block with realistic slot aliasing: random
    vertex slots in [0, 2B), some dead lanes, a mid-stream table state."""
    rng = np.random.default_rng(seed)
    lu = rng.integers(0, 2 * B, B).astype(np.int32)
    lv = rng.integers(0, 2 * B, B).astype(np.int32)
    live = (rng.random(B) > 0.1).astype(np.int32)
    lv = np.where(live == 1, lv, lu)          # dead lanes alias u == v
    ints = np.stack([lu, lv, live], 1)
    buf = np.full(10 * B, -1, np.int32)
    buf[2 * B:4 * B] = rng.integers(0, 6, 2 * B)
    buf[4 * B:10 * B] = 0
    # pre-cluster a third of the slots into a few existing local clusters
    pre = rng.choice(2 * B, 2 * B // 3, replace=False)
    cl = rng.integers(2 * B, 2 * B + 16, pre.size)
    buf[pre] = cl
    np.add.at(buf, 2 * B + cl, rng.integers(1, 8, pre.size))
    scal = np.array([16, 0, pre.size, int(buf[2*B:4*B].sum())], np.int32)
    return jnp.asarray(ints), jnp.asarray(buf), jnp.asarray(scal)


def _cluster_scan_ref(ints, buf, scal, vmax, allow_split, sdf):
    """Oracle: the XLA inner scan (`.at[].add` fused scatter) over the
    same `edge_decisions` math."""
    from functools import partial
    from repro.core.clustering import _edge_step_local
    B = ints.shape[0]
    step = partial(_edge_step_local, vmax=jnp.float32(vmax),
                   allow_split=allow_split, split_degree_factor=sdf, B=B)
    (buf2, nid, nid0, sv, sd), fires = jax.lax.scan(
        step, (buf, scal[0], scal[1], scal[2], scal[3]), ints)
    return buf2, jnp.stack([nid, nid0, sv, sd]), fires


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("sdf", [0.0, 4.0])
def test_cluster_scatter_matches_xla_scan(seed, sdf):
    ints, buf, scal = _cluster_block_inputs(seed, sdf=sdf)
    vmax = 12.5
    got_buf, got_scal, got_pk = ops.cluster_scatter(
        ints, buf, scal, vmax, allow_split=True, split_degree_factor=sdf,
        interpret=True)
    want_buf, want_scal, want_pk = _cluster_scan_ref(
        ints, buf, scal, vmax, True, sdf)
    np.testing.assert_array_equal(np.asarray(got_buf), np.asarray(want_buf))
    np.testing.assert_array_equal(np.asarray(got_scal), np.asarray(want_scal))
    np.testing.assert_array_equal(np.asarray(got_pk), np.asarray(want_pk))


def test_cluster_scatter_no_split_matches_xla_scan():
    ints, buf, scal = _cluster_block_inputs(7)
    got = ops.cluster_scatter(ints, buf, scal, 9.0, allow_split=False,
                              interpret=True)
    want = _cluster_scan_ref(ints, buf, scal, 9.0, False, 0.0)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_cluster_kernel_full_stream_matches_xla():
    """Whole clustering pass (block localization + carry across blocks)
    is bit-identical between the Pallas strategy and the XLA scan."""
    from repro.core import web_graph
    from repro.core.clustering import streaming_clustering_jax, default_vmax
    g = web_graph(scale=10, edge_factor=5, seed=4)
    vmax = default_vmax(g.num_edges, 8)
    for sdf in (0.0, 4.0):
        outs = {}
        for kern in ("xla", "pallas"):
            outs[kern] = streaming_clustering_jax(
                g.src, g.dst, g.num_vertices, vmax,
                split_degree_factor=sdf, kernel=kern, interpret=True)
        for a, b in zip(outs["xla"], outs["pallas"]):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ------------------------------------------------- greedy transform

@pytest.mark.parametrize("cap,dead", [
    (1.1, 0.0),     # the usual τ: few partitions fill
    (0.3, 0.0),     # every partition fills: the least-loaded fallback
    (1.1, 0.25),    # dead (padding) lanes, as on the sharded backend
])
def test_greedy_transform_matches_scan(cap, dead):
    """The Alg. 1 kernel (what a TPU runs) equals the lax.scan (what
    every other platform runs) bit for bit, across several edge blocks
    whose load table carries from block to block."""
    from repro.core.transform import _transform_scan
    from repro.kernels.greedy_transform import BLOCK, greedy_transform
    rng = np.random.default_rng(0)
    E, k = 2 * BLOCK + 517, 8
    cols = [rng.integers(0, k, E), rng.integers(0, k, E),
            rng.integers(1, 40, E), rng.integers(1, 40, E),
            rng.integers(0, 2, E), rng.integers(0, 2, E),
            (rng.random(E) >= dead).astype(np.int64)]
    cols = [jnp.asarray(c, jnp.int32) for c in cols]
    lmax = jnp.float32(cap * E / k)
    got = greedy_transform(*cols, lmax, k=k, interpret=True)
    want = _transform_scan(*cols, lmax, k=k)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_resolve_cluster_kernel():
    from repro.core.stages import resolve_cluster_kernel
    assert resolve_cluster_kernel("pallas") == "pallas"
    assert resolve_cluster_kernel("xla") == "xla"
    assert resolve_cluster_kernel("auto") in ("pallas", "xla")
    with pytest.raises(ValueError):
        resolve_cluster_kernel("scan")


def test_partition_cluster_kernel_bit_identical():
    """cluster_kernel='pallas' flows through CLUGPConfig → jit backend and
    lands the exact same assignment as the XLA scatter path."""
    from repro.core.partitioner import partition
    from repro.core.pipeline import CLUGPConfig
    from repro.core import web_graph
    g = web_graph(scale=9, edge_factor=5, seed=2)
    res = {}
    for kern in ("xla", "pallas"):
        r = partition(g.src, g.dst, g.num_vertices,
                      CLUGPConfig(k=4, cluster_kernel=kern), backend="jit")
        res[kern] = r.assign
    np.testing.assert_array_equal(res["xla"], res["pallas"])
