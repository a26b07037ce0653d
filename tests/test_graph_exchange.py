"""Exchange-layer tests: vectorized build_layout vs the retained reference
builder, halo routing-table invariants, and halo-vs-dense engine
equivalence on random graphs under 8 virtual (stacked) devices."""
import dataclasses

import numpy as np
import pytest

from repro.core import CLUGPConfig, partition
from repro.core.graphgen import web_graph
from repro.graph import (build_layout, build_layout_reference,
                         reference_cc, reference_pagerank, simulate_cc,
                         simulate_pagerank)

from conftest import random_graph_and_assign as _random_graph_and_assign


# ------------------------------------------------------- layout equivalence

# k = 16 is the benchmark cells' cut; 32 and 64 the paper's larger ones
@pytest.mark.parametrize("seed,k", [(0, 2), (1, 4), (2, 8), (3, 7), (4, 16),
                                    (5, 32), (6, 64), (7, 16)])
def test_vectorized_layout_matches_reference(seed, k):
    src, dst, n, assign = _random_graph_and_assign(seed, k)
    vec = build_layout(src, dst, assign, n, k)
    ref = build_layout_reference(src, dst, assign, n, k)
    for f in dataclasses.fields(vec):
        a, b = getattr(vec, f.name), getattr(ref, f.name)
        if isinstance(a, np.ndarray):
            np.testing.assert_array_equal(a, b, err_msg=f.name)
        else:
            assert a == b, (f.name, a, b)


@pytest.mark.parametrize("k", [4, 32])
def test_vectorized_layout_matches_reference_on_sorted_keys(k):
    """Past k·V = 2^25 the builder finds (partition, vertex) keys by
    sorting, not in a dense table: the same tables either way."""
    src, dst, n, assign = _random_graph_and_assign(5, k)
    big = (1 << 25) // k + 1                 # untouched ids above n
    vec = build_layout(src, dst, assign, big, k)
    ref = build_layout_reference(src, dst, assign, big, k)
    for f in dataclasses.fields(vec):
        a, b = getattr(vec, f.name), getattr(ref, f.name)
        if isinstance(a, np.ndarray):
            np.testing.assert_array_equal(a, b, err_msg=f.name)
        else:
            assert a == b, (f.name, a, b)


def test_vectorized_layout_matches_reference_on_partition():
    g = web_graph(scale=9, edge_factor=6, seed=1)
    k = 8
    res = partition(g.src, g.dst, g.num_vertices,
                    CLUGPConfig.optimized(k))
    vec = build_layout(g.src, g.dst, res.assign, g.num_vertices, k)
    ref = build_layout_reference(g.src, g.dst, res.assign,
                                 g.num_vertices, k)
    for f in dataclasses.fields(vec):
        a, b = getattr(vec, f.name), getattr(ref, f.name)
        if isinstance(a, np.ndarray):
            np.testing.assert_array_equal(a, b, err_msg=f.name)
        else:
            assert a == b, (f.name, a, b)


def test_layout_sparse_lookup_path_matches_dense():
    """The searchsorted fallback (k·V over the dense-map budget) produces
    the same tables as the dense inverse map: same edges/assignment, but an
    id space big enough that k·V exceeds 1<<25."""
    src, dst, n, assign = _random_graph_and_assign(7, 4, n=120)
    dense = build_layout(src, dst, assign, n, 4)
    big_n = (1 << 25) // 4 + 1
    sparse = build_layout(src, dst, assign, big_n, 4)
    for f in ("edge_src", "edge_dst", "edge_mask", "is_master",
              "own_slot", "halo_send", "halo_recv"):
        np.testing.assert_array_equal(getattr(dense, f),
                                      getattr(sparse, f), err_msg=f)
    np.testing.assert_array_equal(
        dense.vert_gid[dense.vert_mask], sparse.vert_gid[sparse.vert_mask])
    assert dense.mirrors_total == sparse.mirrors_total


# ------------------------------------------------- routing-table invariants

@pytest.mark.parametrize("seed,k", [(0, 4), (5, 8)])
def test_halo_routing_invariants(seed, k):
    src, dst, n, assign = _random_graph_and_assign(seed, k)
    lay = build_layout(src, dst, assign, n, k)
    pad = lay.l_max
    valid_send = lay.halo_send != pad
    valid_recv = lay.halo_recv != pad

    # send/recv lanes pair up exactly: lane (p,q,h) is populated on the
    # sender iff (q,p,h) is populated on the receiver
    np.testing.assert_array_equal(
        valid_send, np.swapaxes(valid_recv, 0, 1))

    # every mirror slot is routed exactly once, and only mirror slots are
    mirror_slots = lay.vert_mask & ~lay.is_master
    for p in range(k):
        sent = lay.halo_send[p][valid_send[p]]
        assert len(sent) == len(set(sent.tolist())), "duplicate send lane"
        np.testing.assert_array_equal(
            np.sort(sent), np.flatnonzero(mirror_slots[p]))
        # no device sends to itself
        assert not valid_send[p, p].any()

    # total routed lanes == mirror count; pads vanish from the count
    assert int(valid_send.sum()) == lay.mirrors_total

    # each lane references the same vertex on both endpoints, and the recv
    # side lands on a master slot of that vertex's owner
    for p in range(k):
        for q in range(k):
            for h in np.flatnonzero(valid_send[p, q]):
                s_slot = lay.halo_send[p, q, h]
                r_slot = lay.halo_recv[q, p, h]
                gid = lay.vert_gid[p, s_slot]
                assert lay.vert_gid[q, r_slot] == gid
                assert lay.is_master[q, r_slot]
                assert lay.owner[p, s_slot] == q


def test_comm_model_halo_between_ideal_and_dense():
    g = web_graph(scale=10, edge_factor=8, seed=0)
    k = 8
    res = partition(g.src, g.dst, g.num_vertices,
                    CLUGPConfig.optimized(k))
    lay = build_layout(g.src, g.dst, res.assign, g.num_vertices, k)
    # every mirror has exactly one lane, so the ragged ideal bounds the
    # padded halo volume from below, and the halo volume undercuts the
    # dense k²·L_max slab on any real partition
    assert lay.comm_bytes("ideal") <= lay.comm_bytes("halo")
    assert lay.comm_bytes("halo") < lay.comm_bytes("dense")


@pytest.mark.parametrize("exchange,m,expect", [
    ("halo", 1, lambda lay: lay.comm_bytes("halo") // 8),
    ("halo", 2, lambda lay: 2 * 2 * (8 - 2) * lay.h_max * 4),
    ("quantized", 4, lambda lay: 2 * 4 * (8 - 4) * lay.h_max * 4),
    ("dense", 2, lambda lay: 2 * (8 - 2) * lay.l_max * 4),
    ("dense", 8, lambda lay: 0),
    ("ragged", 1, lambda lay: lay.comm_bytes("ragged") // 8),
    ("ragged", 2, None),
    ("ragged_quantized", 1,
     lambda lay: lay.comm_bytes("ragged_quantized") // 8),
    ("halo", 3, None),
])
def test_comm_model_per_chip(exchange, m, expect):
    """``comm_bytes(parts_per_device=m)``: what one chip sends to the
    others.  The halo and dense wires hold any m dividing k and keep the
    lanes between a chip's own partitions off the wire; the other wires
    route one partition a chip (1/k of the whole wire) and refuse more.
    The quantized wire's exact payloads (``lossy=False``) ride the halo
    wire at any m."""
    src, dst, n, assign = _random_graph_and_assign(2, 8)
    lay = build_layout(src, dst, assign, n, 8)
    lossy = exchange != "quantized"
    if expect is None:
        with pytest.raises(ValueError):
            lay.comm_bytes(exchange, parts_per_device=m)
        return
    assert lay.comm_bytes(exchange, parts_per_device=m,
                          lossy=lossy) == expect(lay)
    assert lay.comm_bytes(exchange, parts_per_device=m, lossy=lossy,
                          programs=3) == 3 * expect(lay)


# ------------------------------------------------- halo vs dense equivalence

@pytest.mark.parametrize("seed", [0, 1])
def test_simulated_pagerank_halo_matches_dense_and_reference(seed):
    src, dst, n, assign = _random_graph_and_assign(seed, 8, n=400)
    lay = build_layout(src, dst, assign, n, 8)
    ref = reference_pagerank(src, dst, n, iters=25)
    pr_dense = simulate_pagerank(lay, iters=25, exchange="dense")
    pr_halo = simulate_pagerank(lay, iters=25, exchange="halo")
    assert np.abs(pr_dense - ref).max() < 1e-6
    assert np.abs(pr_halo - ref).max() < 1e-6
    assert np.abs(pr_halo - pr_dense).max() < 1e-6


@pytest.mark.parametrize("seed", [0, 1])
def test_simulated_cc_halo_matches_dense_and_reference(seed):
    src, dst, n, assign = _random_graph_and_assign(seed, 8, n=400)
    lay = build_layout(src, dst, assign, n, 8)
    ref = reference_cc(src, dst, n)
    cc_dense = simulate_cc(lay, iters=40, exchange="dense")
    cc_halo = simulate_cc(lay, iters=40, exchange="halo")
    touched = np.zeros(n, bool)
    touched[src] = touched[dst] = True
    np.testing.assert_array_equal(cc_dense[touched], ref[touched])
    np.testing.assert_array_equal(cc_halo[touched], ref[touched])


def test_unknown_exchange_rejected():
    from repro.dist.halo import get_exchange
    with pytest.raises(ValueError, match="unknown exchange"):
        get_exchange("sparse-magic")
    # the engine drivers surface the same error (not a bare KeyError)
    src, dst, n, assign = _random_graph_and_assign(0, 4, n=50)
    lay = build_layout(src, dst, assign, n, 4)
    with pytest.raises(ValueError, match="unknown exchange"):
        simulate_pagerank(lay, iters=1, exchange="sparse-magic")


# ------------------------------------------------- satellite regression

def test_parallel_partition_zero_edges_raises_value_error():
    empty = np.zeros(0, dtype=np.int64)
    with pytest.raises(ValueError, match="zero|empty"):
        partition(empty, empty, 10, CLUGPConfig(k=4))


def test_parallel_partition_tiny_stream_still_works():
    # fewer edges than nodes ⇒ some slices empty; must not crash
    src = np.array([0, 1], dtype=np.int64)
    dst = np.array([1, 2], dtype=np.int64)
    res = partition(src, dst, 3, CLUGPConfig(k=2), nodes=4)
    assert res.assign.shape == (2,)
