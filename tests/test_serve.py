"""Partitioning-as-a-service: GraphServer query/ingest/preemption suite.

The server only batches, caches, and swaps — it must never change the
compute.  So the gates are identities: batched replies bit-match direct
``GraphSession.run``/``run_many`` on the same layout; a window flush plus
watermark restream leaves RF ≤ the drifted RF (the restream repair is
monotone by construction); a server rebuilt from its ``ServiceFT``
snapshot carries the identical config blob, edges, and assignment.
"""
import time

import jax
import numpy as np
import pytest

from conftest import random_graph_and_assign

from repro.core import (CLUGPConfig, incremental_assign, metrics,
                        restream_assign, stream_state, web_graph)
from repro import obs
from repro.dist.ft import ServiceFT
from repro.serve import QUERY_KINDS, GraphServer
from repro.session import GraphSession, SessionConfig


def make_server(seed=0, k=4, scale=10, exchange="halo", **kw):
    g = web_graph(scale=scale, seed=seed)
    cfg = SessionConfig(clugp=CLUGPConfig(k=k), iters=8, exchange=exchange)
    sess = GraphSession(cfg).partition(g.src, g.dst, g.num_vertices)
    return GraphServer(sess.layout(), **kw), g


# ------------------------------------------------------------- queries

def test_batched_queries_match_direct_run():
    srv, g = make_server(max_batch=8)
    ref = GraphSession.from_json(srv.sess.to_json()).with_partition(
        g.src, g.dst, g.num_vertices, srv.sess.assign)
    rng = np.random.default_rng(1)
    verts = rng.integers(0, g.num_vertices, 16)
    tickets = {p: srv.submit("score", program=p, vertices=verts)
               for p in ("pagerank", "degree", "cc")}
    t_full = srv.submit("label")          # default cc, full dense vector
    assert srv.serve_pending() == 4
    for p, t in tickets.items():
        want = ref.run(p, iters=8, exchange="halo")[verts]
        got = srv.result(t).value
        assert got.dtype == want.dtype
        assert np.array_equal(got, want), p
    assert np.array_equal(srv.result(t_full).value,
                          ref.run("cc", iters=8, exchange="halo"))


def test_queries_match_on_every_exchange():
    # the server executes through run_many, so its replies are
    # bit-identical to a direct run_many on every wire — lossy included;
    # vs the single-program run the lossy wires differ only by the fused
    # encoding's quantization error (wire tolerance)
    for ex in ("dense", "quantized", "ragged_quantized"):
        srv, g = make_server(exchange=ex)
        ref = GraphSession.from_json(srv.sess.to_json()).with_partition(
            g.src, g.dst, g.num_vertices, srv.sess.assign)
        t = srv.submit("score", program="pagerank")
        srv.step()
        got = srv.result(t).value
        want = ref.run_many(["pagerank"], iters=8, exchange=ex)[0]
        assert np.array_equal(got, want), ex
        single = ref.run("pagerank", iters=8, exchange=ex)
        if ex == "dense":
            assert np.array_equal(got, single)
        else:
            # int8-scale wire error on (V,)-normalized rank mass
            assert np.allclose(got, single, rtol=0.05, atol=2e-4), ex


def test_fused_microbatch_and_value_cache():
    srv, _ = make_server(max_batch=16)
    calls = []
    inner = srv.sess.run_many

    def counting_run_many(progs, **kw):
        calls.append([p.name for p in progs])
        return inner(progs, **kw)

    srv.sess.run_many = counting_run_many
    # pagerank+degree share no cell (f32 vs i32 sum) → two fused calls;
    # cc rides the i32/min cell alone
    for p in ("pagerank", "degree", "cc", "pagerank", "degree"):
        srv.submit("score", program=p, vertices=[0])
    assert srv.step() == 5
    assert srv.stats["microbatches"] == 1
    assert sorted(len(c) for c in calls) == [1, 1, 1]
    # every vector is now cached: a second microbatch computes nothing
    for p in ("pagerank", "degree", "cc"):
        srv.submit("score", program=p, vertices=[1])
    calls.clear()
    srv.step()
    assert calls == []


def test_owner_and_neighbors_queries():
    srv, g = make_server()
    lay = srv.sess.partition_layout
    t1 = srv.submit("owner", vertices=[0, 7, 23])
    t2 = srv.submit("neighbors", vertices=[0, 7])
    srv.serve_pending()
    own = srv.result(t1).value
    assert own.shape == (3,) and own.min() >= 0 and own.max() < lay.k
    # owner really is the master device of that vertex in the layout
    for v, p in zip([0, 7, 23], own):
        gids = lay.vert_gid[p][lay.is_master[p]]
        assert v in gids
    nb = srv.result(t2).value
    want0 = np.unique(np.concatenate([g.dst[g.src == 0],
                                      g.src[g.dst == 0]]))
    assert np.array_equal(nb[0], want0)


def test_bad_requests_are_rejected():
    srv, _ = make_server()
    with pytest.raises(ValueError, match="unknown query kind"):
        srv.submit("foo")
    with pytest.raises(ValueError, match="need vertices"):
        srv.submit("owner")
    t = srv.submit("score", program="not-a-program")
    srv.step()
    assert "unknown program" in srv.result(t).error
    assert tuple(QUERY_KINDS) == ("score", "label", "neighbors", "owner")


# ----------------------------------------------------- incremental path

def test_incremental_assign_seeds_resident_loads():
    src, dst, n, assign = random_graph_and_assign(seed=3, k=4)
    cfg = CLUGPConfig(k=4)
    rng = np.random.default_rng(4)
    ws = rng.integers(0, n, 200)
    wd = rng.integers(0, n, 200)
    wa = incremental_assign(src, dst, ws, wd, assign, n, cfg)
    assert wa.shape == (200,) and wa.min() >= 0 and wa.max() < 4
    # the grown stream respects the grown balance cap τ·(E_old+E_new)/k
    loads = np.bincount(np.concatenate([assign, wa]), minlength=4)
    lmax = cfg.tau * (len(src) + 200) / 4
    assert loads.max() <= int(np.ceil(lmax))
    # stream_state marks exactly the vertices replicated >= 2 partitions
    st = stream_state(src, dst, assign, n, 4)
    v = int(src[0])
    parts = np.unique(assign[(src == v) | (dst == v)])
    assert bool(st.divided[v]) == (len(parts) > 1)


def test_restream_assign_is_monotone():
    src, dst, n, assign = random_graph_and_assign(seed=5, k=8)
    cfg = CLUGPConfig(k=8)
    rf0 = metrics.replication_factor(src, dst, assign, n, 8)
    best, trace = restream_assign(src, dst, assign, n, cfg, passes=2)
    rf1 = metrics.replication_factor(src, dst, best, n, 8)
    assert len(trace) == 2 and trace[0] == pytest.approx(rf0)
    assert rf1 <= rf0 + 1e-12       # never worse than the input


def test_window_ingest_flush_and_watermark_restream():
    srv, g = make_server(window=400, rf_watermark=1.01,
                         restream_passes=2)
    e0 = len(srv.sess.edges[0])
    rng = np.random.default_rng(6)
    n = g.num_vertices
    flushed = False
    for _ in range(4):
        flushed |= srv.ingest(rng.integers(0, n, 110),
                              rng.integers(0, n, 110))
    assert flushed and srv.stats["windows"] >= 1
    assert len(srv.sess.edges[0]) == e0 + 440 - srv._buffered
    drifted = [v for e, v in srv.rf_trace if e == "window"]
    repaired = [v for e, v in srv.rf_trace if e == "restream"]
    assert srv.stats["restreams"] >= 1
    assert repaired[-1] <= max(drifted) + 1e-12
    # the swapped layout serves the grown graph, caches invalidated
    t = srv.submit("score", program="pagerank", vertices=[0])
    srv.step()
    assert srv.result(t).error is None
    assert srv.sess.partition_layout.num_edges == len(srv.sess.edges[0])


def test_tol_server_warm_starts_after_swap():
    """With ``tol`` set the server's value caches double as warm-start
    seeds: after a window flush + restream swaps the layout, the next
    query re-converges from the pre-swap fixed point in strictly fewer
    iterations than a cold run on the grown graph — and lands within the
    convergence envelope of the cold fixed point."""
    srv, g = make_server(window=400, rf_watermark=1.01,
                         restream_passes=2, tol=1e-6, iters=40)
    t = srv.submit("score", program="pagerank", vertices=[0])
    srv.step()
    assert srv.result(t).error is None
    first_iters = max(srv.last_iters_run.values())
    assert 0 < first_iters <= 40
    rng = np.random.default_rng(6)
    n = g.num_vertices
    for _ in range(4):
        srv.ingest(rng.integers(0, n, 110), rng.integers(0, n, 110))
    assert srv.stats["restreams"] >= 1
    assert not srv._values          # swap invalidated the caches...
    assert srv._warm                # ...into warm-start seeds
    srv.last_iters_run.clear()
    t2 = srv.submit("score", program="pagerank", vertices=[0, 1])
    srv.step()
    assert srv.result(t2).error is None
    warm_iters = max(srv.last_iters_run.values())
    cold, cold_iters = srv.sess.run_many(
        ["pagerank"], iters=40, exchange="halo", tol=1e-6,
        init_values=[np.zeros(0)], return_iters=True)
    assert warm_iters < cold_iters, (warm_iters, cold_iters)
    # both runs stopped inside the tol envelope of the same fixed point
    warm_full = srv._values[("pagerank", "halo")]
    np.testing.assert_allclose(warm_full, cold[0], atol=1e-4)


def test_tol_server_cold_and_warm_share_compute_semantics():
    """A tol server with nothing cached runs the cold path through the
    same loop: its replies bit-match a direct ``run_many`` with the same
    tol and empty seeds."""
    srv, g = make_server(tol=1e-6, iters=40)
    verts = [0, 1, 2, 3]
    t = srv.submit("score", program="pagerank", vertices=verts)
    srv.step()
    direct, _ = srv.sess.run_many(
        ["pagerank"], iters=40, exchange="halo", tol=1e-6,
        init_values=[np.zeros(0)], return_iters=True)
    assert np.array_equal(srv.result(t).value, direct[0][verts])


def test_flush_compilations_show_under_the_step_that_meets_them():
    """A flush grows the graph and so the layout's table shapes.  The
    flush runs on the host and compiles nothing itself: the compilations
    its new shapes bring are recorded under the first ``serve.step``
    after it, and counted in ``stats["compiles"]``."""
    jax.clear_caches()
    srv, g = make_server(window=400, rf_watermark=1.01, restream_passes=2)
    srv.submit("score", program="pagerank", vertices=[0])
    srv.step()
    before = srv.stats["compiles"]
    assert before >= 1                  # the first step compiled its cell
    shapes = (srv.sess.partition_layout.e_max,
              srv.sess.partition_layout.l_max)
    t0 = time.perf_counter()
    rng = np.random.default_rng(11)
    n = g.num_vertices
    for _ in range(4):
        srv.ingest(rng.integers(0, n, 110), rng.integers(0, n, 110))
    assert srv.stats["windows"] == 1
    assert (srv.sess.partition_layout.e_max,
            srv.sess.partition_layout.l_max) != shapes
    assert srv.stats["compiles"] == before
    t = srv.submit("score", program="pagerank", vertices=[0])
    srv.step()
    assert srv.result(t).error is None
    assert srv.stats["compiles"] > before
    recs = obs.spans(t0)
    names = [r[0] for r in recs]
    assert {"serve.flush", "serve.swap", "serve.step"} <= set(names)
    compiles = [r for r in recs if r[0] == obs.COMPILE]
    assert compiles
    assert all(r[4]["stack"][0] == "serve.step" for r in compiles)
    step = next(r for r in recs if r[0] == "serve.step")
    assert step[4] == {"requests": 1, "cells": 1}
    assert srv.stats["compiles"] - before == len(compiles)


def test_ingest_can_grow_the_vertex_set():
    srv, g = make_server(window=50)
    n0 = srv.sess.num_vertices
    srv.ingest(np.arange(n0, n0 + 50), np.zeros(50, dtype=np.int64))
    assert srv.sess.num_vertices == n0 + 50
    t = srv.submit("owner", vertices=[n0 + 10])
    srv.step()
    assert srv.result(t).error is None


# -------------------------------------------------------- preemption

def test_kill_and_resume_identical_partition(tmp_path):
    srv, g = make_server(window=300, rf_watermark=1.01)
    rng = np.random.default_rng(7)
    srv.ingest(rng.integers(0, g.num_vertices, 300),
               rng.integers(0, g.num_vertices, 300))
    srv.ft = ServiceFT(tmp_path)
    srv.checkpoint()
    srv.ft.wait()
    blob, assign = srv.sess.to_json(), srv.sess.assign.copy()
    t = srv.submit("score", program="pagerank", vertices=[0, 1, 2])
    srv.step()
    want = srv.result(t).value
    del srv                                    # the "kill"
    srv2 = GraphServer.resume(ServiceFT(tmp_path))
    assert srv2.sess.to_json() == blob         # same config blob
    assert np.array_equal(srv2.sess.assign, assign)
    t2 = srv2.submit("score", program="pagerank", vertices=[0, 1, 2])
    srv2.step()
    assert np.array_equal(srv2.result(t2).value, want)


def test_resume_empty_dir_raises(tmp_path):
    with pytest.raises(FileNotFoundError):
        GraphServer.resume(ServiceFT(tmp_path))


def test_snapshot_survives_graph_growth(tmp_path):
    # the shape-blind restore path: snapshots of different sizes in the
    # same dir, latest wins
    srv, g = make_server(window=100)
    srv.ft = ServiceFT(tmp_path)
    srv.checkpoint()
    srv.ingest(np.zeros(100, np.int64),
               np.arange(1, 101, dtype=np.int64))
    srv.checkpoint()
    srv.ft.wait()
    srv2 = GraphServer.resume(ServiceFT(tmp_path))
    assert len(srv2.sess.edges[0]) == len(srv.sess.edges[0])


# ------------------------------------------------------- multidevice

@pytest.mark.multidevice
def test_serve_shard_map_smoke(multidevice):
    """The server's fused query step shard_maps one partition per device
    and still bit-matches the single-device simulate path."""
    multidevice("""
        import numpy as np
        from repro.core import CLUGPConfig, web_graph
        from repro.launch.mesh import make_graph_mesh
        from repro.serve import GraphServer
        from repro.session import GraphSession, SessionConfig

        g = web_graph(scale=10, seed=0)
        cfg = SessionConfig(clugp=CLUGPConfig(k=8), iters=6,
                            exchange="halo")
        sess = GraphSession(cfg).partition(g.src, g.dst,
                                           g.num_vertices).layout()
        mesh = make_graph_mesh(8)
        srv = GraphServer(sess, mesh=mesh)
        t1 = srv.submit("score", program="pagerank")
        t2 = srv.submit("score", program="degree")
        srv.serve_pending()
        ref = GraphSession.from_json(sess.to_json()).with_partition(
            g.src, g.dst, g.num_vertices, sess.assign)
        assert np.array_equal(srv.result(t1).value,
                              ref.run("pagerank", iters=6))
        assert np.array_equal(srv.result(t2).value,
                              ref.run("degree", iters=6))
        print("serve shard_map smoke OK")
        """, n_devices=8)
