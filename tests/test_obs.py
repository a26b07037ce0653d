"""The program's spans and compile records (``repro.obs``): parent links
per thread, the bounded ring, compilations tied to the span that caused
them, and the spans a whole ``GraphSession`` job leaves, nested as the
layers call each other."""
import collections
import threading
import time

import jax
import numpy as np
import pytest

from repro import obs
from repro.core import CLUGPConfig, web_graph
from repro.session import GraphSession, SessionConfig


def _since(t0: float) -> list:
    return [r for r in obs.spans(t0) if not r[0].startswith("compile")]


def test_parent_links_and_attrs():
    t0 = time.perf_counter()
    with obs.span("t.outer", a=1) as outer:
        with obs.span("t.inner"):
            pass
        outer.attrs["b"] = 2
    inner, top = _since(t0)
    assert inner[0] == "t.inner" and inner[3] == "t.outer"
    assert top[0] == "t.outer" and top[3] is None
    assert top[4] == {"a": 1, "b": 2}
    assert top[1] <= inner[1] <= inner[2] <= top[2]


def test_span_is_recorded_when_its_body_raises():
    t0 = time.perf_counter()
    with pytest.raises(ValueError):
        with obs.span("t.fails"):
            raise ValueError("boom")
    with obs.span("t.after"):
        pass
    names = [(r[0], r[3]) for r in _since(t0)]
    assert names == [("t.fails", None), ("t.after", None)]


def test_parents_are_per_thread():
    t0 = time.perf_counter()
    opened, release = threading.Event(), threading.Event()

    def other():
        with obs.span("t.thread_outer"):
            opened.set()
            release.wait(10)

    th = threading.Thread(target=other)
    th.start()
    assert opened.wait(10)
    with obs.span("t.main"):       # opened while the other thread's is open
        pass
    release.set()
    th.join(10)
    assert not th.is_alive()
    got = {r[0]: r[3] for r in _since(t0)}
    assert got == {"t.main": None, "t.thread_outer": None}


def test_ring_is_bounded_and_counts_what_it_drops(monkeypatch):
    monkeypatch.setattr(obs, "RING", 4)
    monkeypatch.setattr(obs, "_ring", collections.deque(maxlen=4))
    before = obs.dropped()
    for i in range(10):
        with obs.span("t.ring", i=i):
            pass
    assert obs.dropped() - before == 6
    assert [r[4]["i"] for r in obs.spans()] == [6, 7, 8, 9]


def test_compile_is_recorded_under_its_span_once():
    scale = np.float32(time.time_ns() % 1000 + 1.5)   # a program of its own
    f = jax.jit(lambda x: x * scale + 1)
    x = np.arange(7, dtype=np.float32)
    t0 = time.perf_counter()
    before = obs.compilations()
    with obs.span("t.outer"):
        with obs.span("t.first_call"):
            f(x).block_until_ready()
    mid = time.perf_counter()
    with obs.span("t.second_call"):
        f(x).block_until_ready()
    compiles = [r for r in obs.spans(t0) if r[0] == obs.COMPILE]
    assert len(compiles) == 1
    name, start, end, parent, attrs = compiles[0]
    assert parent == "t.first_call" and start == end < mid
    assert attrs["stack"] == ("t.outer", "t.first_call")
    assert attrs["seconds"] > 0
    assert obs.compilations() - before == 1


GAS_CHILDREN = ["gas.upload", "gas.run", "gas.collect"]
PARTITION_CHILDREN = ["partition.attempt", "partition.fetch",
                      "partition.contract", "partition.summary"]


def _children(recs, parent):
    """Names of the records whose parent is ``parent`` and that lie inside
    it in time, per instance of ``parent``."""
    out = []
    for p in [r for r in recs if r[0] == parent]:
        out.append([r[0] for r in recs if r[3] == parent
                    and p[1] <= r[1] <= r[2] <= p[2]])
    return out


def test_session_job_leaves_the_layer_spans():
    g = web_graph(scale=9, seed=0)
    t0 = time.perf_counter()
    sess = GraphSession(SessionConfig(clugp=CLUGPConfig.optimized(4),
                                      backend="jit", exchange="halo"))
    sess.partition(g.src, g.dst, g.num_vertices).layout()
    _, pr_iters = sess.run("pagerank", iters=60, tol=1e-6,
                           return_iters=True)
    _, cc_iters = sess.run("cc", iters=60, tol=0.0, return_iters=True)
    sess.run_many(["pagerank", "centrality"], iters=3)
    recs = _since(t0)
    assert [r[0] for r in recs if r[3] is None] == [
        "partition", "layout.build", "gas.pagerank", "gas.cc",
        "gas.pagerank+centrality"]
    assert _children(recs, "partition") == [PARTITION_CHILDREN]
    attempt = next(r for r in recs if r[0] == "partition.attempt")
    assert attempt[4]["attempt"] == 0
    assert {"id_cap", "m_cap", "nnz_cap"} <= set(attempt[4])
    for prog, iters in (("gas.pagerank", pr_iters), ("gas.cc", cc_iters),
                        ("gas.pagerank+centrality", 3)):
        assert _children(recs, prog) == [GAS_CHILDREN]
        assert next(r for r in recs if r[0] == prog)[4]["iters"] == iters
    assert 0 < pr_iters < 60 and 0 < cc_iters < 60


@pytest.mark.multidevice
def test_sharded_partition_and_mesh_gas_leave_the_same_spans(multidevice):
    out = multidevice("""
        import time
        from repro import obs
        from repro.core import CLUGPConfig, web_graph
        from repro.launch.mesh import make_graph_mesh, make_stream_mesh
        from repro.session import GraphSession, SessionConfig
        g = web_graph(scale=9, seed=1)
        sess = GraphSession(SessionConfig(clugp=CLUGPConfig.optimized(4),
                                          backend="sharded", nodes=2,
                                          exchange="halo"))
        t0 = time.perf_counter()
        sess.partition(g.src, g.dst, g.num_vertices,
                       mesh=make_stream_mesh(2)).layout()
        sess.run("pagerank", iters=4, mesh=make_graph_mesh(4))
        sess.run_many(["pagerank", "centrality"], iters=2,
                      mesh=make_graph_mesh(4))
        for r in obs.spans(t0):
            if not r[0].startswith("compile"):
                print(r[0], r[3], r[4].get("iters"))
    """, n_devices=4)
    rows = [line.split() for line in out.splitlines() if line]
    assert rows == [
        ["partition.attempt", "partition", "None"],
        ["partition.fetch", "partition", "None"],
        ["partition.summary", "partition", "None"],
        ["partition", "None", "None"],
        ["layout.build", "None", "None"],
        ["gas.upload", "gas.pagerank", "None"],
        ["gas.run", "gas.pagerank", "None"],
        ["gas.collect", "gas.pagerank", "None"],
        ["gas.pagerank", "None", "4"],
        ["gas.upload", "gas.pagerank+centrality", "None"],
        ["gas.run", "gas.pagerank+centrality", "None"],
        ["gas.collect", "gas.pagerank+centrality", "None"],
        ["gas.pagerank+centrality", "None", "2"]]
