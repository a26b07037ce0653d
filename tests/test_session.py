"""GraphSession façade (repro.session): config round-trips, the fluent
partition → layout → run chain, external assignments, and the
multi-device smoke (sharded partition + shard_map GAS + dry-run
collective bytes, all from one JSON blob).
"""
import numpy as np
import pytest

from repro.core import CLUGPConfig, partition, web_graph
from repro.core.graphgen import community_web
from repro.graph import build_layout, reference_cc, reference_pagerank, \
    simulate_cc, simulate_pagerank
from repro.session import GraphSession, PROGRAMS, SessionConfig, \
    resolve_program


@pytest.fixture(scope="module")
def graph10():
    return web_graph(scale=10, edge_factor=6, seed=3)


# ------------------------------------------------- the cells' pipeline

@pytest.fixture(scope="module", params=[0, 1, 2, 3])
def crawl_session(request):
    """The benchmark cells' pipeline at test size: a crawl-shaped graph
    (site-local links, cross-site links to hubs, crawl order), cut 16
    ways with the optimized profile on the jit backend, halo wire."""
    g = community_web(2048, avg_deg=16, seed=request.param)
    sess = GraphSession(SessionConfig(clugp=CLUGPConfig.optimized(16),
                                      backend="jit", exchange="halo"))
    sess.partition(g.src, g.dst, g.num_vertices).layout()
    return g, sess


def test_crawl_pipeline_pagerank_to_tol(crawl_session):
    """PageRank stopped at tol 1e-6 is within 1e-5 relative of the
    float64 reference run for the iterations it reports."""
    g, sess = crawl_session
    pr, it = sess.run("pagerank", iters=100, tol=1e-6, return_iters=True)
    assert 0 < it < 100, it
    ref = reference_pagerank(g.src, g.dst, g.num_vertices, iters=int(it))
    assert np.max(np.abs(pr - ref) / ref) <= 1e-5


def test_crawl_pipeline_wcc_exact(crawl_session):
    g, sess = crawl_session
    np.testing.assert_array_equal(
        sess.run("cc", iters=200),
        reference_cc(g.src, g.dst, g.num_vertices))


# --------------------------------------------------------------- config

def test_config_json_round_trip():
    cfg = SessionConfig(clugp=CLUGPConfig.optimized(8, restream=2),
                        backend="jit", nodes=1, exchange="quantized",
                        iters=17, pad_multiple=16)
    assert SessionConfig.from_json(cfg.to_json()) == cfg


def test_config_round_trip_identical_partition(graph10):
    """Two sessions built from the same JSON blob partition identically —
    the reproducibility contract."""
    g = graph10
    cfg = SessionConfig(clugp=CLUGPConfig.optimized(8, restream=1))
    s1 = GraphSession(cfg).partition(g.src, g.dst, g.num_vertices)
    s2 = GraphSession.from_json(s1.to_json()).partition(
        g.src, g.dst, g.num_vertices)
    assert s1.cfg == s2.cfg
    np.testing.assert_array_equal(s1.assign, s2.assign)
    assert s1.comm_bytes() == s2.comm_bytes()


def test_config_rejects_bad_values():
    with pytest.raises(ValueError, match="unknown backend"):
        SessionConfig(clugp=CLUGPConfig(k=4), backend="cuda")
    with pytest.raises(ValueError, match="unknown exchange"):
        SessionConfig(clugp=CLUGPConfig(k=4), exchange="carrier-pigeon")
    with pytest.raises(ValueError, match="nodes"):
        SessionConfig(clugp=CLUGPConfig(k=4), nodes=0)
    with pytest.raises(TypeError):
        SessionConfig(clugp={"k": 4})


def test_session_accepts_bare_clugp_config(graph10):
    g = graph10
    sess = GraphSession(CLUGPConfig(k=4), exchange="halo")
    sess.partition(g.src, g.dst, g.num_vertices)
    assert sess.cfg.exchange == "halo"
    assert sess.k == 4


# ----------------------------------------------------------- fluent chain

def test_partition_matches_core_api(graph10):
    g = graph10
    cfg = CLUGPConfig(k=8)
    sess = GraphSession(SessionConfig(clugp=cfg)).partition(
        g.src, g.dst, g.num_vertices)
    res = partition(g.src, g.dst, g.num_vertices, cfg, backend="np")
    np.testing.assert_array_equal(sess.assign, res.assign)
    assert sess.stats["rf"] == res.stats["rf"]


def test_run_pagerank_matches_engine_and_oracle(graph10):
    g = graph10
    sess = GraphSession(SessionConfig(clugp=CLUGPConfig(k=4), iters=20))
    pr = sess.partition(g.src, g.dst, g.num_vertices).layout().run(
        "pagerank")
    direct = simulate_pagerank(sess.partition_layout, iters=20,
                               exchange="halo")
    np.testing.assert_array_equal(pr, direct)
    ref = reference_pagerank(g.src, g.dst, g.num_vertices, iters=20)
    assert np.abs(pr - ref).max() < 1e-4


def test_run_cc_int64_labels(graph10):
    g = graph10
    sess = GraphSession(SessionConfig(clugp=CLUGPConfig(k=4)))
    cc = sess.partition(g.src, g.dst, g.num_vertices).run("cc", iters=30)
    assert cc.dtype == np.int64
    np.testing.assert_array_equal(
        cc, simulate_cc(sess.partition_layout, iters=30, exchange="halo"))


def test_layout_lazy_and_explicit(graph10):
    g = graph10
    sess = GraphSession(SessionConfig(clugp=CLUGPConfig(k=4)))
    sess.partition(g.src, g.dst, g.num_vertices)
    lay = sess.partition_layout          # lazily built
    ref = build_layout(g.src, g.dst, sess.assign, g.num_vertices, 4)
    np.testing.assert_array_equal(lay.halo_send, ref.halo_send)
    sess.layout(pad_multiple=16)         # explicit rebuild, wider padding
    assert sess.partition_layout.l_max % 16 == 0


def test_comm_bytes_table(graph10):
    g = graph10
    sess = GraphSession(SessionConfig(clugp=CLUGPConfig(k=4)))
    sess.partition(g.src, g.dst, g.num_vertices)
    cb = sess.comm_bytes()
    lay = sess.partition_layout
    assert cb["ideal"] == lay.comm_bytes("ideal")
    assert cb["quantized"] == lay.comm_bytes("quantized")
    assert cb["halo"] == lay.comm_bytes("halo")
    assert cb["dense_gather"] == lay.comm_bytes("dense")
    assert cb["quantized"] < cb["halo"] < cb["dense_gather"]
    # single-model routing returns the matching table entry
    assert sess.comm_bytes(exchange="halo") == cb["halo"]


def test_run_many_matches_single_runs(graph10):
    g = graph10
    sess = GraphSession(SessionConfig(clugp=CLUGPConfig(k=4), iters=25,
                                      exchange="halo"))
    sess.partition(g.src, g.dst, g.num_vertices)
    d, b = sess.run_many(["sssp", "bfs"])
    assert d.dtype == np.int64 and b.dtype == np.int64
    np.testing.assert_array_equal(d, sess.run("sssp"))
    np.testing.assert_array_equal(b, sess.run("bfs"))


def test_comm_bytes_programs_and_fused(graph10):
    g = graph10
    sess = GraphSession(SessionConfig(clugp=CLUGPConfig(k=4)))
    sess.partition(g.src, g.dst, g.num_vertices)
    lay = sess.partition_layout
    table = sess.comm_bytes(programs=list(PROGRAMS))
    # float sum programs ship the lossy int8 wire; min/int ship exact
    assert table["pagerank"]["quantized"] == \
        lay.comm_bytes("quantized", lossy=True)
    assert table["sssp"]["quantized"] == \
        lay.comm_bytes("quantized", lossy=False)
    for prog in table:
        assert table[prog]["halo"] < table[prog]["dense"]
    # exchange= narrows the per-program rows to plain ints
    narrow = sess.comm_bytes(programs=["pagerank"], exchange="halo")
    assert narrow == {"pagerank": lay.comm_bytes("halo")}
    fused = sess.comm_bytes(programs=["pagerank", "ppr", "centrality"],
                            exchange="quantized", fused=True)
    assert fused == lay.comm_bytes("quantized", programs=3, fused=True)
    assert fused < 3 * table["pagerank"]["quantized"]


def test_comm_bytes_shims_identical_and_warn(graph10):
    """The pre-consolidation entry points survive as DeprecationWarning
    shims that route through the one ``comm_bytes(...)`` — identity on
    every wire format (the PR 5 shim-test pattern)."""
    g = graph10
    sess = GraphSession(SessionConfig(clugp=CLUGPConfig(k=4)))
    sess.partition(g.src, g.dst, g.num_vertices)
    lay = sess.partition_layout
    pairs = [
        (lambda: lay.comm_bytes_mirror_sync(), lay.comm_bytes("dense")),
        (lambda: lay.comm_bytes_halo(), lay.comm_bytes("halo")),
        (lambda: lay.comm_bytes_ragged(), lay.comm_bytes("ragged")),
        (lambda: lay.comm_bytes_ragged_quantized(),
         lay.comm_bytes("ragged_quantized")),
        (lambda: lay.comm_bytes_halo_quantized(),
         lay.comm_bytes("quantized")),
        (lambda: lay.comm_bytes_fused_quantized(3),
         lay.comm_bytes("quantized", programs=3, fused=True)),
        (lambda: lay.comm_bytes_exchange("quantized", lossy=False),
         lay.comm_bytes("quantized", lossy=False)),
        (lambda: lay.comm_bytes_fused(2, "ragged"),
         lay.comm_bytes("ragged", programs=2, fused=True)),
        (lambda: lay.comm_bytes_ideal(), lay.comm_bytes("ideal")),
        (lambda: lay.comm_bytes_dense(), lay.comm_bytes("allreduce")),
        (lambda: sess.comm_bytes_programs(["pagerank"]),
         sess.comm_bytes(programs=["pagerank"])),
        (lambda: sess.comm_bytes_fused(["pagerank", "ppr"],
                                       exchange="quantized"),
         sess.comm_bytes(programs=["pagerank", "ppr"],
                         exchange="quantized", fused=True)),
    ]
    for shim, expected in pairs:
        with pytest.warns(DeprecationWarning):
            assert shim() == expected
    with pytest.raises(ValueError, match="unknown exchange"):
        lay.comm_bytes("carrier-pigeon")
    with pytest.raises(ValueError, match="needs an explicit exchange"):
        lay.comm_bytes(programs=2, fused=True)


def test_run_sweep_lands_on_last_k(graph10):
    """run_sweep: one compiled stacked body partitions at every k, the
    returned table matches the jit backend per k, and the session is left
    on the LAST k's partition ready for layout()/run()."""
    g = graph10
    ks = (4, 8)
    sess = GraphSession(SessionConfig(clugp=CLUGPConfig(k=2)))
    table = sess.run_sweep(g.src, g.dst, g.num_vertices, ks)
    assert sorted(table) == list(ks)
    for k in ks:
        ref = partition(g.src, g.dst, g.num_vertices,
                        CLUGPConfig(k=k), backend="jit")
        np.testing.assert_array_equal(table[k].assign, ref.assign)
    assert sess.k == ks[-1]
    np.testing.assert_array_equal(sess.assign, table[ks[-1]].assign)
    assert sess.partition_layout.k == ks[-1]


def test_with_partition_external_assignment(graph10):
    g = graph10
    rng = np.random.default_rng(0)
    a = rng.integers(0, 4, g.num_edges).astype(np.int32)
    sess = GraphSession(SessionConfig(clugp=CLUGPConfig(k=4)))
    sess.with_partition(g.src, g.dst, g.num_vertices, a)
    assert sess.stats["backend"] == "external"
    assert sess.stats["rf"] > 1.0
    assert sess.comm_bytes()["halo"] > 0
    with pytest.raises(ValueError, match="covers"):
        sess.with_partition(g.src, g.dst, g.num_vertices, a[:-1])


def test_errors_before_partition_and_bad_program(graph10):
    g = graph10
    sess = GraphSession(SessionConfig(clugp=CLUGPConfig(k=4)))
    with pytest.raises(RuntimeError, match="no partition yet"):
        sess.run("pagerank")
    with pytest.raises(RuntimeError, match="no partition yet"):
        sess.layout()
    sess.partition(g.src, g.dst, g.num_vertices)
    with pytest.raises(ValueError, match="unknown program"):
        sess.run("triangle-count")
    with pytest.raises(ValueError, match="unknown program"):
        resolve_program("kcore", 10)
    # the full registry resolves (sssp et al. joined the library)
    for name in sorted({"pagerank", "cc", "sssp", "bfs"}):
        assert resolve_program(name, 10).name == name


# --------------------------------------------------- multidevice smoke

SESSION_SMOKE = """
import numpy as np

from repro.core import CLUGPConfig, web_graph
from repro.launch.mesh import make_graph_mesh
from repro.session import GraphSession, SessionConfig

g = web_graph(scale=9, edge_factor=6, seed=3)
k = 4
cfg = SessionConfig(clugp=CLUGPConfig.optimized(k, restream=1),
                    backend="sharded", nodes=4, exchange="quantized")
s1 = GraphSession(cfg).partition(g.src, g.dst, g.num_vertices)
s2 = GraphSession.from_json(s1.to_json()).partition(g.src, g.dst,
                                                    g.num_vertices)
# the JSON blob reproduces the sharded partition exactly
np.testing.assert_array_equal(s1.assign, s2.assign)
assert s1.stats["backend"] == "sharded" and s1.stats["nodes"] == 4

# shard_map GAS over a real 4-device mesh == stacked simulation, bit for bit
mesh = make_graph_mesh(k)
sim = s1.run("pagerank", iters=15, exchange="dense")
sh = s1.run("pagerank", iters=15, exchange="dense", mesh=mesh)
np.testing.assert_array_equal(sh, sim)

# dry-run cells from round-tripped sessions compile to identical
# collective bytes (the reproducibility contract on the wire)
from repro.analysis.ir import collective_bytes
bytes_ = []
for s in (s1, s2):
    jitted, args = s.dryrun_step("pagerank", mesh=mesh)
    bytes_.append(collective_bytes(jitted.lower(*args).compile().as_text()))
assert bytes_[0] == bytes_[1], bytes_
assert bytes_[0]["total"] > 0, bytes_
print("SESSION_OK", bytes_[0]["total"])
"""


@pytest.mark.multidevice
def test_session_multidevice_smoke(multidevice):
    out = multidevice(SESSION_SMOKE, n_devices=8)
    assert "SESSION_OK" in out
