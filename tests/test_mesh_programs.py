"""The GAS programs the mesh tests of ``test_dist_multidevice.py`` leave
out (labelprop, sssp, bfs, degree, centrality, ppr), on the mesh engine
at k = 16 over 8 virtual devices — two partitions a device — on the halo
wire, against ``simulate_gas`` on the same layout.  Integer programs
match bit for bit; the float ones up to the float32 summation order of
the dangling mass (a sum per device, then psum, against one sum over k).
A file of its own, so that a run split by file gives it its own worker."""
import pytest

pytestmark = pytest.mark.multidevice

ITERS = {"labelprop": 40, "sssp": 40, "bfs": 40, "degree": 2,
         "centrality": 30, "ppr": 30}


@pytest.mark.parametrize("name", sorted(ITERS))
def test_mesh_program_matches_simulation(multidevice, name):
    multidevice(f"""
    import numpy as np
    from repro.core import CLUGPConfig, web_graph
    from repro.launch.mesh import make_graph_mesh
    from repro.session import GraphSession, SessionConfig

    g = web_graph(scale=9, edge_factor=6, seed=1)
    sess = GraphSession(SessionConfig(clugp=CLUGPConfig.optimized(16),
                                      exchange="halo"))
    sess.partition(g.src, g.dst, g.num_vertices).layout()
    mesh = make_graph_mesh(16)
    assert mesh.shape["parts"] == 8
    got = sess.run("{name}", iters={ITERS[name]}, mesh=mesh)
    want = sess.run("{name}", iters={ITERS[name]})
    assert got.dtype == want.dtype, (got.dtype, want.dtype)
    if np.issubdtype(want.dtype, np.floating):
        err = np.max(np.abs(got - want) / np.maximum(np.abs(want), 1e-30))
        print("err", err)
        assert err <= 1e-6, err
    else:
        np.testing.assert_array_equal(got, want)
    print("ok")
    """)
