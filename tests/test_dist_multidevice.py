"""Multi-device (8 virtual CPU devices) integration tests, run in
subprocesses: shard_map graph engine (single and fused multi-program, one
and several partitions per device)."""
import pytest

pytestmark = pytest.mark.multidevice


def test_shard_map_pagerank_matches_reference(multidevice):
    multidevice("""
    import numpy as np
    from repro.core import web_graph, partition, CLUGPConfig
    from repro.graph import (build_layout, shard_map_pagerank,
                             reference_pagerank)
    from repro.launch.mesh import make_graph_mesh

    g = web_graph(scale=10, edge_factor=6, seed=3)
    res = partition(g.src, g.dst, g.num_vertices,
                    CLUGPConfig.optimized(8))
    lay = build_layout(g.src, g.dst, res.assign, g.num_vertices, 8)
    mesh = make_graph_mesh(8)
    pr = shard_map_pagerank(lay, mesh, iters=30)
    ref = reference_pagerank(g.src, g.dst, g.num_vertices, iters=30)
    err = np.abs(pr - ref).max()
    assert err < 1e-6, err
    print('pagerank ok', err)
    """)


def test_shard_map_pagerank_halo_matches_dense(multidevice):
    """The mirror-routed halo backend matches the dense all_gather backend
    and the oracle on 8 real devices, and actually lowers to all-to-all
    (no all-gather) in the compiled step."""
    multidevice("""
    import numpy as np
    from repro.core import web_graph, partition, CLUGPConfig
    from repro.graph import (build_layout, shard_map_pagerank,
                             pagerank_step_for_dryrun, reference_pagerank)
    from repro.analysis.ir import hlo_collectives
    from repro.launch.mesh import make_graph_mesh

    g = web_graph(scale=10, edge_factor=6, seed=3)
    res = partition(g.src, g.dst, g.num_vertices,
                    CLUGPConfig.optimized(8))
    lay = build_layout(g.src, g.dst, res.assign, g.num_vertices, 8)
    mesh = make_graph_mesh(8)
    ref = reference_pagerank(g.src, g.dst, g.num_vertices, iters=30)
    pr_d = shard_map_pagerank(lay, mesh, iters=30, exchange='dense')
    pr_h = shard_map_pagerank(lay, mesh, iters=30, exchange='halo')
    assert np.abs(pr_d - ref).max() < 1e-6
    assert np.abs(pr_h - ref).max() < 1e-6

    jitted, args = pagerank_step_for_dryrun(lay, mesh, exchange='halo')
    hlo = jitted.lower(*args).compile().as_text()
    kinds = [kind for kind, _, _ in hlo_collectives(hlo)]
    assert 'all-to-all' in kinds, 'halo must use all_to_all'
    assert 'all-gather' not in kinds, 'halo must not gather'
    print('halo shard_map ok')
    """)


def test_shard_map_cc_and_quantized_match_reference(multidevice):
    """shard_map_cc ≡ simulate_cc ≡ reference_cc on every backend, and the
    quantized pagerank driver matches its stacked simulation bit-for-bit
    (same program spec, same exchange math) and the oracle within the
    error-feedback tolerance; its compiled step ships int8 lanes."""
    multidevice("""
    import numpy as np
    from repro.core import web_graph, partition, CLUGPConfig
    from repro.graph import (build_layout, shard_map_cc, shard_map_pagerank,
                             simulate_cc, simulate_pagerank,
                             pagerank_step_for_dryrun, reference_cc,
                             reference_pagerank)
    from repro.analysis.ir import hlo_collectives
    from repro.launch.mesh import make_graph_mesh

    g = web_graph(scale=10, edge_factor=6, seed=3)
    res = partition(g.src, g.dst, g.num_vertices,
                    CLUGPConfig.optimized(8))
    lay = build_layout(g.src, g.dst, res.assign, g.num_vertices, 8)
    mesh = make_graph_mesh(8)

    ref_cc = reference_cc(g.src, g.dst, g.num_vertices)
    for exchange in ('dense', 'halo', 'quantized'):
        cc_sm = shard_map_cc(lay, mesh, iters=30, exchange=exchange)
        cc_sim = simulate_cc(lay, iters=30, exchange=exchange)
        np.testing.assert_array_equal(cc_sm, cc_sim, err_msg=exchange)
        np.testing.assert_array_equal(cc_sm, ref_cc, err_msg=exchange)

    ref_pr = reference_pagerank(g.src, g.dst, g.num_vertices, iters=30)
    pr_sm = shard_map_pagerank(lay, mesh, iters=30, exchange='quantized')
    pr_sim = simulate_pagerank(lay, iters=30, exchange='quantized')
    np.testing.assert_array_equal(pr_sm, pr_sim)
    assert np.abs(pr_sm - ref_pr).max() < 1e-5

    jitted, args = pagerank_step_for_dryrun(lay, mesh, exchange='quantized')
    hlo = jitted.lower(*args).compile().as_text()
    coll = [(kind, out) for kind, _, out in hlo_collectives(hlo)]
    assert any(kind == 'all-to-all' and 's8[' in out
               for kind, out in coll), 'int8 lanes must ship'
    assert not any(kind == 'all-gather' for kind, _ in coll), \\
        'quantized must not all-gather'
    print('cc + quantized shard_map ok')
    """)


def test_shard_map_ragged_ring_matches_and_ships_fewer_bytes(multidevice):
    """The ragged ppermute ring on 8 real devices: pagerank matches the
    oracle on both ragged wires, exact int payloads (CC) ride the ring
    bit-for-bit with the stacked simulation, the compiled step lowers to
    collective-permutes ONLY (no all-to-all, no all-gather — the whole
    point of the per-distance lanes), and the byte models the dry-run
    gate validates against HLO order ragged < halo and ragged_quantized
    < quantized on this skewed-RF layout."""
    multidevice("""
    import numpy as np
    from repro.core import web_graph, partition, CLUGPConfig
    from repro.graph import (build_layout, shard_map_cc, shard_map_pagerank,
                             simulate_cc, simulate_pagerank,
                             pagerank_step_for_dryrun, reference_cc,
                             reference_pagerank)
    from repro.analysis.ir import hlo_collectives
    from repro.launch.mesh import make_graph_mesh

    g = web_graph(scale=10, edge_factor=6, seed=3)
    res = partition(g.src, g.dst, g.num_vertices,
                    CLUGPConfig.optimized(8))
    lay = build_layout(g.src, g.dst, res.assign, g.num_vertices, 8)
    mesh = make_graph_mesh(8)

    ref = reference_pagerank(g.src, g.dst, g.num_vertices, iters=30)
    pr = shard_map_pagerank(lay, mesh, iters=30, exchange='ragged')
    assert np.abs(pr - ref).max() < 1e-6
    # top-delta sparsification lags the padded EF wire (only ~25% of
    # each hop's lanes ship per iteration), so the 30-iter tolerance is
    # the fused-quantized one, not the dense one
    pr_q = shard_map_pagerank(lay, mesh, iters=30,
                              exchange='ragged_quantized')
    assert np.abs(pr_q - ref).max() < 5e-4

    ref_cc = reference_cc(g.src, g.dst, g.num_vertices)
    for exchange in ('ragged', 'ragged_quantized'):
        cc = shard_map_cc(lay, mesh, iters=30, exchange=exchange)
        np.testing.assert_array_equal(
            cc, simulate_cc(lay, iters=30, exchange=exchange),
            err_msg=exchange)
        np.testing.assert_array_equal(cc, ref_cc, err_msg=exchange)

    jitted, args = pagerank_step_for_dryrun(lay, mesh, exchange='ragged')
    hlo = jitted.lower(*args).compile().as_text()
    kinds = [kind for kind, _, _ in hlo_collectives(hlo)]
    assert 'collective-permute' in kinds, 'ragged must ppermute'
    assert 'all-to-all' not in kinds
    assert 'all-gather' not in kinds

    assert lay.comm_bytes('ragged') < lay.comm_bytes('halo')
    assert lay.comm_bytes('ragged_quantized', lossy=True) < \\
        lay.comm_bytes('quantized', lossy=True)
    print('ragged shard_map ok')
    """)


def test_shard_map_fused_many_matches_simulation(multidevice):
    """shard_map_gas_many ≡ simulate_gas_many on 8 real devices for a
    fused f32 bundle (within float reduction-order noise: the global-aux
    psum on the mesh associates differently than the stacked vmap+sum),
    the fused quantized step lowers to one all-to-all pair per phase
    (not one per program), and iters=0 returns init values unchanged."""
    multidevice("""
    import numpy as np
    from repro.core import web_graph, partition, CLUGPConfig
    from repro.graph import (build_layout, gas_step_for_dryrun, get_program,
                             reference_centrality, reference_pagerank,
                             reference_ppr, shard_map_gas_many,
                             simulate_gas_many)
    from repro.analysis.ir import hlo_collectives
    from repro.launch.mesh import make_graph_mesh

    g = web_graph(scale=10, edge_factor=6, seed=3)
    res = partition(g.src, g.dst, g.num_vertices,
                    CLUGPConfig.optimized(8))
    lay = build_layout(g.src, g.dst, res.assign, g.num_vertices, 8)
    mesh = make_graph_mesh(8)
    names = ('pagerank', 'ppr', 'centrality')
    progs = [get_program(p, g.num_vertices) for p in names]
    refs = {
        'pagerank': reference_pagerank(g.src, g.dst, g.num_vertices, 30),
        'ppr': reference_ppr(g.src, g.dst, g.num_vertices, iters=30),
        'centrality': reference_centrality(g.src, g.dst, g.num_vertices,
                                           iters=30),
    }
    for exchange in ('dense', 'halo', 'quantized'):
        sim = simulate_gas_many(progs, lay, iters=30, exchange=exchange)
        sm = shard_map_gas_many(progs, lay, mesh, iters=30,
                                exchange=exchange)
        # the EF quantizer amplifies reduction-order noise (a 1-ulp aux
        # difference can flip an int4 code), so sim↔shard_map is only as
        # tight as the wire itself under 'quantized'
        tol = 5e-4 if exchange == 'quantized' else 1e-5
        for name, a, b in zip(names, sim, sm):
            assert np.abs(a - b).max() < tol, (exchange, name)
            assert np.abs(a - refs[name]).max() < tol, (exchange, name)
            assert np.abs(b - refs[name]).max() < tol, (exchange, name)

    # one collective per phase for the whole bundle: the fused quantized
    # step ships exactly 2 all-to-alls per phase (packed int4 codes +
    # fp16 scales) x 2 phases (reduce + broadcast) = 4 all-to-all ops
    # total, regardless of bundle width, and never all-gathers
    jitted, args = gas_step_for_dryrun(progs, lay, mesh,
                                       exchange='quantized')
    hlo = jitted.lower(*args).compile().as_text()
    kinds = [kind for kind, _, _ in hlo_collectives(hlo)]
    assert kinds.count('all-to-all') == 4, kinds
    assert 'all-gather' not in kinds

    z = shard_map_gas_many(progs, lay, mesh, iters=0, exchange='halo')
    V = g.num_vertices
    np.testing.assert_array_equal(
        z[0], np.full(V, np.float32(1.0 / V), np.float32))
    print('fused shard_map ok')
    """)


@pytest.mark.parametrize("exchange", ["halo", "dense"])
@pytest.mark.parametrize("k", [16, 32])
def test_mesh_gas_several_partitions_per_device(multidevice, k, exchange):
    """k = 16 and 32 partitions on 8 devices (2 and 4 a device): mesh
    PageRank to tol and CC through the session.  PageRank against
    ``simulate_gas``: the same iteration count, and values within 1e-6
    relative, because the two differ only in the float32 summation order
    of the dangling mass (a sum per device, then psum, against one sum
    over k), a few ulps.  Against the float64 reference at that count:
    1e-5 relative, the float32 rounding of ~40 iterations (5.9e-7
    measured).  CC is exact.  The halo step lowers to all-to-all and no
    all-gather."""
    multidevice(f"""
    import numpy as np
    from repro.analysis.ir import hlo_collectives
    from repro.core import CLUGPConfig, web_graph
    from repro.graph import reference_cc, reference_pagerank
    from repro.launch.mesh import make_graph_mesh
    from repro.session import GraphSession, SessionConfig

    g = web_graph(scale=10, edge_factor=6, seed=3)
    sess = GraphSession(SessionConfig(clugp=CLUGPConfig.optimized({k}),
                                      exchange="{exchange}"))
    sess.partition(g.src, g.dst, g.num_vertices).layout()
    mesh = make_graph_mesh({k})
    assert mesh.shape["parts"] == 8
    pr, it = sess.run("pagerank", iters=100, tol=1e-6, mesh=mesh,
                      return_iters=True)
    sim, it_sim = sess.run("pagerank", iters=100, tol=1e-6,
                           return_iters=True)
    assert it == it_sim and 0 < it < 100, (it, it_sim)
    assert np.max(np.abs(pr - sim) / sim) <= 1e-6
    ref = reference_pagerank(g.src, g.dst, g.num_vertices, iters=it)
    assert np.max(np.abs(pr - ref) / ref) <= 1e-5
    cc = sess.run("cc", iters=60, mesh=mesh)
    np.testing.assert_array_equal(
        cc, reference_cc(g.src, g.dst, g.num_vertices))
    if "{exchange}" == "halo":
        jitted, args = sess.dryrun_step("pagerank", mesh=mesh)
        kinds = [kind for kind, _, _ in
                 hlo_collectives(jitted.lower(*args).compile().as_text())]
        assert "all-to-all" in kinds and "all-gather" not in kinds, kinds
    print("ok")
    """)


def test_mesh_gas_compiles_once_and_refuses_what_it_cannot_route(
        multidevice):
    """A second mesh run of the same shapes neither traces the loop
    (``gas.run``'s ``traced`` is 0 and the trace counter holds) nor
    leaves a compile record; ``gas.run`` says how the partitions lie.
    The quantized (lossy payload) and ragged wires refuse two partitions
    a device, as does the fused driver.  ``make_graph_mesh`` takes the
    largest divisor of k that the 8 devices allow, warns where that
    leaves devices idle, and raises where only one device would hold
    every partition (a prime k above 8)."""
    multidevice("""
    import time
    import warnings
    import numpy as np
    from repro import obs
    from repro.core import CLUGPConfig, web_graph
    from repro.graph.engine import mesh_traces
    from repro.launch.mesh import make_graph_mesh
    from repro.session import GraphSession, SessionConfig

    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        assert [make_graph_mesh(k).shape["parts"]
                for k in (1, 4, 6, 8, 12, 16, 20, 64)] == [1, 4, 6, 8, 6, 8,
                                                            5, 8]
    assert [str(w.message) for w in caught] == [
        "k = 12 partitions on 6 of the 8 devices: 2 stay idle",
        "k = 20 partitions on 5 of the 8 devices: 3 stay idle"]
    for k in (13, 17):
        try:
            make_graph_mesh(k)
        except ValueError as e:
            assert f"divides k = {k}" in str(e), e
        else:
            raise AssertionError(f"k = {k} went onto one device")
    g = web_graph(scale=9, edge_factor=6, seed=1)
    sess = GraphSession(SessionConfig(clugp=CLUGPConfig.optimized(16),
                                      exchange="halo"))
    sess.partition(g.src, g.dst, g.num_vertices).layout()
    mesh = make_graph_mesh(16)
    first = sess.run("pagerank", iters=50, tol=1e-6, mesh=mesh)
    traces = mesh_traces()
    t0 = time.perf_counter()
    again = sess.run("pagerank", iters=50, tol=1e-6, mesh=mesh)
    recs = obs.spans(t0)
    np.testing.assert_array_equal(first, again)
    assert mesh_traces() == traces
    assert not [r for r in recs if r[0].startswith("compile")], recs
    attrs = next(r[4] for r in recs if r[0] == "gas.run")
    lay = sess.partition_layout
    assert attrs["traced"] == 0
    assert attrs["devices"] == 8 and attrs["parts_per_device"] == 2
    assert attrs["ici_bytes"] == 2 * 2 * lay.h_max * (16 - 2) * 4
    assert attrs["ici_bytes"] == lay.comm_bytes("halo", parts_per_device=2)

    for exchange, program in (("quantized", "pagerank"),
                              ("ragged", "cc"),
                              ("ragged_quantized", "pagerank")):
        try:
            sess.run(program, iters=3, exchange=exchange, mesh=mesh)
        except ValueError as e:
            assert "routes one partition per device" in str(e), e
        else:
            raise AssertionError(exchange + " ran at 2 partitions a device")
    try:
        sess.run_many(["pagerank", "centrality"], iters=3, mesh=mesh)
    except ValueError as e:
        assert "one partition per device" in str(e), e
    else:
        raise AssertionError("the fused driver ran at 2 a device")
    # exact payloads of the quantized wire ride the halo wire at any m
    np.testing.assert_array_equal(
        sess.run("cc", iters=40, exchange="quantized", mesh=mesh),
        sess.run("cc", iters=40, exchange="halo", mesh=mesh))
    print("ok")
    """)
