"""GAS program library (repro.graph.engine): every program in the
registry matches its NumPy oracle under every exchange wire,
fused multi-program execution matches the per-program runs, iters=0
returns init values untouched (the dry-run byte parser depends on it),
and the fused comm model / CI ordering gate behave."""
import numpy as np
import pytest

from repro.dist.halo import EXCHANGE_NAMES
from repro.graph import (CC_SENTINEL, FusedGAS, PROGRAM_NAMES, build_layout,
                         default_num_seeds, fuse_programs, get_program,
                         reference_bfs, reference_cc, reference_centrality,
                         reference_degree, reference_labelprop,
                         reference_pagerank, reference_ppr, reference_sssp,
                         simulate_gas, simulate_gas_many)

from conftest import random_graph_and_assign

# repro.launch.dryrun mutates XLA_FLAGS (512 virtual devices) at import,
# so it must only be imported inside tests, after jax has initialized —
# a module-level import at collection time would change the whole tier-1
# process's device count (test_graph_quantized.py does the same)

EXCHANGES = ("dense", "halo", "quantized")

# per-program iteration budget (int programs need the frontier to close)
# and oracle thunk; float programs are judged within the quantized
# error-feedback tolerance, int programs must be bit-exact everywhere
ITERS = {"pagerank": 30, "cc": 40, "labelprop": 40, "sssp": 40, "bfs": 40,
         "degree": 2, "centrality": 30, "ppr": 30}

# float tolerance against the oracle after ITERS iterations, per wire:
# the top-Δ wire ships only the heaviest quarter of each hop's delta, so
# 30 iterations leave it within 1e-3 (its error keeps shrinking, to under
# 1e-6 by 100; test_graph_quantized pins both); every other single-program
# wire is within 1e-5
FLOAT_TOL = {"ragged_quantized": 1e-3}


@pytest.fixture(scope="module")
def case():
    src, dst, n, assign = random_graph_and_assign(0, 8, n=400)
    lay = build_layout(src, dst, assign, n, 8)
    refs = {
        "pagerank": reference_pagerank(src, dst, n, iters=30),
        "cc": reference_cc(src, dst, n),
        "labelprop": reference_labelprop(src, dst, n, iters=40),
        "sssp": reference_sssp(src, dst, n, iters=40),
        "bfs": reference_bfs(src, dst, n, iters=40),
        "degree": reference_degree(src, dst, n),
        "centrality": reference_centrality(src, dst, n, iters=30),
        "ppr": reference_ppr(src, dst, n, iters=30),
    }
    return src, dst, n, lay, refs


# ------------------------------------------------- program × exchange matrix

@pytest.mark.parametrize("exchange", EXCHANGE_NAMES)
@pytest.mark.parametrize("name", PROGRAM_NAMES)
def test_program_matches_oracle(case, name, exchange):
    _, _, n, lay, refs = case
    got = simulate_gas(get_program(name, n), lay, iters=ITERS[name],
                       exchange=exchange)
    ref = refs[name]
    if np.issubdtype(got.dtype, np.floating):
        assert np.abs(got - ref).max() < FLOAT_TOL.get(exchange, 1e-5)
    else:
        # min/int payloads ship exactly on every backend — incl. quantized,
        # whose EF path is bypassed for non-lossy payloads
        np.testing.assert_array_equal(got.astype(np.int64), ref)


def test_registry_rejects_unknown_program():
    with pytest.raises(ValueError, match="unknown program"):
        get_program("triangle-count", 10)


def test_sssp_unreachable_vertices_keep_sentinel(case):
    src, dst, n, lay, refs = case
    # seeds are gid < num_seeds for labelprop; SSSP has one source — any
    # vertex the oracle leaves at the sentinel must stay there on-device
    got = simulate_gas(get_program("sssp", n), lay, iters=40,
                       exchange="halo")
    unreachable = refs["sssp"] == CC_SENTINEL
    assert (got[unreachable] == CC_SENTINEL).all()
    assert got[0] == 0      # the source itself


@pytest.fixture(scope="module", params=["np", "jit"])
def partitioner_layout(request):
    from repro.core import CLUGPConfig, partition, web_graph
    g = web_graph(scale=9, edge_factor=6, seed=1)
    res = partition(g.src, g.dst, g.num_vertices, CLUGPConfig(k=4),
                    backend=request.param)
    lay = build_layout(g.src, g.dst, res.assign, g.num_vertices, 4)
    return request.param, g, lay


ORACLES = {
    "pagerank": lambda g: reference_pagerank(g.src, g.dst, g.num_vertices,
                                             iters=30),
    "cc": lambda g: reference_cc(g.src, g.dst, g.num_vertices),
    "labelprop": lambda g: reference_labelprop(g.src, g.dst, g.num_vertices,
                                               iters=40),
    "sssp": lambda g: reference_sssp(g.src, g.dst, g.num_vertices,
                                     iters=40),
    "bfs": lambda g: reference_bfs(g.src, g.dst, g.num_vertices, iters=40),
    "degree": lambda g: reference_degree(g.src, g.dst, g.num_vertices),
    "centrality": lambda g: reference_centrality(g.src, g.dst,
                                                 g.num_vertices, iters=30),
    "ppr": lambda g: reference_ppr(g.src, g.dst, g.num_vertices, iters=30),
}


@pytest.mark.parametrize("name", PROGRAM_NAMES)
def test_programs_match_oracle_on_partitioner_layouts(partitioner_layout,
                                                      name):
    """The oracle match holds on real CLUGP partitions from the host and
    device partitioner backends, not just random assignments (the
    sharded backend's layout is exercised in the multidevice suite —
    device count locks at first jax init)."""
    backend, g, lay = partitioner_layout
    ref = ORACLES[name](g)
    prog = get_program(name, g.num_vertices)
    for exchange in EXCHANGES:
        got = simulate_gas(prog, lay, iters=ITERS[name], exchange=exchange)
        if np.issubdtype(got.dtype, np.floating):
            assert np.abs(got - ref).max() < 1e-5, (name, exchange)
        else:
            np.testing.assert_array_equal(
                got.astype(np.int64), ref,
                err_msg=f"{backend}/{name}/{exchange}")


# ------------------------------------------------------------ fused driver

@pytest.mark.parametrize("exchange", EXCHANGE_NAMES)
def test_fused_f32_bundle_matches_references(case, exchange):
    _, _, n, lay, refs = case
    names = ("pagerank", "ppr", "centrality")
    outs = simulate_gas_many([get_program(p, n) for p in names], lay,
                             iters=30, exchange=exchange)
    # the fused quantized wire is int4 (vs int8 separate) so its EF
    # tolerance is wider; the top-Δ wire keeps its 30-iteration bound;
    # the exact wires' fused math is the separate math
    tol = {"quantized": 5e-4}.get(exchange, FLOAT_TOL.get(exchange, 1e-5))
    for name, got in zip(names, outs):
        assert np.abs(got - refs[name]).max() < tol, name


@pytest.mark.parametrize("exchange", EXCHANGE_NAMES)
def test_fused_i32_bundle_bit_exact(case, exchange):
    _, _, n, lay, refs = case
    names = ("sssp", "bfs", "labelprop")
    progs = [get_program(p, n) for p in names]
    outs = simulate_gas_many(progs, lay, iters=40, exchange=exchange)
    for name, prog, got in zip(names, progs, outs):
        np.testing.assert_array_equal(got.astype(np.int64), refs[name],
                                      err_msg=f"{name}/{exchange}")
        # fused ≡ single-program run, bit for bit (same exchange)
        np.testing.assert_array_equal(
            got, simulate_gas(prog, lay, iters=40, exchange=exchange),
            err_msg=f"{name}/{exchange} fused vs single")


def test_fused_rejects_heterogeneous_and_empty(case):
    _, _, n, _, _ = case
    with pytest.raises(ValueError, match="combine|dtype"):
        FusedGAS((get_program("pagerank", n), get_program("cc", n)))
    with pytest.raises(ValueError, match="at least one"):
        fuse_programs([])
    # fuse_programs normalizes to a FusedGAS with stable identity fields
    fused = fuse_programs([get_program("sssp", n), get_program("bfs", n)])
    assert fused.combine == "min" and fused.name == "sssp+bfs"


# -------------------------------------------------------- iters=0 regression

@pytest.mark.parametrize("exchange", EXCHANGE_NAMES)
def test_iters_zero_returns_init(case, exchange):
    """Regression: a trip-count-0 fori_loop still bakes its collectives
    into the HLO, so iters=0 must skip the loop entirely and return the
    program's init values unchanged."""
    _, _, n, lay, _ = case
    pr0 = simulate_gas(get_program("pagerank", n), lay, iters=0,
                       exchange=exchange)
    np.testing.assert_array_equal(
        pr0, np.full(n, np.float32(1.0 / n), np.float32))
    d0, b0 = simulate_gas_many(
        [get_program("sssp", n), get_program("bfs", n)], lay, iters=0,
        exchange=exchange)
    for got in (d0, b0):
        assert got[0] == 0
        assert (got[1:] == CC_SENTINEL).all()


# ------------------------------------------------------- fused comm model

def test_fused_comm_model_beats_separate(case):
    from repro.launch.dryrun import FUSED_GATE_RATIO
    _, _, _, lay, _ = case
    for nprog in (2, 3, 4):
        fused = lay.comm_bytes("quantized", programs=nprog, fused=True)
        sep = lay.comm_bytes("quantized", programs=nprog, lossy=True)
        assert fused == lay._bytes_fused_quantized(nprog)
        # int4 halves the lane payload; the fp16 subgroup scales cost 16
        # bytes/row vs the separate int8 row's 4 — a net win once
        # h_max > 24, which every padded layout satisfies
        assert fused < sep
    # at the CI gate scale (h_max == 200) the modelled ratio clears the
    # 0.6 gate with margin: (200//2 + 16) / (200 + 4) ≈ 0.569
    h = 200
    assert (h // 2 + lay.FUSED_SCALE_BYTES) < FUSED_GATE_RATIO * (h + 4)


def test_check_graph_ordering_fused_gate():
    from repro.launch.dryrun import check_graph_ordering

    def cell(prog, ex, wire, **kw):
        return {"program": prog, "exchange": ex, "status": "ok",
                "collective_bytes_wire": wire, **kw}

    sep = [cell("pagerank", "dense", 1000), cell("pagerank", "halo", 100),
           cell("pagerank", "quantized", 30, lossy_payload=True),
           cell("ppr", "dense", 1000), cell("ppr", "halo", 100),
           cell("ppr", "quantized", 30, lossy_payload=True)]
    good = cell("pagerank+ppr", "quantized", 30, fused=True,
                fused_programs=["pagerank", "ppr"])
    assert check_graph_ordering(sep + [good]) == []
    # fused step shipping ≥ 0.6 × Σ separate fails the gate
    bad = dict(good, collective_bytes_wire=40)
    msgs = check_graph_ordering(sep + [bad])
    assert len(msgs) == 1 and "fused" in msgs[0]
    # a fused row whose bundle lacks separate quantized cells is itself
    # a violation (the gate can't silently vacuously pass)
    orphan = dict(good, fused_programs=["pagerank", "centrality"])
    msgs = check_graph_ordering(sep + [orphan])
    assert len(msgs) == 1 and "centrality" in msgs[0]


# --------------------------------------------------------------- seeds

def test_default_num_seeds_floor():
    assert default_num_seeds(10) == 2
    assert default_num_seeds(1024) == 4
