"""The benchmark harness on the CPU at tiny sizes: everything is found by
name, the copied references agree with the program's oracles, the
generator keeps its shapes, the kernel's roofline counts are the hand
arithmetic, and the trace reduction reads a small recorded chip trace."""
from __future__ import annotations

import gzip
import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"
sys.path.insert(0, str(BENCH))

from harness import graphs, reference, registry, roofline, trace  # noqa: E402

SPEC = registry.spec(ROOT)
TINY = {"generator": "crawl", "seed": 5, "num_vertices": 1024,
        "num_edges": 14000,
        "edge_factor": 36, "avg_site": 40, "beta": 0.08, "alpha": 2.1,
        "hub_zipf": 1.5}


# ------------------------------------------------------------- registry

@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_every_cell_finds_its_files(cell):
    w = registry.workload(SPEC, cell)
    cfg = registry.config(SPEC, w["config"], ROOT)
    assert cfg["name"] == w["config"]
    assert callable(registry.loop(registry.traffic(w["traffic"])["loop"]))
    assert (BENCH / "limits" / f"{cell}.json").is_file()
    for traced in (False, True):
        ms = registry.metrics_of(SPEC, cell, traced)
        assert ms, (cell, traced)
        for m in ms:
            assert callable(registry.reader(m["name"]))
    assert "setup_s" in [m["name"] for m in registry.metrics_of(
        SPEC, cell, False)]


@pytest.mark.parametrize("what", ["workload", "config", "traffic", "loop",
                                  "reader"])
def test_unknown_names_fail(what):
    call = {"workload": lambda: registry.workload(SPEC, "no-such-cell"),
            "config": lambda: registry.config(SPEC, "no-such-config", ROOT),
            "traffic": lambda: registry.traffic("no-such-traffic"),
            "loop": lambda: registry.loop("no-such-loop"),
            "reader": lambda: registry.reader("no_such.metric")}[what]
    with pytest.raises(KeyError):
        call()


@pytest.mark.parametrize("name", sorted(p.stem for p in
                                        (BENCH / "loops").glob("*.py")))
def test_every_loop_file_has_the_loop_protocol(name):
    loop = registry.loop(name)
    for method in ("limits", "setup", "run", "drain", "results", "summary",
                   "check", "control"):
        assert callable(getattr(loop, method)), (name, method)


def test_config_files_state_their_cut():
    for c in SPEC["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert cfg["source"] == c["source"]
        assert "edge_factor" in cfg["assumed"]
        g = cfg["graph"]
        per = g["num_edges"] / g["num_vertices"]
        assert abs(per - 16.1) / 16.1 < 0.10


# ------------------------------------------------------------- generator

@pytest.mark.parametrize("seed", [0, 2**31 + 7])
def test_crawl_has_fixed_shapes(seed):
    src, dst = graphs.generate(TINY, seed)
    assert src.shape == dst.shape == (TINY["num_edges"],)
    assert src.dtype == np.int32
    deg = np.bincount(np.concatenate([src, dst]), minlength=1024)
    assert deg.shape == (1024,) and deg.min() > 0       # no isolated page
    assert (src != dst).all()
    assert np.unique(src.astype(np.int64) * 1024 + dst).shape[0] == src.size
    again = graphs.generate(TINY, seed)
    assert np.array_equal(src, again[0]) and np.array_equal(dst, again[1])
    # one draw, relabelled: crawl order under the seed's permutation
    src0, dst0 = graphs.draw(TINY)
    inv = np.argsort(graphs.relabel(src0, dst0, 1024, seed))
    assert (np.diff(inv[src]) >= 0).all()
    assert np.array_equal(inv[src], src0) and np.array_equal(inv[dst], dst0)


def _wcc_rounds(src, dst, n):
    op = reference.label_op("cc", src, dst, n)
    label, rounds = reference.labels_cold(n, "cc"), 0
    while True:
        nxt = op.step(label)
        rounds += 1
        if np.array_equal(nxt, label):
            return rounds
        label = nxt


def test_relabelling_keeps_the_wcc_work():
    """Every seed's naming keeps each component's least page its least,
    so WCC runs as many rounds under every seed."""
    n = 2048
    rng = np.random.default_rng(0)
    # chains of many lengths: their rounds hang on where the least id sits
    src = rng.permutation(n)[:1500]
    dst = np.roll(src, 1)
    cut = np.sort(rng.choice(1500, 60, replace=False))
    src, dst = np.delete(src, cut), np.delete(dst, cut)
    wcc0 = reference.wcc(src, dst, n)
    rounds = set()
    for seed in (1, 2**31 + 11, 7, 8, 9):
        pi = graphs.relabel(src, dst, n, seed)
        assert np.array_equal(np.sort(pi), np.arange(n))
        # the least page of each component keeps the least id
        assert np.array_equal(pi[wcc0],
                              reference.wcc(pi[src], pi[dst], n)[pi])
        rounds.add(_wcc_rounds(pi[src], pi[dst], n))
    assert len(rounds) == 1


def test_crawl_refuses_too_few_edges():
    with pytest.raises(ValueError):
        graphs.generate(dict(TINY, num_edges=10**6), 0)


def test_arrivals_follow_the_sites():
    src, dst = graphs.generate(TINY, 3)
    a_src, a_dst = graphs.arrivals(TINY, 4000, 3)
    assert a_src.shape == (4000,) and (a_src != a_dst).all()
    assert a_src.max() < 1024 and a_dst.max() < 1024
    # most arrivals stay near their source in crawl order, as the
    # crawl's links do
    inv = np.argsort(graphs.relabel(*graphs.draw(TINY), 1024, 3))
    near = np.abs(inv[a_src] - inv[a_dst]) < 40 * 40
    assert near.mean() > 0.8


# ------------------------------------------------------------ references

@pytest.fixture(scope="module")
def small_graph():
    src, dst = graphs.generate(TINY, 11)
    return src, dst, TINY["num_vertices"]


def test_references_agree_with_the_programs_oracles(small_graph):
    sys.path.insert(0, str(ROOT / "src"))
    from repro.core import metrics
    from repro.graph import (reference_cc, reference_degree,
                             reference_labelprop, reference_pagerank)
    src, dst, n = small_graph
    np.testing.assert_allclose(
        reference.pagerank(src, dst, n, 30, 0.85),
        reference_pagerank(src, dst, n, iters=30), rtol=1e-12)
    assert np.array_equal(reference.wcc(src, dst, n),
                          reference_cc(src, dst, n))
    cc = reference.label_op("cc", src, dst, n).run(
        reference.labels_cold(n, "cc"), 60)
    assert np.array_equal(cc, reference_cc(src, dst, n))
    lp = reference.label_op("labelprop", src, dst, n).run(
        reference.labels_cold(n, "labelprop"), 25)
    assert np.array_equal(lp, reference_labelprop(src, dst, n, iters=25))
    assert np.array_equal(reference.degree(src, dst, n),
                          reference_degree(src, dst, n))
    assign = np.random.default_rng(0).integers(0, 4, src.size)
    assert reference.replication_factor(src, dst, assign, n, 4) == \
        metrics.replication_factor(src, dst, assign, n, 4)
    assert reference.balance(assign, 4) == metrics.load_balance(assign, 4)


def test_layout_check_accepts_the_layout_and_finds_a_fault(small_graph):
    sys.path.insert(0, str(ROOT / "src"))
    from repro.graph import build_layout
    src, dst, n = small_graph
    assign = np.random.default_rng(1).integers(0, 4, src.size)
    lay = build_layout(src, dst, assign, n, 4)
    assert reference.layout_faults(lay, src, dst, assign, n, 4) == 0
    own = reference.masters(src, dst, assign, n, 4)
    for p in range(4):
        sel = lay.vert_mask[p] & lay.is_master[p]
        assert (own[lay.vert_gid[p][sel]] == p).all()
    lay.edge_dst[1, 0] = lay.edge_src[1, 0]       # a self loop: no such edge
    assert reference.layout_faults(lay, src, dst, assign, n, 4) > 0


def test_bf16_control_departs_from_float64(small_graph):
    src, dst, n = small_graph
    f64 = reference.pagerank(src, dst, n, 40, 0.85)
    b16 = reference.pagerank(src, dst, n, 40, 0.85, precision="bf16")
    gap = np.max(np.abs(b16 - f64) / f64)
    assert 1e-4 < gap < 0.5
    x = np.array([1.0, 1.0 + 2.0**-7, 2.0**-20], np.float32)
    assert np.array_equal(reference._bf16(x), x)          # representable
    tie = np.array([1.0 + 2.0**-8, 1.0 + 3 * 2.0**-8], np.float32)
    assert reference._bf16(tie).tolist() == [1.0, 1.0 + 2.0**-6]


# -------------------------------------------------------------- roofline

def test_game_kernel_counts_are_the_hand_arithmetic():
    flops, nbytes = roofline.game_bestresponse(m=1000, k=16)
    assert flops == 12 * 1000 * 16 == 192000
    # cut mass 1000*16*4, per cluster 5 float32 words, 16 loads
    assert nbytes == 64000 + 20000 + 64 == 84064
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    t, bound = roofline.least_seconds(flops, nbytes, peaks)
    assert bound == "memory" and t == pytest.approx(84064 / 819e9)


KERNEL_OP = ("%branch_1_fun.3 = (s32[1,32768]{1,0:T(1,128)}, "
             "f32[1,32768]{1,0:T(1,128)}) custom-call(f32[32768,128]{1,0} "
             "%p0, f32[1,32768]{1,0} %p1), custom_call_target="
             '"tpu_custom_call"')


def test_game_kernel_roofline_reader():
    read = registry.reader("game_kernel_roofline.batch")

    class Ctx:
        config = {"partition": {"k": 16}}
        peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
        results = {"jobs": [{"stats": {"num_clusters": 1000}}]}
        trace_window = (0, 10_000_000)
        trace = {"devices": {"/device:TPU:0": [
            [1000, 20000, KERNEL_OP], [30000, 20000, KERNEL_OP],
            [60000, 5000, "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %a)"]]},
            "host": []}

    got = read(Ctx)
    assert got == pytest.approx(100 * 2 * (84064 / 819e9) / 40e-6)


# ----------------------------------------------------------------- trace

def _recorded():
    path = Path(__file__).with_name("trace_small.json.gz")
    with gzip.open(path, "rt") as f:
        return json.load(f)


def test_trace_reduction_on_a_recorded_trace():
    rec = _recorded()
    exp = rec["expected"]
    w = tuple(rec["window"])
    assert trace.busy_s(rec, w) == pytest.approx(exp["busy_s"], rel=1e-9)
    top = trace.top_ops(rec, w, n=3)
    assert [t[0] for t in top] == [trace.short_name(n)
                                   for n in exp["top3"]]
    assert trace.op_seconds(rec, w, lambda s: s == exp["top3"][0]) == \
        pytest.approx(top[0][1], rel=1e-9)
    gaps = trace.idle_gaps(rec, w)
    window_s = (w[1] - w[0]) * 1e-9
    assert sum(g[1] for g in gaps) == pytest.approx(
        window_s - trace.busy_s(rec, w), rel=1e-6)


def test_trace_reduction_by_hand():
    ar = "%all-reduce.1 = f32[4]{0} all-reduce(f32[4]{0} %x)"
    reads = "%fusion.2 = f32[4]{0} fusion(f32[4]{0} %all-reduce.1)"
    rec = {"devices": {"/device:TPU:0": [[0, 10, "a"], [5, 10, "b"],
                                          [30, 10, ar]],
                       "/device:TPU:1": [[0, 40, "a"], [45, 5, reads]]},
           "host": [[0, 100, "traced"], [16, 10, "layout"],
                    [10, 50, "job"]]}
    w = (0, 50)
    assert trace.busy_union(rec["devices"]["/device:TPU:0"], *w).tolist() \
        == [[0, 15], [30, 40]]
    assert trace.busy_s(rec, w) == pytest.approx((25 + 45) / 2 * 1e-9)
    assert trace.top_ops(rec, w, n=1) == [["a", pytest.approx(25e-9)]]
    assert trace.short_name(ar) == "%all-reduce.1 (all-reduce)"
    assert trace.op_seconds(rec, w, trace.is_collective) == \
        pytest.approx(10 / 2 * 1e-9)
    assert trace.op_count(rec, w, lambda s: s == "a") == 1.0
    # gap 15-30 (mid 22.5) lies in "layout" inside "job"; gap 40-50 in job
    assert trace.idle_gaps(rec, w) == [["layout", pytest.approx(15e-9)],
                                       ["job", pytest.approx(10e-9)]]
    assert trace.window_of(rec) == (0, 100)
