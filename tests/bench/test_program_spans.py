"""The readers of the program's own spans (``harness/program_spans.py``):
the trace clock recovered from the benchmark's anchor spans, the idle
split across overlapping spans, each reader on a hand-made run, and the
host readers on a tiny run of the batch loop on the CPU."""
from __future__ import annotations

import sys
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "bench"))
sys.path.insert(0, str(ROOT / "src"))

import run as bench_run  # noqa: E402
from harness import program_spans, registry  # noqa: E402
from harness.record import Recorder  # noqa: E402
from repro import obs  # noqa: E402

MS = 1e-3
OFF = 7_000_123.0          # trace ns = perf_counter s × 1e9 + OFF
# the traced job's benchmark spans, job 0, in ms on the program's clock
ANCHORS = {"partition": (1, 50), "layout": (50, 60), "pagerank": (60, 80),
           "cc": (80, 99)}


def _rec(name, a, b, parent=None, **attrs):
    return (name, a * MS, b * MS, parent, attrs)


# the program's records of that job, in ms
RECORDS = [
    _rec("partition.attempt", 1, 40, "partition", attempt=0),
    _rec("partition.fetch", 40, 41, "partition"),
    _rec("partition.contract", 41, 45, "partition"),
    _rec("partition.summary", 45, 49, "partition"),
    _rec("partition", 1, 49),
    _rec("layout.build", 50, 59),
    _rec("gas.upload", 60, 62, "gas.pagerank"),
    _rec("compile", 63, 63, "gas.run", stack=("gas.pagerank", "gas.run")),
    _rec("compile.cache_hit", 63, 63, "gas.run"),
    _rec("gas.run", 62, 78, "gas.pagerank"),
    _rec("gas.collect", 78, 79, "gas.pagerank"),
    _rec("gas.pagerank", 60, 79, iters=6),
    _rec("gas.upload", 80, 81, "gas.cc"),
    _rec("compile", 85, 85, "gas.run", stack=("gas.cc", "gas.run")),
    _rec("gas.run", 81, 97, "gas.cc"),
    _rec("gas.collect", 97, 98, "gas.cc"),
    _rec("gas.cc", 80, 98, iters=4),
]
BUSY = [(2, 39), (63, 77), (82, 96)]         # device ops, ms
# idle (ms) by the innermost span over it, from RECORDS and BUSY by hand
IDLE = {"none": 6, "partition.attempt": 2, "partition.fetch": 1,
        "partition.contract": 4, "partition.summary": 4, "layout.build": 9,
        "gas.upload": 3, "gas.run": 4, "gas.collect": 2}


def _ns(ms):
    return ms * MS * 1e9 + OFF


def _host(anchors=ANCHORS):
    return [[_ns(a), _ns(b) - _ns(a), name] for name, (a, b) in
            anchors.items()] + [[_ns(0), _ns(100) - _ns(0), "traced"]]


def _bench_spans(anchors=ANCHORS):
    return [(name, a * MS, b * MS, {"job": 0})
            for name, (a, b) in anchors.items()]


class Ctx:
    def __init__(self, host=None):
        self.rec = Recorder()
        self.rec.spans = _bench_spans()
        self.results = {"window": (0.0, 100 * MS),
                        "jobs": [{"pagerank_iters": 6, "cc_iters": 4}]}
        self.trace_window = (_ns(0), _ns(100))
        self.trace = {"devices": {"/device:TPU:0": [
            [_ns(a), _ns(b) - _ns(a), "%fusion.1 = f32[8]{0} fusion()"]
            for a, b in BUSY]}, "host": host or _host()}


@pytest.fixture
def handmade(monkeypatch):
    monkeypatch.setattr(obs, "spans", lambda t0, t1: [
        r for r in RECORDS if t0 <= r[1] <= t1])
    return Ctx()


def test_clock_offset_is_recovered():
    w = (_ns(0), _ns(100))
    off, worst = program_spans.clock_offset(_bench_spans(), _host(), w)
    assert off == pytest.approx(OFF, abs=1e-3)
    assert worst == pytest.approx(0, abs=1e-3)
    # one end 50 µs late: within the limit, and the offset holds
    late = {**ANCHORS, "cc": (80, 99.05)}
    off, worst = program_spans.clock_offset(_bench_spans(), _host(late), w)
    assert off == pytest.approx(OFF, abs=1e-3)
    assert worst == pytest.approx(50e3, rel=1e-6)


@pytest.mark.parametrize("anchors", [
    {**ANCHORS, "layout": (50.2, 60)},            # a start 200 µs off
    {**ANCHORS, "pagerank": (60, 80.15)},         # an end 150 µs off
    {k: v for k, v in ANCHORS.items() if k != "cc"},   # an anchor missing
])
def test_clock_offset_refuses_anchors_that_disagree(anchors):
    w = (_ns(0), _ns(100))
    assert program_spans.clock_offset(_bench_spans(), _host(anchors),
                                      w) is None


def test_idle_split_across_overlapping_spans(handmade):
    split = program_spans.idle_split(handmade)
    assert split == pytest.approx({k: v * MS for k, v in IDLE.items()},
                                  abs=1e-12)
    assert handmade.rec.counters["program_clock_residual_us"] == \
        pytest.approx(0, abs=1e-6)


def test_a_gap_straddling_two_spans_is_split_between_them(monkeypatch):
    ctx = Ctx()
    # one gap, 10-30 ms, across a span that ends at 20 and one from 15
    recs = [_rec("a.first", 5, 20), _rec("b.second", 15, 40)]
    monkeypatch.setattr(obs, "spans", lambda t0, t1: recs)
    ctx.trace["devices"] = {"/device:TPU:0": [
        [_ns(0), _ns(10) - _ns(0), "x"], [_ns(30), _ns(100) - _ns(30), "x"]]}
    split = program_spans.idle_split(ctx)
    # 10-15 in a.first alone; 15-20 in both, the shorter (a.first) wins;
    # 20-30 in b.second alone
    assert split == pytest.approx({"a.first": 10 * MS, "b.second": 10 * MS},
                                  abs=1e-12)


EXPECTED = {"partition_host_s.batch": 8 * MS,
            "partition_attempts.batch": 1.0,
            "window_compiles.batch": 1,
            "gas_device_ms_per_iter.batch": (14 + 14) / 10,
            "idle_unattributed_pct.batch": 100 * 6 / 35}


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_on_a_handmade_run(name, handmade):
    got = registry.reader(name)(handmade)
    assert got == pytest.approx(EXPECTED[name], rel=1e-9)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_without_program_spans_reads_nothing(name, monkeypatch):
    import repro
    monkeypatch.delattr(repro, "obs")       # a program without spans:
    monkeypatch.setitem(sys.modules, "repro.obs", None)    # import fails
    assert registry.reader(name)(Ctx()) is None


def test_new_entries_name_their_readers():
    spec = registry.spec(ROOT)
    entries = {m["name"]: m for m in spec["per_layer"]}
    for name in EXPECTED:
        assert entries[name]["workloads"] == ["web-batch"]
        assert entries[name]["moves"] == "job_s"


TINY = ["config.graph.num_vertices=1024", "config.graph.num_edges=14000",
        "config.partition.k=4"]


def test_host_readers_on_a_tiny_batch_run():
    bench = registry.spec(ROOT)
    w = registry.workload(bench, "web-batch")
    traffic = registry.traffic(w["traffic"])
    config = registry.config(bench, w["config"], ROOT)
    bench_run.apply_overrides(config, traffic, TINY)
    ctx = bench_run.Ctx(cell=w, config=config, traffic=traffic,
                        seed=2**31 + 11, seconds=0.6,
                        limits=registry.measured_limits("web-batch"),
                        rec=Recorder(), peaks={})
    jax.clear_caches()
    try:
        line = bench_run.run_cell(ctx, jax.devices()[:1], False)
    finally:
        jax.clear_caches()
    assert line["correct"], line["checks"]
    jobs = len(ctx.results["jobs"])
    t0, t1 = ctx.results["window"]
    recs = obs.spans(t0, t1)
    # at this size the first run of the body overflows its guessed caps
    # and runs again with larger ones: every job alike
    attempts = [r[4]["attempt"] for r in recs if r[0] == "partition.attempt"]
    runs = registry.reader("partition_attempts.batch")(ctx)
    assert runs == len(attempts) / jobs and runs == int(runs) >= 1
    assert attempts == list(range(int(runs))) * jobs
    assert registry.reader("window_compiles.batch")(ctx) == 0
    host = registry.reader("partition_host_s.batch")(ctx)
    partition = registry.reader("partition_s.batch")(ctx)
    assert 0 < host < partition
    # the device readers need a trace
    assert registry.reader("gas_device_ms_per_iter.batch")(ctx) is None
    assert registry.reader("idle_unattributed_pct.batch")(ctx) is None
    # every job left its spans in the window: one partition each
    assert sum(r[0] == "partition" for r in recs) == jobs
