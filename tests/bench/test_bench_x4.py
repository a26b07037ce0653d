"""The four-chip batch cell ``web-batch-x4`` on the CPU: its configuration
and cell are found by name, its three readers give the hand arithmetic
on a hand-made record and nothing without the program's counters, and a
tiny run of the ``jobs`` loop on four virtual devices (stream split over
four, eight partitions on a mesh of four) comes out correct."""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "bench"))
sys.path.insert(0, str(ROOT / "src"))

from harness import registry  # noqa: E402
from harness.record import Recorder  # noqa: E402
from repro import obs  # noqa: E402

CELL = "web-batch-x4"
NEW = ("exchange_ici_kb_per_iter.batch",
       "exchange_device_ms_per_iter.batch", "gas_traces.batch")


def test_cell_and_configuration_resolve_by_name():
    spec = registry.spec(ROOT)
    cell = registry.workload(spec, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "web-uk2002-x4", "batch", 4)
    cfg = registry.config(spec, cell["config"], ROOT)
    part, graph = cfg["partition"], cfg["graph"]
    assert (part["k"], part["backend"], part["nodes"]) == (16, "sharded", 4)
    assert cfg["analytics"]["mesh"] and cfg["analytics"]["exchange"] == "halo"
    assert graph["num_edges"] // part["nodes"] == 2 ** 20
    assert registry.measured_limits(CELL)["pagerank_gap"] > 0
    names = [m["name"] for m in registry.metrics_of(spec, CELL, True)]
    assert set(NEW) <= set(names)
    for m in spec["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL] and m["moves"] == "job_s"
    assert [m["name"] for m in registry.metrics_of(spec, CELL, False)] == [
        "setup_s", "job_s"]


MS = 1e-3
OFF = 5_000_000.0          # trace ns = perf_counter s × 1e9 + OFF
ANCHORS = {"partition": (1, 20), "layout": (20, 30), "pagerank": (30, 60),
           "cc": (60, 90)}
RUN = {"devices": 4, "parts_per_device": 4, "ici_bytes": 300_000}
RECORDS = [
    ("gas.run", 32 * MS, 58 * MS, "gas.pagerank", {**RUN, "traced": 0}),
    ("gas.pagerank", 30 * MS, 59 * MS, None, {"iters": 6}),
    ("gas.run", 62 * MS, 88 * MS, "gas.cc",
     {**RUN, "ici_bytes": 200_000, "traced": 1}),
    ("gas.cc", 60 * MS, 89 * MS, None, {"iters": 4}),
]
A2A = "%all-to-all.3 = f32[4,4,4,8]{3,2,1,0} all-to-all(%x), dimensions={0}"
FUSION = "%fusion.7 = f32[4,64]{1,0} fusion(%p), kind=kLoop"
# device ops, ms: two collectives inside each gas.run span, one outside;
# the second device's collectives run half as long
OPS = {"/device:TPU:0": [(10, 12, A2A), (33, 40, FUSION), (40, 42, A2A),
                         (44, 45, A2A), (63, 66, A2A), (70, 71, A2A)],
       "/device:TPU:1": [(10, 12, A2A), (40, 41, A2A), (63, 64.5, A2A)]}
# collective ms inside gas.run: device 0 2 + 1 + 3 + 1 = 7, device 1
# 1 + 1.5 = 2.5; mean 4.75 over 10 iterations
EXPECTED = {"exchange_ici_kb_per_iter.batch": 250.0,
            "exchange_device_ms_per_iter.batch": 4.75 / 10,
            "gas_traces.batch": 1}


def _ns(ms):
    return ms * MS * 1e9 + OFF


class Ctx:
    def __init__(self):
        self.rec = Recorder()
        self.rec.spans = [(name, a * MS, b * MS, {"job": 0})
                          for name, (a, b) in ANCHORS.items()]
        self.results = {"window": (0.0, 100 * MS),
                        "jobs": [{"pagerank_iters": 6, "cc_iters": 4}]}
        self.trace_window = (_ns(0), _ns(100))
        self.trace = {
            "devices": {d: [[_ns(a), _ns(b) - _ns(a), op]
                            for a, b, op in ops] for d, ops in OPS.items()},
            "host": [[_ns(a), _ns(b) - _ns(a), name]
                     for name, (a, b) in ANCHORS.items()]
            + [[_ns(0), _ns(100) - _ns(0), "traced"]]}


@pytest.mark.parametrize("name", NEW)
def test_new_reader_on_a_handmade_run(name, monkeypatch):
    monkeypatch.setattr(obs, "spans", lambda t0, t1: [
        r for r in RECORDS if t0 <= r[1] <= t1])
    assert registry.reader(name)(Ctx()) == pytest.approx(EXPECTED[name],
                                                         rel=1e-9)


@pytest.mark.parametrize("name", NEW)
def test_new_reader_without_the_counters_reads_nothing(name, monkeypatch):
    """The one-chip engine, and a program older than these counters, put
    no devices, ``ici_bytes`` or ``traced`` on ``gas.run``."""
    bare = [(n, a, b, p, {}) for n, a, b, p, _ in RECORDS
            if n == "gas.pagerank"]
    monkeypatch.setattr(obs, "spans", lambda t0, t1: bare)
    assert registry.reader(name)(Ctx()) is None


@pytest.mark.multidevice
def test_jobs_loop_on_four_virtual_devices_is_correct(multidevice):
    """A tiny web-batch-x4: the stream split over four devices, eight
    partitions on a mesh of four, through ``system.meshes`` and the
    ``jobs`` loop; correct, and the window traced nothing."""
    out = multidevice(f"""
    import sys
    sys.path.insert(0, {str(ROOT / 'bench')!r})
    import jax
    import run as bench_run
    from harness import registry
    from harness.record import Recorder

    spec = registry.spec()
    w = registry.workload(spec, "web-batch-x4")
    config = registry.config(spec, w["config"])
    traffic = registry.traffic(w["traffic"])
    bench_run.apply_overrides(config, traffic, [
        "config.graph.num_vertices=1024", "config.graph.num_edges=14000",
        "config.partition.k=8"])
    ctx = bench_run.Ctx(cell=w, config=config, traffic=traffic,
                        seed=2**31 + 13, seconds=1.0,
                        limits=registry.measured_limits("web-batch-x4"),
                        rec=Recorder(), peaks={{}})
    line = bench_run.run_cell(ctx, jax.devices()[:4], False)
    assert line["correct"], line["checks"]
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert ctx.loop.gas_mesh.shape["parts"] == 4
    assert registry.reader("gas_traces.batch")(ctx) == 0
    kb = registry.reader("exchange_ici_kb_per_iter.batch")(ctx)
    assert kb > 0
    print("jobs", line["attempted"], "kb", kb)
    """, n_devices=4)
    assert "jobs" in out
