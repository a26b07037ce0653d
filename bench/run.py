"""Run one cell of the benchmark once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its traffic mix and its metrics are found by
name (``BENCHMARK.json``, ``bench/configs``, ``bench/traffic``,
``bench/loops``, ``bench/metrics``, ``bench/limits``; see
``bench/README.md``).  Set-up builds the cell's
data from ``--seed`` and warms every program the window runs; the window
then lasts ``--seconds``; after it, what the window produced is held to
the plain references.  The last lines of standard error give each number
compared beside its limit; the last line of standard output is one JSON
object with ``correct``, ``attempted``, ``failed``, ``metrics``,
``device`` (and ``breakdown`` when traced) and, last, ``checks``.  With
``--trace 0`` the metrics are the cell's end-to-end ones, with
``--trace 1`` its per-layer ones, read from a device trace of the window.

Without a TPU, or with fewer chips than the cell asks for, it exits 2 and
prints no result.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

from harness import device, registry, trace  # noqa: E402
from harness.record import Recorder  # noqa: E402


@dataclass
class Ctx:
    """What a loop and the metric readers are handed."""
    cell: dict
    config: dict
    traffic: dict
    seed: int
    seconds: float
    rec: Recorder
    limits: dict = field(default_factory=dict)   # measured; run_cell adds
    setup_s: float = 0.0
    results: dict = field(default_factory=dict)
    trace: dict | None = None          # trace.load record of the window
    trace_window: tuple = (0, 0)       # its window, ns on the trace clock
    peaks: dict = field(default_factory=dict)
    loop: object = None                # the traffic loop, after a run
    tracer: object = None              # trace.Tracer of a traced run


def apply_overrides(config: dict, traffic: dict, sets: list) -> None:
    """``--set traffic.edge_rate=800`` style edits, for sweeps by hand."""
    for item in sets:
        path, value = item.split("=", 1)
        head, *keys = path.split(".")
        node = {"config": config, "traffic": traffic}[head]
        for key in keys[:-1]:
            node = node[key]
        node[keys[-1]] = json.loads(value)


def run_cell(ctx: Ctx, devs, traced: bool) -> dict:
    """Set-up, window, drain and check of one run; the result line."""
    rec = ctx.rec
    rec.listen_compiles()
    loop = ctx.loop = registry.loop(ctx.traffic["loop"])(ctx)
    # what must match exactly and what the configuration states, under
    # the cell's measured limits
    ctx.limits = {**loop.limits(), **ctx.limits}
    t0 = time.perf_counter()
    loop.setup()
    ctx.setup_s = time.perf_counter() - t0
    tdir = tempfile.mkdtemp(prefix="bench_trace_")
    try:
        if traced:
            ctx.tracer = trace.Tracer(tdir)
        loop.run(ctx.seconds)
        if traced:
            ctx.tracer.stop()
        loop.drain()
        peak = device.memory_peak_bytes(devs)
        if traced:
            t = time.perf_counter()
            ctx.trace = trace.load(tdir)
            ctx.trace_window = trace.window_of(ctx.trace)
            rec.count("trace_load_s", time.perf_counter() - t)
    finally:
        shutil.rmtree(tdir, ignore_errors=True)
    readings, attempted, failed = loop.check()
    ctx.results = loop.results()
    checks = {name: {"value": v, "limit": ctx.limits[name]}
              for name, v in readings.items()}
    dev = device.info(devs)
    dev["memory_peak_bytes"] = peak
    metrics = {}
    bench = registry.spec()
    for m in registry.metrics_of(bench, ctx.cell["name"], traced):
        value = registry.reader(m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    line = {"correct": all(c["value"] <= c["limit"] for c in checks.values())
            and failed == 0,
            "attempted": attempted, "failed": failed, "metrics": metrics,
            "device": dev}
    if traced:
        w = ctx.trace_window
        dev["busy_s"] = trace.busy_s(ctx.trace, w)
        dev["window_s"] = (w[1] - w[0]) * 1e-9
        line["breakdown"] = {"device_ops": trace.top_ops(ctx.trace, w),
                             "idle_gaps": trace.idle_gaps(ctx.trace, w)}
    line["checks"] = checks
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--set", action="append", default=[],
                    help="override a configuration or traffic value, "
                         "e.g. traffic.edge_rate=800 (sweeps by hand)")
    args = ap.parse_args(argv)

    bench = registry.spec()
    cell = registry.workload(bench, args.workload)
    config = registry.config(bench, cell["config"])
    traffic = registry.traffic(cell["traffic"])
    apply_overrides(config, traffic, args.set)
    try:
        devs = device.gate(cell["chips"])
    except device.NoChip as e:
        print(f"bench: {e}; this benchmark runs on the chip only",
              file=sys.stderr)
        return 2
    cache = device.enable_compile_cache()
    ctx = Ctx(cell=cell, config=config, traffic=traffic, seed=args.seed,
              seconds=args.seconds, rec=Recorder(annotate=bool(args.trace)),
              limits=registry.measured_limits(cell["name"]),
              peaks=device.peaks(devs[0].device_kind))
    line = run_cell(ctx, devs, bool(args.trace))
    t0, t1 = ctx.results["window"]
    info = {"cache": cache, "setup_s": ctx.setup_s,
            "compile_requests": len(ctx.rec.compiles),
            "cache_hits": len(ctx.rec.cache_hits),
            "compiles_in_window": ctx.rec.compilations(t0, t1)}
    info.update(ctx.rec.counters)
    info.update(ctx.loop.summary())
    print("[run] " + json.dumps(info), flush=True)
    for name, c in line["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
