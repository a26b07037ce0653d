"""The program's own spans (``repro.obs``) as the metric readers use
them: cut to the run's window, put on the device trace's clock, and the
device's idle time split across them.

The program and the recorder both stamp ``time.perf_counter``, so the
window needs no mapping.  The trace has its own clock: the offset is
found from the traced job's benchmark spans (``partition``, ``layout``,
``pagerank``, ``cc`` of job 0), which are both in the recorder and, as
``bench:<name>`` host events, in the trace.  A program without
``repro.obs`` leaves no spans, and every function here then gives None.
"""
from __future__ import annotations

import statistics

import numpy as np

from . import system  # noqa: F401  (puts the program on the path)
from . import trace

ANCHORS = ("partition", "layout", "pagerank", "cc")
MAX_RESIDUAL_NS = 100_000
COMPILE, CACHE_HIT = "compile", "compile.cache_hit"


def records(ctx):
    """The program's records that start inside the window, or None."""
    try:
        from repro import obs
    except ImportError:
        return None
    t0, t1 = ctx.results["window"]
    return obs.spans(t0, t1)


def per_job(ctx, names) -> tuple | None:
    """(spans, seconds) of the window's spans named in ``names``, each
    over the jobs the window ran; None without spans or jobs."""
    recs, jobs = records(ctx), ctx.results.get("jobs")
    if recs is None or not jobs:
        return None
    got = [r for r in recs if r[0] in names]
    return len(got) / len(jobs), sum(r[2] - r[1] for r in got) / len(jobs)


def compilations(recs) -> int:
    """Compile requests less those the persistent cache answered."""
    return (sum(r[0] == COMPILE for r in recs)
            - sum(r[0] == CACHE_HIT for r in recs))


def clock_offset(bench_spans, host, window) -> tuple | None:
    """(offset, largest residual), in ns: trace time = perf_counter × 1e9
    + offset, the median over the four anchors' start differences.  None
    unless each anchor is found once in both, or where any start or end
    lands more than ``MAX_RESIDUAL_NS`` from where the offset puts it."""
    ours: dict = {}
    for name, t0, t1, attrs in bench_spans:
        if name in ANCHORS and attrs.get("job") == 0:
            ours.setdefault(name, []).append((t0 * 1e9, t1 * 1e9))
    theirs: dict = {}
    for start, dur, name in host:
        if name in ANCHORS and window[0] <= start < window[1]:
            theirs.setdefault(name, []).append((start, start + dur))
    if any(len(d.get(n, ())) != 1 for d in (ours, theirs) for n in ANCHORS):
        return None
    pairs = [(ours[n][0], theirs[n][0]) for n in ANCHORS]
    off = statistics.median(t[0] - r[0] for r, t in pairs)
    worst = max(abs(t[i] - r[i] - off) for r, t in pairs for i in (0, 1))
    if worst > MAX_RESIDUAL_NS:
        return None
    return off, worst


def on_trace_clock(ctx) -> list | None:
    """(name, start_ns, end_ns, parent) of the program's spans that
    overlap the traced window, on the trace clock; instants left out.
    The mapping's largest residual goes to the run's counters (the
    ``[run]`` log line), as ``program_clock_residual_us``."""
    recs = records(ctx)
    if recs is None or ctx.trace is None:
        return None
    w = ctx.trace_window
    mapping = clock_offset(ctx.rec.spans, ctx.trace["host"], w)
    if mapping is None:
        return None
    off, worst = mapping
    ctx.rec.counters["program_clock_residual_us"] = worst * 1e-3
    out = []
    for name, t0, t1, parent, _ in recs:
        a, b = t0 * 1e9 + off, t1 * 1e9 + off
        if b > a and a < w[1] and b > w[0]:
            out.append((name, a, b, parent))
    return out


def busy_in(union: np.ndarray, a: float, b: float) -> float:
    """ns of the disjoint busy intervals ``union`` inside [a, b)."""
    if union.size == 0:
        return 0.0
    return float(np.clip(np.minimum(union[:, 1], b)
                         - np.maximum(union[:, 0], a), 0, None).sum())


def idle_split(ctx) -> dict | None:
    """{span name or "none": idle seconds} of the traced window: each
    stretch of idle device time goes to the innermost program span over
    it (the shortest), or to "none" where no span is; averaged over the
    traced devices.  Also noted in the run's counters, as
    ``program_idle_s``."""
    spans = on_trace_clock(ctx)
    if spans is None or not ctx.trace["devices"]:
        return None
    t0, t1 = ctx.trace_window
    cuts = sorted({t0, t1} | {min(max(x, t0), t1)
                              for _, a, b, _ in spans for x in (a, b)})
    owners = []
    for a, b in zip(cuts, cuts[1:]):
        mid = (a + b) / 2
        cover = [(e - s, name) for name, s, e, _ in spans if s <= mid < e]
        owners.append((a, b, min(cover)[1] if cover else "none"))
    devs = ctx.trace["devices"]
    out: dict = {}
    for ev in devs.values():
        union = trace.busy_union(ev, t0, t1)
        for a, b, who in owners:
            idle = (b - a) - busy_in(union, a, b)
            if idle > 0:
                out[who] = out.get(who, 0.0) + idle * 1e-9 / len(devs)
    ctx.rec.counters["program_idle_s"] = dict(
        sorted(out.items(), key=lambda kv: -kv[1]))
    return out


def busy_under(ctx, name: str) -> float | None:
    """Device seconds busy inside the traced window's program spans
    called ``name``, averaged over the traced devices."""
    spans = on_trace_clock(ctx)
    if spans is None or not ctx.trace["devices"]:
        return None
    got = [(a, b) for n, a, b, _ in spans if n == name]
    if not got:
        return None
    t0, t1 = ctx.trace_window
    devs = ctx.trace["devices"]
    tot = 0.0
    for ev in devs.values():
        union = trace.busy_union(ev, t0, t1)
        tot += sum(busy_in(union, a, b) for a, b in got)
    return tot * 1e-9 / len(devs)
