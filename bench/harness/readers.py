"""What the per-layer metric readers share: the window's spans, and the
device trace's window."""
from __future__ import annotations

from . import trace


def spans(ctx, name: str) -> list:
    """(name, start, end, attrs) of the window's spans called ``name``."""
    t0, t1 = ctx.results["window"]
    return [s for s in ctx.rec.spans if s[0] == name and t0 <= s[1] <= t1]


def mean_seconds(ctx, name: str):
    got = spans(ctx, name)
    if not got:
        return None
    return sum(s[2] - s[1] for s in got) / len(got)


def device_idle_pct(ctx):
    """100 × (1 − busy / window) of the traced window, busy averaged over
    the chips; nothing without a trace or a device op in it."""
    if ctx.trace is None:
        return None
    w = ctx.trace_window
    busy = trace.busy_s(ctx.trace, w)
    if busy <= 0 or w[1] <= w[0]:
        return None
    return 100.0 * (1.0 - busy / ((w[1] - w[0]) * 1e-9))


def gas_iterations(ctx) -> int:
    return sum(int(j["pagerank_iters"]) + int(j["cc_iters"])
               for j in ctx.results.get("jobs", []))
