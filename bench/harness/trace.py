"""Device trace of a run's window, and its reduction to numbers.

``Tracer`` runs ``jax.profiler`` over part of the window; ``load`` reads
the written ``.xplane.pb`` into a plain record:

    {"devices": {plane: [[start_ns, duration_ns, op], ...]},
     "host":    [[start_ns, duration_ns, span], ...]}

holding each device's ``XLA Ops`` line and the harness's own host spans
(``bench:<name>``), both on the profiler's clock.  The functions below
reduce such a record; ``tests/bench`` checks them on a small record cut
from a chip trace.
"""
from __future__ import annotations

import glob
import os

import numpy as np

from .record import SPAN_PREFIX

OPS_LINE = "XLA Ops"
COLLECTIVES = ("all-reduce", "all-gather", "all-to-all", "collective-permute",
               "reduce-scatter", "psum", "ppermute")


class Tracer:
    """The profiler over part of a run's window: from ``start`` to
    ``stop`` (a loop may stop it early, as the batch loop does after its
    first job, to keep the trace small enough to read in seconds).  The
    traced part is the host span ``traced`` in the trace itself."""

    def __init__(self, trace_dir: str):
        self.dir = trace_dir
        self._ann = None

    def start(self) -> None:
        import jax
        jax.profiler.start_trace(self.dir)
        self._ann = jax.profiler.TraceAnnotation(SPAN_PREFIX + "traced")
        self._ann.__enter__()

    def stop(self) -> None:
        if self._ann is None:
            return
        import jax
        self._ann.__exit__(None, None, None)
        self._ann = None
        jax.profiler.stop_trace()


def load(trace_dir: str) -> dict:
    """The newest ``.xplane.pb`` under ``trace_dir`` as a plain record."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(files[-1])
    devices, host = {}, []
    names: dict = {}       # one string per distinct op: traces repeat them
    for plane in data.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[plane.name] = [
                        [int(e.start_ns), int(e.duration_ns),
                         names.setdefault(e.name, e.name)]
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        host.append([int(e.start_ns), int(e.duration_ns),
                                     e.name[len(SPAN_PREFIX):]])
    return {"devices": devices, "host": host}


def window_of(rec: dict, name: str = "traced") -> tuple:
    """(start_ns, end_ns) of the host span ``name``; without one, or where
    no device event falls inside it, the extent of the device events."""
    ev = [e for evs in rec["devices"].values() for e in evs]
    spans = [(s, s + d) for s, d, n in rec["host"] if n == name]
    if spans and (not ev or any(spans[-1][0] <= s < spans[-1][1]
                                for s, _, _ in ev)):
        return spans[-1]
    if not ev:
        return 0, 0
    return min(s for s, _, _ in ev), max(s + d for s, d, _ in ev)


def _clipped(events, t0: int, t1: int) -> np.ndarray:
    """(n, 2) [start, end) intervals clipped to the window, sorted."""
    if not events:
        return np.zeros((0, 2), np.int64)
    a = np.array([[s, s + d] for s, d, _ in events], np.int64)
    a[:, 0] = np.maximum(a[:, 0], t0)
    a[:, 1] = np.minimum(a[:, 1], t1)
    a = a[a[:, 1] > a[:, 0]]
    return a[np.argsort(a[:, 0], kind="stable")]


def busy_union(events, t0: int, t1: int) -> np.ndarray:
    """Disjoint [start, end) intervals in which some op ran."""
    a = _clipped(events, t0, t1)
    if a.shape[0] == 0:
        return a
    out = [list(a[0])]
    for s, e in a[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.array(out, np.int64)


def busy_s(rec: dict, window: tuple) -> float:
    """Seconds in which an op ran, averaged over the traced devices."""
    t0, t1 = window
    devs = rec["devices"]
    if not devs:
        return 0.0
    tot = [float((u[:, 1] - u[:, 0]).sum()) if u.size else 0.0
           for u in (busy_union(ev, t0, t1) for ev in devs.values())]
    return float(np.mean(tot)) * 1e-9


def op_seconds(rec: dict, window: tuple, match) -> float:
    """Device seconds of the ops ``match(name)`` accepts, summed over the
    ops and averaged over the traced devices."""
    t0, t1 = window
    devs = rec["devices"]
    if not devs:
        return 0.0
    tot = 0.0
    for ev in devs.values():
        sel = [e for e in ev if match(e[2])]
        a = _clipped(sel, t0, t1)
        tot += float((a[:, 1] - a[:, 0]).sum())
    return tot / len(devs) * 1e-9


def op_count(rec: dict, window: tuple, match) -> float:
    """Number of executions of the ops ``match`` accepts, averaged over
    the traced devices."""
    t0, t1 = window
    devs = rec["devices"]
    if not devs:
        return 0.0
    n = sum(sum(1 for s, d, name in ev if match(name) and t0 <= s < t1)
            for ev in devs.values())
    return n / len(devs)


def is_collective(name: str) -> bool:
    """An op whose own opcode is a collective (not one that only reads a
    collective's result)."""
    rest = name.partition(" = ")[2]
    return any(f" {c}{tail}(" in rest for c in COLLECTIVES
               for tail in ("", "-start", "-done"))


OPCODES = ("custom-call", "fusion", "sort", "copy", "scatter", "gather",
           "dynamic-update-slice", "dynamic-slice", "reduce", "select")
CONTAINERS = (" while(", " conditional(", " call(")


def short_name(op: str) -> str:
    """``%fusion.222 (fusion)`` for an op's full HLO text."""
    head, _, rest = op.partition(" = ")
    for code in COLLECTIVES + OPCODES:
        if f" {code}(" in rest or f" {code}-start(" in rest:
            return f"{head} ({code})"
    return head


def top_ops(rec: dict, window: tuple, n: int = 10) -> list:
    """[[op, seconds]] of the leaf ops that took most device time
    (averaged over devices), longest first.  Loops and conditionals are
    left out: their time is their body's ops'."""
    t0, t1 = window
    devs = rec["devices"]
    tot: dict = {}
    for ev in devs.values():
        for s, d, name in ev:
            if t0 <= s < t1 and not any(c in name for c in CONTAINERS):
                key = short_name(name)
                tot[key] = tot.get(key, 0) + min(d, t1 - s)
    scale = 1e-9 / max(1, len(devs))
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[name, v * scale] for name, v in ranked]


def idle_gaps(rec: dict, window: tuple, n: int = 10,
              ignore=("window", "traced")) -> list:
    """[[host span, seconds]]: the device's idle time inside the window
    (first traced device), each gap given to the innermost host span
    that covers its midpoint ("none" where no span does), summed per
    span name and longest first."""
    t0, t1 = window
    devs = rec["devices"]
    if not devs:
        return []
    u = busy_union(next(iter(devs.values())), t0, t1)
    edges = [t0] + [x for iv in u for x in iv] + [t1]
    spans = [(s, s + d, name) for s, d, name in rec["host"]
             if name not in ignore]
    tot: dict = {}
    for a, b in zip(edges[0::2], edges[1::2]):
        if b <= a:
            continue
        mid = (a + b) / 2
        cover = [(e - s, name) for s, e, name in spans if s <= mid < e]
        who = min(cover)[1] if cover else "none"
        tot[who] = tot.get(who, 0) + (b - a)
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[name, v * 1e-9] for name, v in ranked]
