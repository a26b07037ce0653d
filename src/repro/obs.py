"""Spans and compile records of the program's own layers.

    with obs.span("partition.attempt", m_cap=16384) as s:
        ...
        s.attrs["iters"] = n          # attributes known only at the end

Each span appends ``(name, start, end, parent, attrs)`` to a bounded
in-process ring, on ``time.perf_counter``; ``parent`` is the name of the
innermost span open on the same thread when it began.  While a profiler
trace runs, each span is also written into it as ``repro:<name>``
(``jax.profiler.TraceAnnotation``), so the trace holds it on its own
clock.

Names are ``<layer>.<step>``.  A span sits at a call into a layer, never
inside jit and never per edge, iteration or query, and it begins and ends
only where the code already waits for the device: a span never adds a
synchronisation of its own.

One ``jax.monitoring`` listener, installed on import, adds an instant
record (``start == end``) for every compile request JAX makes
(``compile``; ``attrs["seconds"]``) and every one the persistent cache
answered (``compile.cache_hit``).  Its ``parent`` is the innermost open
span of the compiling thread and ``attrs["stack"]`` the names of all the
open ones, outermost first.  A compilation is a request the cache did not
answer.

Read back with ``spans(t0, t1)``; ``dropped()`` counts the records the
ring has let go.
"""
from __future__ import annotations

import collections
import threading
import time

import jax

PREFIX = "repro:"
RING = 1 << 16
COMPILE = "compile"
CACHE_HIT = "compile.cache_hit"
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"

_ring: collections.deque = collections.deque(maxlen=RING)
_lock = threading.Lock()
_open = threading.local()
_counts = {"dropped": 0, "compilations": 0}


def _stack() -> list:
    try:
        return _open.stack
    except AttributeError:
        _open.stack = []
        return _open.stack


def _append(record: tuple, compiled: int = 0) -> None:
    with _lock:
        if len(_ring) == RING:
            _counts["dropped"] += 1
        _ring.append(record)
        _counts["compilations"] += compiled


class span:
    """Context manager: one record per use; ``as s`` gives the span, whose
    ``attrs`` may still be filled in before it ends."""
    __slots__ = ("name", "attrs", "start", "end", "parent", "_ann")

    def __init__(self, name: str, **attrs):
        self.name = name
        self.attrs = attrs

    def __enter__(self) -> "span":
        stack = _stack()
        self.parent = stack[-1].name if stack else None
        stack.append(self)
        self._ann = jax.profiler.TraceAnnotation(PREFIX + self.name)
        self._ann.__enter__()
        self.start = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.end = time.perf_counter()
        self._ann.__exit__(*exc)
        _stack().pop()
        _append((self.name, self.start, self.end, self.parent, self.attrs))


def _instant(name: str, compiled: int, **attrs) -> None:
    stack = _stack()
    t = time.perf_counter()
    _append((name, t, t, stack[-1].name if stack else None,
             {**attrs, "stack": tuple(s.name for s in stack)}), compiled)


def _on_duration(event: str, duration: float, **_) -> None:
    if event == _COMPILE_EVENT:
        _instant(COMPILE, 1, seconds=duration)


def _on_event(event: str, **_) -> None:
    if event == _CACHE_HIT_EVENT:
        _instant(CACHE_HIT, -1)


jax.monitoring.register_event_duration_secs_listener(_on_duration)
jax.monitoring.register_event_listener(_on_event)


def spans(t0: float = float("-inf"), t1: float = float("inf")) -> list:
    """The ring's records that start in [t0, t1], oldest first."""
    with _lock:
        return [r for r in _ring if t0 <= r[1] <= t1]


def dropped() -> int:
    """Records pushed out of the full ring since the process began."""
    return _counts["dropped"]


def compilations() -> int:
    """Compilations in this process so far: compile requests less those
    the persistent cache answered."""
    return _counts["compilations"]
