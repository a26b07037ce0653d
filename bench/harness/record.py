"""What a run records on the host: spans around each call into a layer,
counts, and the compilations JAX reports.

Spans are kept in memory as (name, start, end) on ``time.perf_counter``.
While a device trace is taken each span is also written into the
profiler's own timeline (``jax.profiler.TraceAnnotation``, named
``bench:<name>``), so the trace reduction can say what the host was doing
in each gap of the device's work.
"""
from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field

import jax

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
SPAN_PREFIX = "bench:"


@dataclass
class Recorder:
    spans: list = field(default_factory=list)
    counters: dict = field(default_factory=dict)
    compiles: list = field(default_factory=list)   # perf_counter stamps
    cache_hits: list = field(default_factory=list)
    annotate: bool = False
    _listening: bool = False

    @contextlib.contextmanager
    def span(self, name: str, **attrs):
        ann = (jax.profiler.TraceAnnotation(SPAN_PREFIX + name)
               if self.annotate else contextlib.nullcontext())
        t0 = time.perf_counter()
        try:
            with ann:
                yield
        finally:
            self.spans.append((name, t0, time.perf_counter(), attrs))

    def count(self, name: str, n: float = 1) -> None:
        self.counters[name] = self.counters.get(name, 0) + n

    def listen_compiles(self) -> None:
        """Stamp every compile request JAX reports from now on, and every
        one the persistent cache answered: a compilation is a request
        the cache did not answer."""
        if self._listening:
            return
        self._listening = True

        def on_duration(event: str, duration: float, **_):
            if event == COMPILE_EVENT:
                self.compiles.append(time.perf_counter())

        def on_event(event: str, **_):
            if event == CACHE_HIT_EVENT:
                self.cache_hits.append(time.perf_counter())

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def compilations(self, t0: float, t1: float) -> int:
        """Compilations (requests less cache hits) in [t0, t1]."""
        return (sum(t0 <= t <= t1 for t in self.compiles)
                - sum(t0 <= t <= t1 for t in self.cache_hits))
