"""Benchmark-side spans and the traced slice.

A span is a ``jax.profiler.TraceAnnotation`` (so it lands in the profiler's
own trace, on the clock the device's events are on) and a host-clock record
(so a reader can sum it in an untraced run too).  Spans inside the program
are a later PR's (PERF.md, Open questions)."""

import contextlib
import os
import shutil
import time

#: names by which the trace reduction labels idle gaps
GAP_SPANS = ("input", "dispatch", "submit", "engine_step", "readback")
from benchmark.harness.trace_reduce import SLICE


class Spans:
    def __init__(self):
        self.records = []          # (name, start_s, end_s), perf_counter

    @contextlib.contextmanager
    def span(self, name: str):
        import jax

        with jax.profiler.TraceAnnotation(name):
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.records.append((name, t0, time.perf_counter()))


class Tracer:
    """Wraps one steady slice of the window in ``jax.profiler`` tracing."""

    def __init__(self, directory: str, enabled: bool):
        self.directory, self.enabled = directory, enabled
        self.started = self.stopped = None
        self._ann = None

    @property
    def pending(self) -> bool:
        return self.enabled and self.started is None

    @property
    def active(self) -> bool:
        return self.started is not None and self.stopped is None

    def start(self):
        import jax

        shutil.rmtree(self.directory, ignore_errors=True)
        os.makedirs(self.directory, exist_ok=True)
        jax.profiler.start_trace(self.directory)
        self._ann = jax.profiler.TraceAnnotation(SLICE)
        self._ann.__enter__()
        self.started = time.perf_counter()

    def stop(self):
        import jax

        self.stopped = time.perf_counter()
        self._ann.__exit__(None, None, None)
        jax.profiler.stop_trace()


class CompileCounter:
    """Counts programs JAX compiles (or loads from its cache) while open:
    inside a measured window there may be none."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.n = self.hits = self.misses = 0
        self.open = False
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_cache)

    def _on_cache(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _on(self, event, duration, **_):
        if self.open and event == self.EVENT:
            self.n += 1
