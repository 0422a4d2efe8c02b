"""Mean device time (ms) of a ``serving_tick`` execution that no scope of
``benchmark/harness/scope_trace.py::BUCKETS`` names: the tick's mean execution
time less the named ``tick_ms.*`` — operations without a vocabulary scope,
operations whose name two scopes share, and the bubbles between operations,
printed apart as a free line."""

from benchmark.harness import scope_trace


def read(trace, spans, run):
    return scope_trace.bucket_ms(
        trace, run, "serving_tick", scope_trace.UNSCOPED)
