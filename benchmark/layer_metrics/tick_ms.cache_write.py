"""Mean device time (ms) a ``serving_tick`` execution spends in the writes of the
new token's rows, latent rows and ring rows into the pool (scope
``cache_write``, wherever it nests), over the executions that start in the
traced slice: chip 0's self time of the operations whose ``tf_op`` scope path
``benchmark/harness/scope_trace.py::BUCKETS`` books to ``cache_write``.  The
``tick_ms.*`` of a cell sum to the tick's mean execution time."""

from benchmark.harness import scope_trace


def read(trace, spans, run):
    return scope_trace.bucket_ms(
        trace, run, "serving_tick", "cache_write")
