"""Mean device time (ms) a ``serving_tick`` execution spends in the expert
layers' routing and the index work between it and the grouped product: router
scores, group-limited top-k, the counts, each choice's place in its expert's
tile-aligned group (scopes ``block/moe/route``, ``block/moe/dispatch``), over
the executions that start in the traced slice: chip 0's self time of the
operations whose ``tf_op`` scope path
``benchmark/harness/scope_trace.py::BUCKETS`` books to ``moe_route``.  The
``tick_ms.*`` of a cell sum to the tick's mean execution time."""

from benchmark.harness import scope_trace


def read(trace, spans, run):
    return scope_trace.bucket_ms(
        trace, run, "serving_tick", "moe_route")
