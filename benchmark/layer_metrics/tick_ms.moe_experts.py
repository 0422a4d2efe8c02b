"""Mean device time (ms) a ``serving_tick`` execution spends in the held experts'
product: the gather of the routed rows, the three grouped products and the
gather-combine (scope ``block/moe/gmm``).  At least ``moe_gmm_ms_per_tick``:
the kernel lies inside it, over the executions that start in the traced slice:
chip 0's self time of the operations whose ``tf_op`` scope path
``benchmark/harness/scope_trace.py::BUCKETS`` books to ``moe_experts``.  The
``tick_ms.*`` of a cell sum to the tick's mean execution time."""

from benchmark.harness import scope_trace


def read(trace, spans, run):
    return scope_trace.bucket_ms(
        trace, run, "serving_tick", "moe_experts")
