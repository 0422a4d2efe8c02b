"""Mean device time (ms) a ``serving_tick`` execution spends in the dense FFN
halves: second norm, the dense MLP / SwiGLU, the residual add, and an expert
layer's shared expert (scopes ``block/mlp``, ``block/moe/shared``), over the
executions that start in the traced slice: chip 0's self time of the
operations whose ``tf_op`` scope path
``benchmark/harness/scope_trace.py::BUCKETS`` books to ``ffn_dense``.  The
``tick_ms.*`` of a cell sum to the tick's mean execution time."""

from benchmark.harness import scope_trace


def read(trace, spans, run):
    return scope_trace.bucket_ms(
        trace, run, "serving_tick", "ffn_dense")
