"""Mean device time (ms) a ``serving_tick`` execution spends in the attention
halves' projections: first norm, q/k/v or the MLA / KDA projections, rotation,
the output gate (``block/attn/gate``), the output projection and the residual
add (scopes ``block/attn/proj``, ``block/mla/proj``, ``block/kda/proj``), over
the executions that start in the traced slice: chip 0's self time of the
operations whose ``tf_op`` scope path
``benchmark/harness/scope_trace.py::BUCKETS`` books to ``attn_proj``.  The
``tick_ms.*`` of a cell sum to the tick's mean execution time."""

from benchmark.harness import scope_trace


def read(trace, spans, run):
    return scope_trace.bucket_ms(
        trace, run, "serving_tick", "attn_proj")
