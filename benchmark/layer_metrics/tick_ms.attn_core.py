"""Mean device time (ms) a ``serving_tick`` execution spends in the attention
itself: the flash-decode kernels or their einsum fallback with the masks
around them, the work list of the busy slots' live blocks
(``tick/work_list``), and a gated delta-rule layer's convolution, gates and
state update (scopes ``block/attn/core``, ``block/mla/core``,
``block/kda/conv``, ``block/kda/gate``, ``block/kda/state_update``).  At least
``decode_attn_ms_per_tick`` + ``kda_step_ms_per_tick``: the kernels lie inside
it, over the executions that start in the traced slice: chip 0's self time of
the operations whose ``tf_op`` scope path
``benchmark/harness/scope_trace.py::BUCKETS`` books to ``attn_core``.  The
``tick_ms.*`` of a cell sum to the tick's mean execution time."""

from benchmark.harness import scope_trace


def read(trace, spans, run):
    return scope_trace.bucket_ms(
        trace, run, "serving_tick", "attn_core")
