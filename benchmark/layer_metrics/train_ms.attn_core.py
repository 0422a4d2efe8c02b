"""Mean device time (ms) a ``train_step`` execution spends in the attention
itself, scope ``block/attn/core``: the rotation of queries and keys and the
flash kernels (``flash_fwd`` / ``flash_bwd``; the banded ``window_flash_*``
of a sliding layer under ``block/attn/window``) — forward, backward and the
backward's recomputed forward together: chip 0's self time of the operations
whose scope path holds the scope, over the executions that start in the
traced slice (``benchmark/harness/train_scope_trace.py``)."""

from benchmark.harness import train_scope_trace


def read(trace, spans, run):
    return train_scope_trace.bucket_ms(trace, run, "attn_core")
