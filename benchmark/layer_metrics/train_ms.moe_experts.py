"""Mean device time (ms) a ``train_step`` execution spends in the expert
layers' products, scope ``block/moe/gmm``: the gather of the routed rows,
the three grouped products forward, ``dX`` and ``dW`` (``moe_gmm``,
``moe_gmm_dw``) and the gather-combine — forward, backward and the
backward's recomputed forward together: chip 0's self time of the operations
whose scope path holds the scope, over the executions that start in the
traced slice (``benchmark/harness/train_scope_trace.py``)."""

from benchmark.harness import train_scope_trace


def read(trace, spans, run):
    return train_scope_trace.bucket_ms(trace, run, "moe_experts")
