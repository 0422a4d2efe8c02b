"""Mean device time (ms) a ``train_step`` execution spends in the expert
layers' routing and index work: router scores, softmax, top-k, gates and the
counts (scope ``block/moe/route``) and each choice's place in its expert's
tile-aligned group (``block/moe/dispatch``) — forward, backward and the
backward's recomputed forward together: chip 0's self time of the operations
whose scope path holds either scope, over the executions that start in the
traced slice (``benchmark/harness/train_scope_trace.py``)."""

from benchmark.harness import train_scope_trace


def read(trace, spans, run):
    return train_scope_trace.bucket_ms(trace, run, "moe_route")
