"""Mean device time (ms) of a ``train_step`` execution outside ``loss_grad`` and
``optimizer``: the step's mean execution time less ``step_ms.fwd``, ``.bwd``
and ``.optimizer`` — operations without a scope, operations whose name two
scopes share, and the bubbles between operations, printed apart as a free
line."""

from benchmark.harness import scope_trace


def read(trace, spans, run):
    return scope_trace.bucket_ms(
        trace, run, "train_step", scope_trace.UNSCOPED)
