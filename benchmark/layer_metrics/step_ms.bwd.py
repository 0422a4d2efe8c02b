"""Mean device time (ms) a ``train_step`` execution spends in the backward pass:
the operations under ``loss_grad`` whose scope path holds ``transpose(`` (a
rematerialised forward inside it counts: it runs there), over the executions
that start in the traced slice: chip 0's self time of the operations
``benchmark/harness/scope_trace.py::BUCKETS`` books to ``bwd``.  The four
``step_ms.*`` sum to the step's mean execution time."""

from benchmark.harness import scope_trace


def read(trace, spans, run):
    return scope_trace.bucket_ms(
        trace, run, "train_step", "bwd")
