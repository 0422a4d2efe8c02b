"""Mean device time (ms) a ``train_step`` execution spends in the optimizer's
update (scope ``optimizer``), over the executions that start in the traced
slice: chip 0's self time of the operations
``benchmark/harness/scope_trace.py::BUCKETS`` books to ``optimizer``.  The
four ``step_ms.*`` sum to the step's mean execution time."""

from benchmark.harness import scope_trace


def read(trace, spans, run):
    return scope_trace.bucket_ms(
        trace, run, "train_step", "optimizer")
