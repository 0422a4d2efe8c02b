"""Mean device time (ms) a ``train_step`` execution spends in the forward pass:
the operations under ``loss_grad`` whose scope path holds no ``transpose(``,
over the executions that start in the traced slice: chip 0's self time of the
operations ``benchmark/harness/scope_trace.py::BUCKETS`` books to ``fwd``.
The four ``step_ms.*`` sum to the step's mean execution time.  The cell's
first reader of the split: prints the whole table — each phase by ``embed``,
``block/attn``, ``block/mlp``, ``head_ce`` — as free lines."""

from benchmark.harness import scope_trace


def read(trace, spans, run):
    return scope_trace.bucket_ms(
        trace, run, "train_step", "fwd", say_table=True)
