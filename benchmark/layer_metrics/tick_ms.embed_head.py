"""Mean device time (ms) a ``serving_tick`` execution spends in the token pick,
the embedding lookup, the final norm, the logits over the vocabulary, the
selection and the packing of the program's one int32 result (scopes
``tick/embed``, ``tick/head``), over the executions that start in the traced
slice: chip 0's self time of the operations whose ``tf_op`` scope path
``benchmark/harness/scope_trace.py::BUCKETS`` books to ``embed_head``.  The
``tick_ms.*`` of a cell sum to the tick's mean execution time.  The cell's
first reader of the split: prints the whole table by leaf scope as free lines."""

from benchmark.harness import scope_trace


def read(trace, spans, run):
    return scope_trace.bucket_ms(
        trace, run, "serving_tick", "embed_head", say_table=True)
