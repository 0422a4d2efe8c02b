"""Mean device time (ms) a ``serving_tick`` execution spends in the
selective state-space layers' projections, scope ``block/mamba/proj``: the
layer's first norm, ``W_in``, ``W_x`` with the three inner norms, ``W_dt``
and its softplus, the gate, ``W_out`` and the residual add — chip 0's self
time over the executions that start in the traced slice
(``benchmark/harness/ssm_scope_trace.py``: ``scope_trace.py``'s split with
the ``block/mamba/*`` rows before its table)."""

from benchmark.harness import ssm_scope_trace


def read(trace, spans, run):
    return ssm_scope_trace.bucket_ms(trace, run, "serving_tick", "ssm_proj")
