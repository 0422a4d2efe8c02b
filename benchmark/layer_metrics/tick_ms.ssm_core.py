"""Mean device time (ms) a ``serving_tick`` execution spends in the
selective state-space layers' own work, scopes ``block/mamba/conv`` (the
short convolution and its window) and ``block/mamba/core`` (the ``ssm_step``
kernel, the rates and the layout of its operands).  At least
``ssm_step_ms_per_tick``: the kernel lies inside it
(``benchmark/harness/ssm_scope_trace.py``)."""

from benchmark.harness import ssm_scope_trace


def read(trace, spans, run):
    return ssm_scope_trace.bucket_ms(trace, run, "serving_tick", "ssm_core")
