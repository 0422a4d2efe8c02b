"""Device time a prefill spends in the banded flash-attention forward
(``window_flash_fwd``, one call a windowed layer), from the traced slice:
the kernel's events that start inside a ``serving_prefill_*`` execution,
over those executions.  ``None`` where the prefills run no such kernel."""

from benchmark.harness import window_kernel_costs


def read(trace, spans, run):
    got = window_kernel_costs.kernel_in_prefills(trace, "window_flash_fwd")
    return None if got is None else got[0] / len(got[1]) * 1e3
