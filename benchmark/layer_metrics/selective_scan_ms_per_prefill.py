"""Device time a prefill spends in the selective scan (``selective_scan``,
one call a state layer), from the traced slice: the kernel's events that
start inside a ``serving_prefill_*`` execution, over those executions.
``None`` where the prefills run no such kernel."""

from benchmark.harness import window_kernel_costs


def read(trace, spans, run):
    got = window_kernel_costs.kernel_in_prefills(trace, "selective_scan")
    return None if got is None else got[0] / len(got[1]) * 1e3
