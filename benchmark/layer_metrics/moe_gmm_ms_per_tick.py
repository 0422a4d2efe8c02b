"""Device time a decode tick spends in the grouped expert product
(``moe_gmm``, three calls an expert layer), from the traced slice: the
kernel's events that start inside a ``serving_tick`` execution, over those
executions.  ``None`` where the tick runs no such kernel."""

from benchmark.harness import serve_kernel_costs


def read(trace, spans, run):
    seconds = serve_kernel_costs.seconds_per_tick(trace, "moe_gmm")
    return None if seconds is None else seconds * 1e3
