"""Device time a decode tick spends in the selective state-space layers'
one-token state update (``ssm_step``, one call a state layer), from the
traced slice: the kernel's events that start inside a ``serving_tick``
execution, over those executions.  ``None`` where the tick runs no such
kernel."""

from benchmark.harness import serve_kernel_costs


def read(trace, spans, run):
    seconds = serve_kernel_costs.seconds_per_tick(trace, "ssm_step")
    return None if seconds is None else seconds * 1e3
