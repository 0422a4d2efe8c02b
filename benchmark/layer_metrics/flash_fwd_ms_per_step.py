"""Device time a train step spends in the flash-attention forward kernel
(``flash_fwd``, one call a layer), from the traced slice: the ops line's
events by the kernel's own name."""

from benchmark.harness import kernel_costs


def read(trace, spans, run):
    return kernel_costs.ms_per_step(trace, run, "flash_fwd")
