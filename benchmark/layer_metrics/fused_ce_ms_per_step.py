"""Device time a train step spends in the fused cross-entropy's three kernels
(``fused_ce_stats``, ``fused_ce_dh``, ``fused_ce_dtable``), from the traced
slice: the ops line's events by the kernel's own name."""

from benchmark.harness import kernel_costs


def read(trace, spans, run):
    return kernel_costs.ms_per_step(trace, run, "fused_ce")
