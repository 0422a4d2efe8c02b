"""Device time a train step spends in the grouped expert product's kernels:
every Pallas kernel whose name holds ``moe_gmm`` — the forward (twice where
the layer is recomputed), ``dX`` (the same kernel on the transposed weights)
and ``moe_gmm_dw`` — from the traced slice, over the slice's steps."""

from benchmark.harness import kernel_costs


def read(trace, spans, run):
    return kernel_costs.ms_per_step(trace, run, "moe_gmm")
