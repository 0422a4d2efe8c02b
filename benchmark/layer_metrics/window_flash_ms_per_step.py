"""Device time a train step spends in the banded flash-attention kernels
(``window_flash_fwd`` and ``window_flash_bwd``, one call each a
sliding-window layer; the forward twice where the layer is recomputed in the
backward pass), from the traced slice: the ops line's events by the kernels'
own names, over the slice's steps."""

from benchmark.harness import kernel_costs


def read(trace, spans, run):
    return kernel_costs.ms_per_step(trace, run, "window_flash")
