"""Device time a train step spends in the causal flash-attention kernels of
the FULL-attention layers (``flash_fwd`` and ``flash_bwd`` at GQA group 8,
one layer in four; the forward twice where the layer is recomputed), from
the traced slice: the ops line's events whose name STARTS with the kernel's
(``flash_fwd`` is a substring of ``window_flash_fwd``, which
``window_flash_ms_per_step`` reads), over the slice's steps."""

from benchmark.harness import train_moe_window_costs


def read(trace, spans, run):
    return train_moe_window_costs.full_flash_ms_per_step(trace, run)
