"""Share of its roofline that the flash-attention backward kernel
(``flash_bwd``, one call a layer) reaches: the larger of needed FLOPs over
the chip's peak and needed bytes over its bandwidth
(``harness/kernel_costs.py``: recomputation and the table's padding are not
needed work), over the measured time a step."""

from benchmark.harness import kernel_costs


def read(trace, spans, run):
    return kernel_costs.roofline_share(trace, run, "flash_bwd")
