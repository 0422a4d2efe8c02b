"""Share of its roofline that the fused cross-entropy's three kernels
(``fused_ce_stats``, ``fused_ce_dh``, ``fused_ce_dtable``) reach: the
larger of needed FLOPs over the chip's peak and needed bytes over its
bandwidth (``harness/kernel_costs.py``: recomputation and the table's
padding are not needed work), over the measured time a step."""

from benchmark.harness import kernel_costs


def read(trace, spans, run):
    return kernel_costs.roofline_share(trace, run, "fused_ce")
