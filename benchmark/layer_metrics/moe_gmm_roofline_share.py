"""Share of its roofline that the grouped expert product (``moe_gmm``)
reaches in the tick: the larger of the FLOPs of the tokens routed to held
experts over the chip's peak and the weight bytes of the experts HIT over
its bandwidth (``harness/serve_kernel_costs.py``, from the engine's
counters), over the kernel's measured time a tick."""

from benchmark.harness import serve_kernel_costs


def read(trace, spans, run):
    return serve_kernel_costs.roofline_share(trace, run, "moe_gmm")
