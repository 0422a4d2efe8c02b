"""Share of its roofline that the absorbed latent-attention kernel
(``decode_attn_mla``, one call a layer) reaches in the tick: the larger of
the live cache rows' bytes (1152 B a row a layer) over the chip's bandwidth
and their FLOPs (2 x 128 heads x 1088 a row) over its peak
(``harness/serve_kernel_costs.py``, rows from the engine's counter), over
the kernel's measured time a tick."""

from benchmark.harness import serve_kernel_costs


def read(trace, spans, run):
    return serve_kernel_costs.roofline_share(trace, run, "decode_attn_mla")
