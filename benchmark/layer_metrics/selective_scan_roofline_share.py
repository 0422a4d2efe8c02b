"""Share of its roofline that the prefill's selective scan
(``selective_scan``) reaches: the traced prefills' REAL (token, layer) pairs
— their padded lengths times the window's real share, from the engine's
``serving/prefill_scan_tokens`` — each reading ``c``, ``dt``, ``B``, ``C``
and writing ``y`` once, over the chip's bandwidth, or nine operations a state
element over the chip's peak if larger (``harness/ssm_kernel_costs.py``),
over the kernel's measured time in those prefills.  The operations run on
the vector unit, whose peak is far under the matrix unit's: the share reads
low by nature (PERF.md, section 3)."""

from benchmark.harness import ssm_kernel_costs


def read(trace, spans, run):
    return ssm_kernel_costs.selective_scan_roofline_share(trace, run)
