"""Share of its roofline that the tick's flash-decode attention reaches in a
model that mixes full and windowed GQA layers (``decode_attn_gqa``, one call
a layer: over every row of the slot in a full layer, over a ring of the
window's rows in a sliding one): the busy slots' rows and ring rows read
once (``serving/tick_row_bytes`` + ``serving/tick_ring_bytes``) over the
chip's bandwidth, or every query head's operations against its live rows
over the chip's peak if larger (``harness/window_kernel_costs.py``), over
the ``decode_attn*`` kernels' measured time a tick."""

from benchmark.harness import window_kernel_costs


def read(trace, spans, run):
    return window_kernel_costs.gqa_decode_roofline_share(trace, run)
