"""Share of its roofline that the gated delta-rule state update
(``kda_step``) reaches in the tick: the busy slots' state read and written
once (2 MB a slot a layer each way at the published widths; the engine's
``serving/tick_state_slots_live`` counts the busy (slot, layer) pairs) over
the chip's bandwidth, or its operations over the chip's peak if larger
(``harness/hybrid_kernel_costs.py``), over the kernel's measured time a
tick."""

from benchmark.harness import hybrid_kernel_costs


def read(trace, spans, run):
    return hybrid_kernel_costs.roofline_share(trace, run, "kda_step")
