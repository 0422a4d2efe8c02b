"""Share of its roofline that the selective state-space layers' one-token
state update (``ssm_step``) reaches in the tick: the busy slots' state read
and written once (320 KB a slot a layer each way at the published widths;
the engine's ``serving/tick_state_slots_live`` counts the busy (slot, layer)
pairs) with the token's vectors over the chip's bandwidth, or its operations
over the chip's peak if larger (``harness/ssm_kernel_costs.py``), over the
kernel's measured time a tick."""

from benchmark.harness import ssm_kernel_costs


def read(trace, spans, run):
    return ssm_kernel_costs.ssm_step_roofline_share(trace, run)
