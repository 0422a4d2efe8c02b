"""Share of its roofline that the fused cross-entropy reaches in a train step
of a configuration with a sliced vocabulary (``fused_ce_stats``,
``fused_ce_dh``, ``fused_ce_dtable`` together): the logits once, ``dh`` and
``dtable`` (6 T V D; the kernels compute the logits three times, which is
not needed work) at this configuration's ``hidden_size`` and ``vocab_size``
(``harness/train_moe_window_costs.py::fused_ce``), over the kernels'
measured time a step (``fused_ce_ms_per_step``)."""

from benchmark.harness import train_moe_window_costs


def read(trace, spans, run):
    return train_moe_window_costs.fused_ce_roofline_share(trace, run)
