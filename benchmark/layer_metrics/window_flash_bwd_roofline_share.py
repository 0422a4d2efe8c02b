"""Share of its roofline that the banded flash-attention backward
(``window_flash_bwd``, one call a sliding-window layer) reaches in a train
step: the (query, key) pairs inside the band times ``8 x head_dim``
operations a pair a query head — twice the forward's; the recomputed
``QK^T`` and the masked halves of the sub-blocks the band's edges cross are
not needed work (``harness/train_moe_window_costs.py``) — over the chip's
peak, or its bytes over the bandwidth if that is more, over the kernel's
measured time a step."""

from benchmark.harness import train_moe_window_costs


def read(trace, spans, run):
    return train_moe_window_costs.window_flash_bwd_roofline_share(trace, run)
