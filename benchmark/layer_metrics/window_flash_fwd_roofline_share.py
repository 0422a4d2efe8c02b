"""Share of the chip's bf16 peak that the banded flash-attention forward
reaches in the traced prefills: the (query, key) pairs of REAL positions
inside the band (``serving/prefill_band_pairs``: per real query the keys
``0 <= q - k < window``) times ``4 x head_dim`` operations a pair a head,
every windowed layer (``harness/window_kernel_costs.py``), over the
``window_flash_fwd`` kernel's measured time."""

from benchmark.harness import window_kernel_costs


def read(trace, spans, run):
    return window_kernel_costs.window_flash_roofline_share(trace, run)
