"""``mla_decode_attn_roofline_share`` for a model only some of whose layers
are latent attention: the live cache rows' bytes and FLOPs of the layers
that ARE (``linear_attn_config.full_attn_layers``, not ``num_hidden_
layers``: ``harness/hybrid_kernel_costs.py``) over the chip's peaks, over
the ``decode_attn_mla`` kernel's measured time a tick."""

from benchmark.harness import hybrid_kernel_costs


def read(trace, spans, run):
    return hybrid_kernel_costs.roofline_share(trace, run, "decode_attn_mla")
