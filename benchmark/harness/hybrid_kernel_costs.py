"""What the Pallas kernels of a HYBRID model's served tick need — a model
whose layers keep either a recurrent state a sequence (a gated delta-rule
layer, ``kda_step``) or a latent row a token (``decode_attn_mla``) — for
their shares of the roofline.  Beside ``harness/serve_kernel_costs.py``
(which counts every layer as latent attention and is not edited); the
kernel's measured time a tick is read as there (``seconds_per_tick``).

Needed, not executed: the state of the slots that are BUSY, read and
written once a tick (the kernel skips every other slot's, and the engine
counts the busy (slot, state layer) pairs: ``serving/tick_state_slots_
live``), not the pool's; the latent rows of the layers that ARE latent
attention (``linear_attn_config.full_attn_layers``).  A program without the
counters or the kernels, or a configuration without such layers, gives
``None``.
"""

from benchmark.harness import program_trace
from benchmark.harness.serve_kernel_costs import _per_tick, seconds_per_tick


def kda_step(config: dict, run: dict):
    """The state updates of one tick, every state layer: each busy (slot,
    layer) pair has its ``(heads, d, d)`` float32 state read once and
    written once and its five vectors read (``q, k, g, v, beta``: float32),
    and costs per head a decay, ``S'^T k``, a rank-1 update and a read-out:
    seven operations an element of the state."""
    pairs = _per_tick(run, "serving/tick_state_slots_live")
    lin = config.get("linear_attn_config")
    if not pairs or not lin:
        return None
    heads, d = lin["num_heads"], lin["head_dim"]
    return {"flops": pairs * heads * 7 * d * d,
            "bytes": pairs * heads * (2 * d * d + 6 * d) * 4}


def decode_attn_mla(config: dict, run: dict):
    """``serve_kernel_costs.decode_attn_mla`` with the layers that ARE
    latent attention counted from the configuration: each live cache row
    (``kv_lora_rank + qk_rope_head_dim`` bf16 values) is read once a latent
    layer and meets every head's query and every head's weights."""
    rows = _per_tick(run, "serving/tick_cache_rows_live")
    lin = config.get("linear_attn_config")
    if rows is None or not lin:
        return None
    rank, rope = config["kv_lora_rank"], config["qk_rope_head_dim"]
    heads, layers = config["num_attention_heads"], len(
        lin["full_attn_layers"])
    return {"flops": layers * rows * 2 * heads * (rank + rope + rank),
            "bytes": layers * rows * (rank + rope) * 2}


NEEDS = {"kda_step": kda_step, "decode_attn_mla": decode_attn_mla}


def roofline_share(trace: dict, run: dict, kernel: str):
    """The kernel's least time a tick on this chip over its measured time a
    tick (%), and which bound is the larger, printed as a free line."""
    seconds = seconds_per_tick(trace, kernel)
    cell = program_trace.cell_of(trace) if seconds else None
    peaks = run.get("peaks", {})
    if cell is None or "bf16_flops" not in peaks:
        return None
    cost = NEEDS[kernel](cell["config"], run)
    if cost is None:
        return None
    by_flops = cost["flops"] / peaks["bf16_flops"]
    by_bytes = cost["bytes"] / peaks["hbm_bytes_per_s"]
    print(f"{kernel} (hybrid): needs {by_flops * 1e3:.4f} ms by FLOPs, "
          f"{by_bytes * 1e3:.4f} ms by bytes a tick; measured "
          f"{seconds * 1e3:.4f} ms", flush=True)
    return 100.0 * max(by_flops, by_bytes) / seconds
