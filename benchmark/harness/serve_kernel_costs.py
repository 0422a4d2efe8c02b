"""What each Pallas kernel of the SERVED tick needs, from shapes and the
engine's own counters, for its share of the roofline, and the kernel's
measured time a tick (``layer_metrics/moe_gmm_*``, ``mla_decode_attn_*``).
Beside ``harness/kernel_costs.py`` (the training step's kernels), which is
not edited.

A tick's work is not fixed by shapes alone: the grouped expert product reads
the weights of the experts that HAVE a token, and the latent attention reads
each slot's cache up to its own length.  So the needed operations and bytes
come from the engine's counters (``ServingEngine.metrics()``) averaged over
the window's ticks, and the time from the traced slice: the kernel's events
on the device's ops line that start inside a ``serving_tick`` execution (the
prefill programs run the same grouped product, on other rows).  Needed, not
executed: the zero columns that pad a latent row to whole lane tiles and the
rows that pad an expert's group to a tile are not needed work.  A program
without the counters or the kernels gives ``None``.
"""

import bisect

from benchmark.harness import program_trace
from benchmark.harness.trace_reduce import KERNEL_TAG, read_events

_EVENTS = {}


def seconds_per_tick(trace: dict, kernel: str):
    """Device seconds a ``serving_tick`` execution spends in the Pallas
    kernels whose name holds ``kernel``, over the ticks that start in the
    traced slice; ``None`` without such ticks or kernels."""
    v = program_trace.load(trace)
    found = program_trace.newest_xplane() if v is not None else None
    if found is None:
        return None
    ticks = sorted((s, e) for n, s, e in v["modules"]
                   if n.startswith("serving_tick"))
    if not ticks:
        return None
    path = found[0]
    if path not in _EVENTS:
        devices = read_events(path)["devices"]
        _EVENTS.clear()
        _EVENTS[path] = devices[min(devices)] if devices else []
    starts = [s for s, _ in ticks]
    total, seen = 0, False
    for name, s, e in _EVENTS[path]:
        if not name.endswith(KERNEL_TAG) or kernel not in name:
            continue
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and s < ticks[i][1]:
            total, seen = total + (e - s), True
    return total / 1e9 / len(ticks) if seen else None


def _per_tick(run: dict, key: str):
    m = run.get("engine_metrics", {})
    ticks = m.get("serving/tick_calls")
    return m[key] / ticks if ticks and key in m else None


def moe_gmm(config: dict, run: dict):
    """The grouped expert products of one tick, all expert layers: every
    expert with a token has its three projections read once (bf16), and
    every (token, held expert) assignment costs three matmuls."""
    hit = _per_tick(run, "serving/moe_tick_experts_hit")
    rows = _per_tick(run, "serving/moe_tick_assignments_held")
    if hit is None or rows is None:
        return None
    per_expert = 3 * config["hidden_size"] * config["moe_intermediate_size"]
    return {"flops": rows * 2 * per_expert, "bytes": hit * per_expert * 2}


def decode_attn_mla(config: dict, run: dict):
    """The absorbed latent attention of one tick, every layer: each live
    cache row (``kv_lora_rank + qk_rope_head_dim`` bf16 values) is read
    once and meets every head's query (scores) and every head's weights
    (the sum of ``c_kv``)."""
    rows = _per_tick(run, "serving/tick_cache_rows_live")
    if rows is None:
        return None
    rank, rope = config["kv_lora_rank"], config["qk_rope_head_dim"]
    heads, layers = config["num_attention_heads"], config["num_hidden_layers"]
    return {"flops": layers * rows * 2 * heads * (rank + rope + rank),
            "bytes": layers * rows * (rank + rope) * 2}


NEEDS = {"moe_gmm": moe_gmm, "decode_attn_mla": decode_attn_mla}


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
    print(f"{kernel}: needs {by_flops * 1e3:.4f} ms by FLOPs, "
          f"{by_bytes * 1e3:.4f} ms by bytes a tick; measured "
          f"{seconds * 1e3:.4f} ms", flush=True)
    return 100.0 * max(by_flops, by_bytes) / seconds
