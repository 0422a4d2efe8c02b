"""What the Pallas kernels of a model that MIXES windowed and full GQA
attention need — a ring of the window's rows a slot in some layers, every
row in the others (``layer_types``: ``sliding_attention`` /
``full_attention``) — for their shares of the roofline: the tick's
flash-decode attention over both kinds of cache (``decode_attn*``) and the
prefill's banded flash forward (``window_flash_fwd``).  Beside
``harness/serve_kernel_costs.py`` and ``hybrid_kernel_costs.py``, which are
not edited; a kernel's measured time a tick is read as there
(``seconds_per_tick``), a kernel's time inside the PREFILL programs here.

Needed, not executed: the rows and the ring rows of the slots that are BUSY
(the engine's ``serving/tick_row_bytes`` and ``serving/tick_ring_bytes``:
host arithmetic on each slot's position; the kernel also reads the free and
cached slots' up to their held positions), read once a tick, over the ticks
of the TRACED SLICE, whose kernel time they are divided by (the counters as
the driver read them at the slice's two ends, ``run["slice_metrics"]``: a
slice with fewer busy slots than the run's mean read 100.8 % against the
whole run's ticks, ledger, PR 43); of a prefill
the (query, key) pairs of REAL positions inside the band
(``serving/prefill_band_pairs``), not the padded rows' and not the masked
halves of the sub-blocks the band's edges cross.  A program without the
counters or the kernels, or a configuration without such layers, gives
``None``.
"""

import bisect

from benchmark.harness import program_trace
from benchmark.harness.serve_kernel_costs import _EVENTS, seconds_per_tick
from benchmark.harness.trace_reduce import KERNEL_TAG, read_events

PREFILL = "serving_prefill_"


def _layers(config: dict):
    """``(heads of the full layers, heads of the sliding layers)``, a
    number a layer; ``None`` for a configuration that mixes no kinds."""
    kinds = config.get("layer_types")
    heads = config.get("num_attention_heads_per_layer")
    if not kinds or not heads or "sliding_window" not in config:
        return None
    of = lambda kind: [h for h, k in zip(heads, kinds) if k == kind]
    return of("full_attention"), of("sliding_attention")


def kernel_in_prefills(trace: dict, kernel: str):
    """``(device seconds in the Pallas kernels whose name holds ``kernel``,
    the padded lengths of the prefill programs)`` over the prefill
    executions that start in the traced slice; ``None`` without such
    executions or kernels."""
    v = program_trace.load(trace)
    found = program_trace.newest_xplane() if v is not None else None
    if found is None:
        return None
    runs = sorted((s, e, n) for n, s, e in v["modules"]
                  if n.startswith(PREFILL))
    if not runs:
        return None
    path = found[0]
    if path not in _EVENTS:       # shared with the tick's readers: the
        #                           trace's chip-0 events, read once a process
        devices = read_events(path)["devices"]
        _EVENTS.clear()
        _EVENTS[path] = devices[min(devices)] if devices else []
    events = _EVENTS[path]
    starts = [s for s, _, _ in runs]
    total, seen = 0, False
    for name, s, e in events:
        if not name.endswith(KERNEL_TAG) or kernel not in name:
            continue
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and s < runs[i][1]:
            total, seen = total + (e - s), True
    if not seen:
        return None
    return total / 1e9, [int(n[len(PREFILL):].split(".")[0].split("_")[0])
                         for _, _, n in runs]


def band_pairs(s: int, window: int) -> int:
    """(query, key) pairs of ``s`` positions under a band of ``window``."""
    head = min(s, window)
    return head * (head + 1) // 2 + (s - head) * window


def _per_slice_tick(run: dict, key: str):
    """An engine counter's growth over the traced slice, a tick of it."""
    m = run.get("slice_metrics") or {}
    ticks = m.get("serving/tick_calls")
    return m[key] / ticks if ticks and key in m else None


def decode_attn_gqa(config: dict, run: dict):
    """The tick's attention over both kinds of cache: the busy slots' rows
    (all full layers) and ring rows (all sliding layers) read once, bf16 K
    and V; every query head of a layer meets each of its live rows twice
    (the score, the weighted sum): ``4 x head_dim`` operations a (head,
    row).  A tick of the traced slice's own."""
    layers = _layers(config)
    rows = _per_slice_tick(run, "serving/tick_row_bytes")
    ring = _per_slice_tick(run, "serving/tick_ring_bytes")
    ring_rows = _per_slice_tick(run, "serving/tick_ring_rows_live")
    per_token = run.get("engine_metrics", {}).get(
        "serving/cache_bytes_per_token")
    if layers is None or rows is None or ring is None or not per_token:
        return None
    full, sliding = layers
    hd = config["head_dim"]
    live_rows = rows / per_token               # of one full layer
    return {"bytes": rows + ring,
            "flops": 4 * hd * (live_rows * sum(full)
                               + ring_rows * sum(sliding) / len(sliding))}


def gqa_decode_roofline_share(trace: dict, run: dict):
    """The ``decode_attn*`` kernels' least time a tick on this chip over
    their measured time a tick (%)."""
    seconds = seconds_per_tick(trace, "decode_attn")
    cell = program_trace.cell_of(trace) if seconds else None
    peaks = run.get("peaks", {})
    if cell is None or "bf16_flops" not in peaks:
        return None
    cost = decode_attn_gqa(cell["config"], run)
    if cost is None:
        return None
    by_flops = cost["flops"] / peaks["bf16_flops"]
    by_bytes = cost["bytes"] / peaks["hbm_bytes_per_s"]
    m = run.get("engine_metrics", {})
    whole = (m.get("serving/tick_row_bytes", 0.0)
             + m.get("serving/tick_ring_bytes", 0.0)) / max(
                 m.get("serving/tick_calls", 0.0), 1.0)
    print(f"decode_attn (rows + rings): needs {by_flops * 1e3:.4f} ms by "
          f"FLOPs, {by_bytes * 1e3:.4f} ms by bytes a tick of the slice "
          f"({cost['bytes']:.0f} B; the whole run's mean tick {whole:.0f} "
          f"B); measured {seconds * 1e3:.4f} ms", flush=True)
    return 100.0 * max(by_flops, by_bytes) / seconds


def window_flash_roofline_share(trace: dict, run: dict):
    """The banded flash forward's needed operations over the chip's bf16
    peak, over its measured time, in the traced prefills (%).  The traced
    prefills' padded lengths are the programs' names; of the pairs in a
    padded prompt's band the REAL ones are the window's share
    (``serving/prefill_band_pairs`` over ``..._padded``: the engine counts
    both a prefill)."""
    got = kernel_in_prefills(trace, "window_flash_fwd")
    cell = program_trace.cell_of(trace) if got else None
    peaks = run.get("peaks", {})
    m = run.get("engine_metrics", {})
    real, padded = (m.get("serving/prefill_band_pairs"),
                    m.get("serving/prefill_band_pairs_padded"))
    layers = _layers(cell["config"]) if cell else None
    if layers is None or "bf16_flops" not in peaks or not real \
            or not padded:
        return None
    seconds, lengths = got
    cfg = cell["config"]
    pairs = sum(band_pairs(s, cfg["sliding_window"]) for s in lengths)
    flops = 4 * cfg["head_dim"] * sum(layers[1]) * pairs * real / padded
    least = flops / peaks["bf16_flops"]
    print(f"window_flash_fwd: {len(lengths)} traced prefills {lengths}, "
          f"real share of the band {real / padded:.4f}; needs "
          f"{least * 1e3:.4f} ms by FLOPs; measured {seconds * 1e3:.4f} ms",
          flush=True)
    return 100.0 * least / seconds
