"""What the two Pallas kernels of a selective state-space (Mamba-1) layer
need for their shares of the roofline: ``ssm_step`` (the served tick, one
call a state layer) and ``selective_scan`` (the prefill, one call a state
layer).  Beside ``harness/serve_kernel_costs.py``, ``hybrid_kernel_costs.py``
and ``window_kernel_costs.py``, which are not edited; a kernel's measured
time a tick is read as there (``seconds_per_tick``), a kernel's time inside
the PREFILL programs as ``window_kernel_costs.kernel_in_prefills`` reads it.

Needed, not executed: the state of the slots that are BUSY, read and
written once a tick (the kernel skips every other slot's; the engine counts
the busy (slot, state layer) pairs: ``serving/tick_state_slots_live``); of a
prefill the REAL tokens (``serving/prefill_scan_tokens``: (real token, scan
layer) pairs — the kernel also walks the padded rest of the chunk that holds
the last real token).  The share is the larger of bytes over the chip's
bandwidth and operations over its PEAK, over the measured time.  The peak is
the matrix unit's; the scan's operations (a multiply, an exponential, two
multiply-adds and a read-out a state element) run on the VECTOR unit, which
has no published peak, so ``selective_scan``'s share reads low by nature:
it says how far the kernel is from what the chip's memory would allow, not
from what its vector unit can do.  A program without the counters or the
kernels, or a configuration without such layers, gives ``None``.
"""

from benchmark.harness import program_trace, window_kernel_costs
from benchmark.harness.serve_kernel_costs import _per_tick, seconds_per_tick

#: operations a state element a token: ``dt * A``, ``exp``, ``* s``, ``(dt
#: c) * B``, ``+``, ``* C``, ``+`` (the read-out's sum), and the two
#: products ``dt * c`` / ``D * c`` shared by a channel's 16 states
OPS_PER_ELEMENT = 9


def _widths(config: dict):
    """``(inner channels E, states a channel N)``; ``None`` for a
    configuration without such layers."""
    if "mamba_expand" not in config or "mamba_d_state" not in config:
        return None
    return (config["mamba_expand"] * config["hidden_size"],
            config["mamba_d_state"])


def pair_bytes(e: int, n: int) -> int:
    """Bytes one busy (slot, layer) pair needs in a tick: the ``(N, E)``
    float32 state read once and written once, the token's ``c`` and ``dt``
    read and ``y`` written (``E`` float32 each), ``B`` and ``C`` read."""
    return 4 * (2 * e * n + 3 * e + 2 * n)


def token_bytes(e: int, n: int) -> int:
    """Bytes one (real token, layer) pair needs in a prefill: ``c`` and
    ``dt`` read, ``y`` written (``E`` float32 each), ``B`` and ``C`` read;
    the state is read and written once a prefill, not a token."""
    return 4 * (3 * e + 2 * n)


def ssm_step(config: dict, run: dict):
    """The state updates of one tick, every state layer."""
    pairs = _per_tick(run, "serving/tick_state_slots_live")
    widths = _widths(config)
    if not pairs or widths is None:
        return None
    e, n = widths
    return {"flops": pairs * OPS_PER_ELEMENT * e * n,
            "bytes": pairs * pair_bytes(e, n)}


def selective_scan(config: dict, run: dict, lengths):
    """The scans of the traced prefills, every state layer.  The traced
    prefills' padded ``lengths`` are the programs' names; of a padded
    prompt's (token, layer) pairs the REAL ones are the window's share
    (``serving/prefill_scan_tokens`` over ``serving/prefill_tokens_padded``:
    the engine counts both a prefill), and each prefill reads and writes
    the state once a layer."""
    m = run.get("engine_metrics", {})
    pairs, real, padded = (m.get("serving/prefill_scan_tokens"),
                           m.get("serving/prefill_tokens_real"),
                           m.get("serving/prefill_tokens_padded"))
    widths = _widths(config)
    if not pairs or not real or not padded or widths is None:
        return None
    e, n = widths
    layers = pairs / real
    pairs = sum(lengths) * pairs / padded
    return {"flops": pairs * OPS_PER_ELEMENT * e * n,
            "bytes": pairs * token_bytes(e, n)
            + len(lengths) * layers * 2 * e * n * 4}


def _share(kernel: str, cost: dict, seconds: float, peaks: dict, per: str):
    by_flops = cost["flops"] / peaks["bf16_flops"]
    by_bytes = cost["bytes"] / peaks["hbm_bytes_per_s"]
    print(f"{kernel}: needs {by_flops * 1e3:.4f} ms by operations (the "
          f"matrix unit's peak; they run on the vector unit), "
          f"{by_bytes * 1e3:.4f} ms by bytes {per}; measured "
          f"{seconds * 1e3:.4f} ms", flush=True)
    return 100.0 * max(by_flops, by_bytes) / seconds


def ssm_step_roofline_share(trace: dict, run: dict):
    """``ssm_step``'s least time a tick on this chip over its measured time
    a tick (%)."""
    seconds = seconds_per_tick(trace, "ssm_step")
    cell = program_trace.cell_of(trace) if seconds else None
    peaks = run.get("peaks", {})
    if cell is None or "bf16_flops" not in peaks:
        return None
    cost = ssm_step(cell["config"], run)
    if cost is None:
        return None
    return _share("ssm_step", cost, seconds, peaks, "a tick")


def selective_scan_roofline_share(trace: dict, run: dict):
    """``selective_scan``'s least time over the traced prefills on this
    chip over its measured time in them (%)."""
    got = window_kernel_costs.kernel_in_prefills(trace, "selective_scan")
    cell = program_trace.cell_of(trace) if got else None
    peaks = run.get("peaks", {})
    if cell is None or "bf16_flops" not in peaks:
        return None
    seconds, lengths = got
    cost = selective_scan(cell["config"], run, lengths)
    if cost is None:
        return None
    return _share("selective_scan", cost, seconds, peaks,
                  f"over {len(lengths)} traced prefills {lengths}")
