"""What the Pallas kernels of a TRAINED model with windowed GQA attention
and routed experts need, for their shares of the roofline: the banded flash
attention backward (``window_flash_bwd``), the grouped expert product's
three kernels' faces in a train step (``moe_gmm``: the forward and, on the
transposed weights, ``dX``; ``moe_gmm_dw``: the weight gradient) and the
fused cross-entropy at this configuration's keys.  Beside
``harness/kernel_costs.py``, which is not edited: a kernel's measured time a
step is read as there (``ms_per_step``: the ops line's events by the
kernel's own name), and the least time is its ``least_seconds``.

Needed, not executed: the (query, key) pairs INSIDE the band (not the masked
halves of the sub-blocks its edges cross, not the recomputed ``QK^T``); the
expert products over the assignments that fell on HELD experts in the steps
of the TRACED SLICE — the routing counts the program's step hands out as its
aux, which the family hands to :func:`book_slice` for the steps it
dispatched while the slice was traced, the same steps whose kernels the
trace times (the router learns between the check and the slice, so the
check's counts, the program's ``train/moe_*`` counters, would not do) — not
the tile padding, not the worst-case rows, and the forward ONCE though
per-layer recomputation runs it twice; weights read once a pass.  A program
without the kernels, a run without a traced slice, or a configuration
without such layers, gives ``None``.
"""

from benchmark.harness import kernel_costs, program_trace
from benchmark.harness.trace_reduce import KERNEL_TAG
from benchmark.harness.window_kernel_costs import band_pairs


def counters() -> dict:
    """The program's process-wide counters (none of these in a program
    before them)."""
    from chainermn_tpu.observability.trace import get_tracer

    return get_tracer().counters()


#: the routing of the steps dispatched inside the traced slice, summed:
#: steps, (token, expert) assignments in all and on held experts
_slice = {"steps": 0, "total": 0.0, "held": 0.0}


def book_slice(counts, steps: int = 1) -> None:
    """Add the routing-count vector of ``steps`` train steps of the traced
    slice (``moe.COUNT_FIELDS`` first: assignments in all, on held experts),
    already on the host."""
    _slice["steps"] += steps
    _slice["total"] += float(counts[0])
    _slice["held"] += float(counts[1])


def reset_slice() -> None:
    _slice.update(steps=0, total=0.0, held=0.0)


def held_share():
    """Held over total (token, expert) assignments (%) of the program's
    ``train/moe_*`` counters; ``None`` without them."""
    c = counters()
    held = c.get("train/moe_assignments_held")
    total = c.get("train/moe_assignments_total")
    if held is None or not total:
        return None
    return 100.0 * held / total


def slice_held_share():
    """The same share of the traced slice's steps; ``None`` without one."""
    if not _slice["total"]:
        return None
    return 100.0 * _slice["held"] / _slice["total"]


def held_assignments_per_step():
    """(token, expert) assignments on held experts a step of the traced
    slice, summed over the expert layers; ``None`` without a slice."""
    if not _slice["steps"]:
        return None
    return _slice["held"] / _slice["steps"]


def _shapes(config: dict, traffic: dict):
    """The sizes of a configuration that mixes windowed and full GQA layers
    and routes experts; ``None`` for any other."""
    keys = ("layer_types", "sliding_window", "num_attention_heads",
            "num_key_value_heads", "head_dim", "moe_intermediate_size",
            "num_experts_held", "hidden_size", "vocab_size")
    if any(k not in config for k in keys) or "seq_len" not in traffic:
        return None
    return {"batch": traffic["batch_per_chip"], "seq": traffic["seq_len"],
            "sliding": sum(t == "sliding_attention"
                           for t in config["layer_types"]),
            "layers": len(config["layer_types"]),
            "window": config["sliding_window"],
            "heads": config["num_attention_heads"],
            "kv_heads": config["num_key_value_heads"],
            "hd": config["head_dim"], "d": config["hidden_size"],
            "inner": config["moe_intermediate_size"],
            "held": config["num_experts_held"],
            "vocab": config["vocab_size"]}


def window_flash_fwd(config, traffic):
    """The banded forward, every sliding layer: ``QK^T`` and ``PV`` over
    the band's pairs (4 x head_dim operations a pair a query head); reads
    Q, K, V (GQA: K and V at their own head count), writes O and one
    float32 log-sum-exp a row."""
    z = _shapes(config, traffic)
    if z is None or not z["sliding"]:
        return None
    pairs = z["batch"] * band_pairs(z["seq"], z["window"])
    rows = z["batch"] * z["seq"]
    q = rows * z["heads"] * z["hd"] * 2                       # bf16
    kv = rows * z["kv_heads"] * z["hd"] * 2
    return {"flops": z["sliding"] * 4 * z["hd"] * z["heads"] * pairs,
            "bytes": z["sliding"] * (2 * q + 2 * kv
                                     + rows * z["heads"] * 4)}


def window_flash_bwd(config, traffic):
    """The banded backward, every sliding layer: ``dV = P^T dO``, ``dP = dO
    V^T``, ``dQ = dS K``, ``dK = dS^T Q`` over the band's pairs (the
    recomputed ``QK^T`` is not needed work): twice the forward on both
    counts."""
    fwd = window_flash_fwd(config, traffic)
    return fwd and {k: 2 * v for k, v in fwd.items()}


def _gmm_pass(z, assignments: float) -> dict:
    """One pass (forward, or ``dX``, or ``dW``) of the three grouped
    products of every expert layer over ``assignments`` rows in all: 2 x D
    x F operations a row a product; the held experts' weights once, the
    rows in and out once."""
    product = z["d"] * z["inner"]
    weights = z["layers"] * z["held"] * 3 * product * 2       # bf16
    rows = assignments * (3 * z["d"] + 3 * z["inner"]) * 2
    return {"flops": 3 * 2 * product * assignments,
            "bytes": weights + rows}


def moe_gmm_train(config, traffic, assignments: float):
    """Forward + ``dX`` + ``dW`` of the three products."""
    z = _shapes(config, traffic)
    if z is None or not assignments:
        return None
    one = _gmm_pass(z, assignments)
    return {k: 3 * v for k, v in one.items()}


def moe_gmm_dw(config, traffic, assignments: float):
    """The weight gradient alone: ``dW[e] = X_e^T dY_e``."""
    z = _shapes(config, traffic)
    if z is None or not assignments:
        return None
    return _gmm_pass(z, assignments)


def fused_ce(config, traffic):
    """Fused cross-entropy over the sliced vocabulary, forward and backward
    (``kernel_costs.fused_ce`` at this configuration's keys): the logits
    ``h W^T`` once, ``dh = dlogits W`` and ``dW = dlogits^T h`` (6 T V D in
    all; the kernels compute the logits three times); reads h and the
    table, writes dh and dtable."""
    z = _shapes(config, traffic)
    if z is None:
        return None
    tokens = z["batch"] * z["seq"]
    h, table = tokens * z["d"] * 2, z["vocab"] * z["d"] * 2    # bf16
    return {"flops": 3 * 2.0 * tokens * z["d"] * z["vocab"],
            "bytes": 2 * h + 2 * table}


def full_flash_ms_per_step(trace, run):
    """Device milliseconds a step spends in the FULL layers' causal flash
    kernels: names that START with ``flash_fwd`` / ``flash_bwd``
    (``kernel_costs.ms_per_step`` matches a substring, and ``flash_fwd`` is
    one of ``window_flash_fwd``).  ``None`` for a configuration that mixes
    no windowed and full layers, or without such a kernel in the slice."""
    steps = run.get("steps_in_slice")
    seconds = [t for n, t in trace["op_seconds"].items()
               if n.endswith(KERNEL_TAG)
               and n.startswith(("flash_fwd", "flash_bwd"))]
    cell = program_trace.cell_of(trace) if steps and seconds else None
    if cell is None or _shapes(cell["config"], cell["traffic"]) is None:
        return None
    return sum(seconds) / steps * 1e3


def _share(trace, run, kernel: str, cost_of):
    """The least time ``cost_of(config, traffic)`` needs on this chip over
    the measured time a step of the kernels whose name holds ``kernel``
    (%)."""
    ms = kernel_costs.ms_per_step(trace, run, kernel)
    cell = program_trace.cell_of(trace) if ms else None
    if cell is None or "bf16_flops" not in run.get("peaks", {}):
        return None
    cost = cost_of(cell["config"], cell["traffic"])
    if cost is None:
        return None
    least = kernel_costs.least_seconds(cost, run["peaks"])
    print(f"{kernel}: needs {cost['flops'] / 1e12:.4f} TFLOP, "
          f"{cost['bytes'] / 1e9:.4f} GB a step: {least * 1e3:.4f} ms at "
          f"the least; measured {ms:.4f} ms", flush=True)
    return 100.0 * least * 1e3 / ms


def window_flash_bwd_roofline_share(trace, run):
    return _share(trace, run, "window_flash_bwd", window_flash_bwd)


def fused_ce_roofline_share(trace, run):
    return _share(trace, run, "fused_ce", fused_ce)


def moe_gmm_roofline_share(trace, run, kernel: str):
    """``kernel``: ``moe_gmm`` (all three faces) or ``moe_gmm_dw``."""
    assignments = held_assignments_per_step()
    if assignments is None:
        return None
    need = moe_gmm_dw if kernel == "moe_gmm_dw" else moe_gmm_train
    return _share(trace, run, kernel,
                  lambda config, traffic: need(config, traffic, assignments))
