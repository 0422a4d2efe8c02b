"""What each Pallas kernel of the training step NEEDS, from shapes: the
floating-point operations and the HBM bytes of one step on one chip, for
its share of the roofline (``layer_metrics/<kernel>_roofline_share.py``).

Needed, not executed: work that a kernel repeats to save memory (the
flash backward's second ``QK^T``; the fused cross-entropy's two further
passes over the logits) is not counted, nor are the rows by which the
table is padded for alignment, so a share says how far the kernel is from
the least time the chip could take for the mathematics.  The least time
is the larger of operations over the peak FLOP/s and bytes over the peak
bytes/s (``harness/device.py::PEAKS``).
"""

from benchmark.harness import flops as _flops, program_trace
from benchmark.harness.trace_reduce import KERNEL_TAG


def _lm_shapes(config, traffic):
    d, heads = config["n_embd"], config["n_head"]
    return {"batch": traffic["batch_per_chip"], "seq": traffic["seq_len"],
            "layers": config["n_layer"], "d": d, "heads": heads,
            "head_dim": d // heads, "vocab": config["vocab_size"]}


def flash_fwd(config, traffic) -> dict:
    """Causal attention forward, every layer: ``QK^T`` and ``PV`` over the
    lower triangle; reads Q, K, V, writes O (bf16) and one float32
    log-sum-exp a row."""
    z = _lm_shapes(config, traffic)
    rows = z["batch"] * z["heads"]
    pair = _flops.matmul(z["seq"], z["head_dim"], z["seq"]) / 2   # causal
    tensor = z["batch"] * z["seq"] * z["d"] * 2                    # bf16
    return {"flops": z["layers"] * rows * 2 * pair,
            "bytes": z["layers"] * (4 * tensor + rows * z["seq"] * 4)}


def flash_bwd(config, traffic) -> dict:
    """Causal attention backward, every layer: ``dV = P^T dO``,
    ``dP = dO V^T``, ``dQ = dS K``, ``dK = dS^T Q`` (the recomputed
    ``QK^T`` is not needed work); reads Q, K, V, O, dO and two row
    statistics, writes dQ, dK, dV: twice the forward on both counts."""
    return {k: 2 * v for k, v in flash_fwd(config, traffic).items()}


def fused_ce(config, traffic) -> dict:
    """Fused cross-entropy, forward and backward: the logits ``h W^T``
    once, ``dh = dlogits W`` and ``dW = dlogits^T h`` (6 T V D in all;
    the kernels compute the logits three times, 10 T V D); reads h and the
    table, writes dh and dtable."""
    z = _lm_shapes(config, traffic)
    tokens = z["batch"] * z["seq"]
    h, table = tokens * z["d"] * 2, z["vocab"] * z["d"] * 2
    return {"flops": 3 * _flops.matmul(tokens, z["d"], z["vocab"]),
            "bytes": 2 * h + 2 * table}


#: kernel name (``pl.pallas_call(name=...)``, or the stem its kernels
#: share) -> what a step needs of it
NEEDS = {"flash_fwd": flash_fwd, "flash_bwd": flash_bwd,
         "fused_ce": fused_ce}


def least_seconds(cost: dict, peaks: dict) -> float:
    """The roofline's least time for ``cost`` on a chip with ``peaks``."""
    return max(cost["flops"] / peaks["bf16_flops"],
               cost["bytes"] / peaks["hbm_bytes_per_s"])


def ms_per_step(trace: dict, run: dict, kernel: str):
    """Device milliseconds a train step spends in the Pallas kernels whose
    own name holds ``kernel`` (``flash_fwd.7``, ``fused_ce_dh.1``: the
    numeric suffix is the compiler's and moves with any refactor; the
    name is the program's ``pl.pallas_call(name=...)``).  ``None`` where
    the slice holds no such kernel (a program before the names)."""
    steps = run.get("steps_in_slice")
    seconds = [t for n, t in trace["op_seconds"].items()
               if n.endswith(KERNEL_TAG) and kernel in n]
    if not steps or not seconds:
        return None
    return sum(seconds) / steps * 1e3


def roofline_share(trace: dict, run: dict, kernel: str):
    """The kernel's least time on this chip over its measured time (%).
    Shapes come from the cell the trace was taken in."""
    ms = ms_per_step(trace, run, kernel)
    cell = program_trace.cell_of(trace) if ms else None
    if cell is None or "bf16_flops" not in run.get("peaks", {}):
        return None
    cost = NEEDS[kernel](cell["config"], cell["traffic"])
    return 100.0 * least_seconds(cost, run["peaks"]) * 1e3 / ms
