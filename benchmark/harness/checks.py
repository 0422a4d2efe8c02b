"""The rows of the comparison that decides ``correct``, as every driver
prints them: each number compared, beside its limit."""

import math


def row(name: str, value: float, limit) -> dict:
    return {"name": name, "value": value, "limit": limit,
            "ok": math.isfinite(value) and (limit is None or value <= limit)}


def training(ref, want: dict, got: dict) -> list:
    """A family's first training steps against its reference ``ref``
    (``LIMITS``, ``worst_leaf_gap``): each step's loss, the first gradient's
    norm and the parameters' change, both by the worst leaf."""
    lim = ref.LIMITS
    return [
        row("loss_gap", max(abs(a - b) for a, b in zip(
            got["losses"], want["losses"])), lim["loss_gap"]),
        row("grad_norm_gap", ref.worst_leaf_gap(
            got["grad_norms"], want["grad_norms"]), lim["grad_norm_gap"]),
        row("update_norm_gap", ref.worst_leaf_gap(
            got["update_norms"], want["update_norms"]),
            lim["update_norm_gap"]),
    ]
