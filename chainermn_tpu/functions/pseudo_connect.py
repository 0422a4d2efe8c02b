"""Ordering edges for communication graphs.

Reference parity: ``chainermn/functions/pseudo_connect.py ::
PseudoConnect`` [uv] (SURVEY.md §2.2) — grafts a fake dependency edge so
backprop visits remote-communication nodes in the right order (without it,
multi-hop model-parallel graphs deadlock: rank A waits to send a gradient
rank B never asks for).

TPU-native there is no deadlock to prevent — the whole graph is one XLA
program and the scheduler orders collectives — but explicit ordering edges
are still occasionally needed to stop XLA *reordering* communication past
compute (e.g. to enforce a pipeline schedule's phase structure).
``optimization_barrier`` provides exactly that contract.
"""

from __future__ import annotations

from .._compat import optimization_barrier


def pseudo_connect(delegate_variable, *actual_variables):
    """Tie ``actual_variables`` to ``delegate_variable`` with a scheduling
    edge.  Returns the actual variables unchanged in value (single variable
    → returned bare; several → tuple), but the compiler must materialize
    ``delegate_variable`` first — the reference's backward-ordering
    guarantee, expressed to XLA instead of to a define-by-run tape.
    """
    if not actual_variables:
        raise ValueError("pseudo_connect needs at least one actual variable")
    tied = optimization_barrier((delegate_variable, actual_variables))
    out = tied[1]
    return out[0] if len(out) == 1 else out
