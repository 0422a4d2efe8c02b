"""Device time of a ``train_step`` execution by the scopes INSIDE the phases.

``harness/scope_trace.py`` books the train step by phase (``fwd`` / ``bwd``
/ ``optimizer``: under ``loss_grad`` the phase wins over every other row of
its ``BUCKETS``) and its printed table stops at ``block/attn`` / ``block/mlp``
(``DETAIL``).  A model whose block has more than one kind of work in a half
— rotation and attention beside the projections; routing, dispatch and the
grouped products beside each other — writes its train step under the same
leaf scopes the served programs use (``block/attn/core``, ``block/moe/route``,
``block/moe/dispatch``, ``block/moe/gmm``), so this reader hands
``scope_trace`` the same operations with the phase's scope taken off their
paths: they are then booked by the SAME table's served rows (``attn_core``,
``moe_route``, ``moe_experts``; ``attn_proj`` and ``ffn_dense`` come by
themselves), forward, backward and the backward's recomputed forward
together, chip 0's self time inside the ``train_step`` executions of the
traced slice.  Everything is ``scope_trace``'s — the metadata walk
(``read_metadata``, ``scope_paths``), the table and its path rule
(``bucket_of``), the split and its self-time rule (``split_events``); that
file is not edited.  ``None`` where the trace holds no such scope of the
program (a program before them, another architecture).
"""

from benchmark.harness import program_trace, scope_trace
from benchmark.harness.trace_reduce import SLICE, read_events

PROGRAM = "train_step"
PHASE_SCOPE = "loss_grad"
#: the buckets of ``scope_trace.BUCKETS`` the ``train_ms.*`` metrics read
TRAIN_BUCKETS = ("attn_core", "moe_route", "moe_experts")

_SPLITS = {}


def inside_phase(op_name: str) -> str:
    """``op_name`` without the phase's scope (also where autodiff wrapped
    it: ``transpose(jvp(loss_grad))``), so that the table's other rows see
    the path."""
    return op_name.replace(PHASE_SCOPE, "")


def bucket_of(op_name: str):
    """The bucket an operation of the train step is booked to here."""
    return scope_trace.bucket_of(inside_phase(op_name))


def split(path: str, modules=None):
    """``{bucket: mean ms an execution}`` of the ``train_step`` executions
    that start inside the traced slice of the trace at ``path`` (``modules``:
    those executions where the caller has them); ``None`` where none ran or
    none of :data:`TRAIN_BUCKETS`' scopes is in the trace."""
    meta = scope_trace.read_metadata(path)
    if not meta["ops"]:
        return None
    chip0 = min(meta["ops"])
    events = read_events(path)
    if modules is None:
        slices = [h for h in events["host"] if h[0] == SLICE]
        lo, hi = ((slices[0][1], slices[-1][2]) if slices
                  else (float("-inf"), float("inf")))
        modules = [m for m in program_trace.read_modules(path).get(chip0, ())
                   if lo <= m[1] < hi]
    runs = sorted((s, e) for n, s, e in modules if n.startswith(PROGRAM))
    paths, ambiguous = scope_trace.scope_paths(meta, chip0, PROGRAM)
    paths = {name: inside_phase(p) for name, p in paths.items()}
    if not runs or not any(scope_trace.bucket_of(p) in TRAIN_BUCKETS
                           for p in paths.values()):
        return None
    return scope_trace.split_events(events["devices"].get(chip0, []), runs,
                                    paths, ambiguous)["buckets"]


def bucket_ms(trace: dict, run: dict, bucket: str):
    """Mean device milliseconds a ``train_step`` execution spends under
    ``bucket``'s scopes over the traced slice (the split is made once a
    trace)."""
    view = program_trace.load(trace)
    found = program_trace.newest_xplane() if view is not None else None
    if found is None:
        return None
    if found[0] not in _SPLITS:
        _SPLITS.clear()
        _SPLITS[found[0]] = split(found[0], view["modules"])
    out = _SPLITS[found[0]]
    return None if out is None else out.get(bucket, 0.0)
