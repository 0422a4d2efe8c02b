"""Device time of a served program by scope, for a model with selective
state-space layers: ``harness/scope_trace.py``'s split with the rows of the
``block/mamba/*`` scopes laid BEFORE its ``BUCKETS``.

``scope_trace.BUCKETS`` has no row for ``block/mamba/proj`` (the layer's
first norm, its in / x / dt / out projections with the three inner norms,
the gate, the residual add), ``block/mamba/conv`` (the short convolution and
its window) and ``block/mamba/core`` (the ``ssm_step`` / ``selective_scan``
kernels and what surrounds them: the rates, the layout of their operands);
read by that table they are ``unscoped``.  This reader runs the SAME
functions — the metadata walk, the path rule, the split and its self-time
rule — over the longer table, the way ``train_scope_trace.py`` runs them
over paths with the phase taken off; ``scope_trace.py`` is not edited (its
table is swapped for the call and put back).  ``None`` where the trace
holds no such scope of the program (a program before them, another
architecture).
"""

import contextlib

from benchmark.harness import program_trace, scope_trace, serve_kernel_costs

#: inner scopes first, as ``scope_trace.BUCKETS`` has them
MAMBA_ROWS = (
    ("block/mamba/conv", "ssm_core"),
    ("block/mamba/core", "ssm_core"),
    ("block/mamba/proj", "ssm_proj"),
)
BUCKETS = MAMBA_ROWS + scope_trace.BUCKETS
#: the buckets the ``tick_ms.ssm_*`` metrics read
SSM_BUCKETS = ("ssm_proj", "ssm_core")

_SPLITS = {}


@contextlib.contextmanager
def _table():
    """``scope_trace``'s functions read its module's table: the longer one
    for the length of a call."""
    saved = scope_trace.BUCKETS
    scope_trace.BUCKETS = BUCKETS
    try:
        yield
    finally:
        scope_trace.BUCKETS = saved


def leaf_of(op_name: str):
    """``scope_trace.leaf_of`` by the longer table."""
    with _table():
        return scope_trace.leaf_of(op_name)


def bucket_of(op_name: str):
    return leaf_of(op_name)[0]


def split(path: str, prefix: str, modules=None, ops=None):
    """``scope_trace.split`` by the longer table; ``None`` where no such
    program ran or none of :data:`SSM_BUCKETS`' scopes is in it."""
    with _table():
        out = scope_trace.split(path, prefix, modules, ops)
    if out is None or not any(b in out["buckets"] for b in SSM_BUCKETS):
        return None
    return out


def bucket_ms(trace: dict, run: dict, prefix: str, bucket: str):
    """Mean device milliseconds an execution of program ``prefix*`` spends
    under ``bucket``'s scopes over the traced slice (the split is made once
    a trace and program)."""
    view = program_trace.load(trace)
    found = program_trace.newest_xplane() if view is not None else None
    if found is None:
        return None
    key = (found[0], prefix)
    if key not in _SPLITS:
        _SPLITS.clear()
        _SPLITS[key] = split(found[0], prefix, view["modules"],
                             serve_kernel_costs._EVENTS.get(found[0]))
    out = _SPLITS[key]
    return None if out is None else out["buckets"].get(bucket, 0.0)
