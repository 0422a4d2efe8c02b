"""Shard-flow analyzer: static sharding, memory, and collective-cost
model, reconciled against the runtime comm ledger.

The jaxpr engine (``jaxpr_engine.py``) checks *which* collectives a
registered entry point runs and over which axes; this module answers the
three questions the ROADMAP's next tentpoles (ZeRO-1 weight-update
sharding, the ``reshard`` primitive) stand or fall on:

* **Replication report** — for every entry-point argument leaf and every
  sizeable intermediate, is it REPLICATED across the entry's declared
  data axis?  Full replication of optimizer state is exactly the failure
  mode ZeRO-1 (ROADMAP item 2, arxiv 2004.13336) must eliminate, so the
  report names it today and the ZeRO PR lands with a red→green diff:
  entry points declare *expected* replication (label → reason), an
  undeclared replicated arg is an ``unexpected-replication`` finding, and
  a declaration whose arg is no longer replicated is a
  ``stale-replication-annotation`` finding (the annotation must be
  deleted when the sharding lands — same discipline as stale baseline
  entries).

* **Static collective cost model** — per collective equation: the
  LEDGER-convention payload bytes (``observability.comm.payload_info``:
  shape × itemsize of the input payload, axis-size independent) plus the
  physical ring decomposition (``ops.collective.collective_wire_cost``:
  per-rank wire bytes and message counts from the axis size), with scan
  trip counts reported as multipliers.  The quantized int8 ring is
  modeled analytically by ``ops.collective.quantized_ring_cost``; a
  declaring entry point swaps its composite ledger row for the
  per-primitive groups of ``quantized_ring_static_groups`` via the
  ``composite`` build-spec key (see the reconciliation section below).

* **Peak live memory per replica** — classical liveness over the jaxpr:
  a value is live from its defining equation to its last use; the peak
  of the live-set byte total (recursing into sub-jaxprs, where shard_map
  body avals are already per-replica block shapes) estimates the
  activation watermark a replica must hold.  This is the number the
  ZeRO-1 acceptance gate ("peak memory/replica at n=1..8") reads.

Static↔dynamic reconciliation — the anti-rot mechanism
------------------------------------------------------
A cost model that nothing checks decays silently.  Here, every analysis
run ALSO executes the entry point once under the PR 1 accounting layer
(a fresh build, so the compile lands inside a ``CommAccountant.step``
bracket) and asserts, per ``primitive@axis`` group::

    static_eqn_bytes == wrapped_ledger_bytes + declared noted bytes

* ``wrapped`` rows are bookings by the accounted collective face — each
  one has exactly its forward equation in the traced program, so the two
  sides must agree byte-exactly; a gap is a ``comm-ledger-gap`` ERROR
  (either the model rotted or a collective bypasses the accounted face).
* ``noted`` rows (``observability.comm.note`` — traffic no wrapper sees,
  e.g. the autodiff-inserted gradient psum of the default train step)
  must equal the entry's declaration; under vma typing the matching
  psum IS an equation of the traced program, so the declared bytes are
  added to the static side's expectation.

The only tolerance is dtype-dependent padding: sub-byte or odd-itemsize
wire dtypes may pad up to one element per call (``pad_tolerance``); for
the shipped dtypes the comparison is exact.

Findings flow through the same fingerprint/baseline/suppression
machinery as the AST engine; the checked-in baseline is
``.shardflow-baseline.json`` and ``scripts/shardflow_report.py`` is the
CI runner (exit 0/1/2 — the ``lint_spmd.py`` contract).

jax is imported lazily: importing this module costs nothing on jax-free
boxes (same contract as ``jaxpr_engine``).
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Set, Tuple

from .findings import Finding

SHARDFLOW_SCHEMA = "chainermn_tpu.shardflow.v1"
SHARDFLOW_BASELINE_FILENAME = ".shardflow-baseline.json"

SHARDFLOW_RULES: Dict[str, Tuple[str, str]] = {
    "unexpected-replication": (
        "warning", "argument replicated across the data axis without a "
                   "declared expectation"),
    "stale-replication-annotation": (
        "warning", "declared expected replication no longer observed — "
                   "delete the annotation (the sharding landed)"),
    "comm-ledger-gap": (
        "error", "static collective bytes and the runtime comm ledger "
                 "disagree (cost-model rot, or a collective bypassing "
                 "the accounted face)"),
    "shardflow-error": (
        "error", "entry point failed to build/trace/execute under the "
                 "shard-flow analyzer"),
}

#: jaxpr primitive aliases → canonical name.  Under vma typing a psum
#: traces as ``psum_invariant`` and the varying → invariant all_gather as
#: ``all_gather_invariant``: same wire collectives, other names.
_PRIM_ALIAS = {"reduce_scatter": "psum_scatter",
               "psum_invariant": "psum",
               "all_gather_invariant": "all_gather"}

#: Collectives whose result is replication-INVARIANT over their axes
#: (the axes leave the varying set)…
_REDUCING_PRIMS = frozenset({"psum", "pmax", "pmin", "all_gather"})
#: …and collectives whose result stays (or becomes) rank-varying.
_VARYING_PRIMS = frozenset({"psum_scatter", "ppermute", "all_to_all",
                            "pshuffle", "pgather"})
_COLLECTIVE_PRIMS = _REDUCING_PRIMS | _VARYING_PRIMS

#: How many intermediates the replication report keeps (largest first).
_TOP_INTERMEDIATES = 5


# --------------------------------------------------------------------------
# small jaxpr helpers (shared shapes with jaxpr_engine, kept dependency-free)
# --------------------------------------------------------------------------

def _inner(jx):
    return getattr(jx, "jaxpr", jx)  # ClosedJaxpr -> Jaxpr


def _subjaxprs(v) -> List[Any]:
    subs = []
    if hasattr(v, "eqns") or hasattr(v, "jaxpr"):
        subs.append(v)
    elif isinstance(v, (tuple, list)):
        for item in v:
            subs.extend(_subjaxprs(item))
    return subs


def _eqn_subjaxprs(eqn) -> List[Any]:
    out = []
    for v in eqn.params.values():
        out.extend(_subjaxprs(v))
    return out


def _canon(prim_name: str) -> str:
    return _PRIM_ALIAS.get(prim_name, prim_name)


def _axes_of(params: Dict[str, Any]) -> Tuple[str, ...]:
    for key in ("axes", "axis_name"):
        if key in params:
            v = params[key]
            if isinstance(v, str):
                return (v,)
            if isinstance(v, (tuple, list)):
                return tuple(x for x in v if isinstance(x, str))
    return ()


def _aval_nbytes(aval) -> int:
    """Byte size of one aval, computed THROUGH the ledger's own
    convention function (``observability.comm.payload_info`` — avals
    carry shape/dtype, which is all it reads): the static model and the
    accountant can never disagree on the formula, only on what they
    count."""
    if aval is None or getattr(aval, "shape", None) is None \
            or getattr(aval, "dtype", None) is None:
        return 0  # tokens / abstract values carry no payload
    from chainermn_tpu.observability.comm import payload_info

    return payload_info(aval)[0]


def _var_nbytes(v) -> int:
    return _aval_nbytes(getattr(v, "aval", None))


def _is_var(v) -> bool:
    from jax.extend.core import Var

    return isinstance(v, Var)


# --------------------------------------------------------------------------
# static collective cost model
# --------------------------------------------------------------------------

@dataclass
class CollectiveCost:
    """One collective equation of the traced program."""

    primitive: str                 # canonical jaxpr primitive name
    axes: Tuple[str, ...]
    payload_bytes: int             # ledger convention (input payload)
    wire_bytes: int                # physical ring bytes per rank
    messages: int                  # per-rank wire messages
    dtype: str
    shape: Tuple[int, ...]
    trip_count: int = 1            # scan multiplier (1 = straight-line)

    @property
    def group(self) -> str:
        return f"{self.primitive}@{'+'.join(self.axes)}"

    def to_dict(self) -> Dict[str, Any]:
        return {
            "primitive": self.primitive, "axes": list(self.axes),
            "payload_bytes": self.payload_bytes,
            "wire_bytes": self.wire_bytes, "messages": self.messages,
            "dtype": self.dtype, "shape": list(self.shape),
            "trip_count": self.trip_count,
        }


def static_costs(jaxpr, default_axis_sizes: Optional[Dict[str, int]] = None
                 ) -> List[CollectiveCost]:
    """Every collective equation of ``jaxpr`` (recursively), costed.

    Axis sizes come from the enclosing ``shard_map`` equation's mesh
    (``default_axis_sizes`` seeds the walk for bodies traced bare).
    ``trip_count`` carries scan ``length`` multipliers: the LEDGER books
    once per trace, so reconciliation compares at ``trip_count``-blind
    granularity, while the report's physical totals honor it.
    """
    from chainermn_tpu.ops.collective import collective_wire_cost

    out: List[CollectiveCost] = []

    def walk(jx, sizes: Dict[str, int], mult: int):
        for eqn in _inner(jx).eqns:
            name = _canon(eqn.primitive.name)
            if name == "shard_map":
                sub_sizes = dict(sizes)
                mesh = eqn.params.get("mesh")
                shape = getattr(mesh, "shape", None)
                if shape:
                    sub_sizes.update({str(k): int(v)
                                      for k, v in dict(shape).items()})
                walk(eqn.params["jaxpr"], sub_sizes, mult)
                continue
            if name in _COLLECTIVE_PRIMS:
                axes = _axes_of(eqn.params)
                payload = sum(_var_nbytes(v) for v in eqn.invars)
                p = 1
                for a in axes:
                    p *= int(sizes.get(a, 1))
                cost = collective_wire_cost(name, payload, p)
                aval = getattr(eqn.invars[0], "aval", None)
                out.append(CollectiveCost(
                    primitive=name, axes=axes, payload_bytes=payload,
                    wire_bytes=cost["wire_bytes"],
                    messages=cost["messages"],
                    dtype=str(getattr(aval, "dtype", "?")),
                    shape=tuple(getattr(aval, "shape", ())),
                    trip_count=mult))
            sub_mult = mult
            if eqn.primitive.name == "scan":
                sub_mult = mult * int(eqn.params.get("length", 1) or 1)
            for sub in _eqn_subjaxprs(eqn):
                walk(sub, sizes, sub_mult)

    walk(jaxpr, dict(default_axis_sizes or {}), 1)
    return out


def group_bytes(costs: Sequence[CollectiveCost],
                trip_adjusted: bool = False) -> Dict[str, int]:
    """``primitive@axis`` → summed payload bytes (ledger convention)."""
    out: Dict[str, int] = {}
    for c in costs:
        k = c.group
        out[k] = out.get(k, 0) + c.payload_bytes * (
            c.trip_count if trip_adjusted else 1)
    return out


# --------------------------------------------------------------------------
# peak live memory (liveness over the jaxpr)
# --------------------------------------------------------------------------

def peak_live_bytes(jx) -> int:
    """Peak byte total of simultaneously-live values in ``jx``.

    Linear-scan liveness: a var is live from its defining equation until
    its last use (outputs to the end).  A call equation contributes its
    sub-jaxpr's own peak minus the I/O already counted at this level.
    Inside ``shard_map`` bodies the avals are per-replica block shapes,
    so recursing through the shard_map equation yields the PER-REPLICA
    estimate the report publishes.  An estimate, not a simulation: XLA
    fusion/rematerialization can only lower it, donation lowers the
    input share — treat it as the no-fusion upper bound.
    """
    inner = _inner(jx)
    eqns = list(inner.eqns)
    last: Dict[Any, int] = {}
    for i, e in enumerate(eqns):
        for v in e.invars:
            if _is_var(v):
                last[v] = i
    for v in inner.outvars:
        if _is_var(v):
            last[v] = len(eqns)

    alive: Set[Any] = set()
    live = 0
    for v in list(inner.invars) + list(inner.constvars):
        if v in last and v not in alive:
            alive.add(v)
            live += _var_nbytes(v)
    peak = live
    for i, e in enumerate(eqns):
        subs = _eqn_subjaxprs(e)
        extra = 0
        if subs:
            io = (sum(_var_nbytes(v) for v in e.invars if _is_var(v))
                  + sum(_var_nbytes(v) for v in e.outvars))
            extra = max(0, max(peak_live_bytes(s) for s in subs) - io)
        for v in e.outvars:
            if v in last and v not in alive:
                alive.add(v)
                live += _var_nbytes(v)
        peak = max(peak, live + extra)
        for v in list(e.invars) + list(e.outvars):
            if _is_var(v) and v in alive and last.get(v, -1) <= i:
                alive.discard(v)
                live -= _var_nbytes(v)
    return peak


# --------------------------------------------------------------------------
# replication analysis (varying-axes propagation)
# --------------------------------------------------------------------------

def _propagate_vary(jx, in_vary: List[Set[str]],
                    record: Optional[List[Tuple[Any, Set[str]]]] = None
                    ) -> List[Set[str]]:
    """Propagate varying-axes sets through a (Closed)Jaxpr body.

    ``in_vary[i]`` is the set of mesh axes over which invar ``i`` is
    rank-varying (empty = replicated).  Returns the outvars' sets.
    Collective rules: reducing collectives (psum/pmax/pmin/all_gather)
    subtract their axes, redistributing ones (psum_scatter/ppermute/
    all_to_all) add them, ``axis_index`` introduces its axis; every
    other primitive unions its inputs.  Sub-jaxprs recurse; scan/while
    bodies run twice with the carry-out unioned in (a cheap fixed point
    in the ast-engine loop-twice spirit).  ``record`` (top level only)
    collects ``(eqn, out_vary)`` for the intermediates report.
    """
    inner = _inner(jx)
    vary: Dict[Any, Set[str]] = {}
    for v, s in zip(inner.invars, in_vary):
        vary[v] = set(s)
    for v in inner.constvars:
        vary[v] = set()

    def get(v) -> Set[str]:
        if not _is_var(v):
            return set()
        return vary.get(v, set())

    def run_sub(sub, eqn_invars, twice: bool = False) -> List[Set[str]]:
        sub_in = [get(v) for v in eqn_invars]
        si = _inner(sub)
        n = len(si.invars)
        sub_in = (sub_in + [set()] * n)[:n]
        out = _propagate_vary(sub, sub_in)
        if twice:
            # feed outputs back through positionally-matching inputs
            # (scan carries line up after num_consts; a union over ALL
            # positions is a safe over-approximation)
            fed = [set(s) for s in sub_in]
            for o in out:
                for f in fed:
                    f |= o
            out2 = _propagate_vary(sub, fed)
            out = [a | b for a, b in zip(out, out2)]
        return out

    for eqn in inner.eqns:
        name = _canon(eqn.primitive.name)
        base: Set[str] = set()
        for v in eqn.invars:
            base |= get(v)
        if name in _REDUCING_PRIMS:
            res = base - set(_axes_of(eqn.params))
            outs = [set(res) for _ in eqn.outvars]
        elif name in _VARYING_PRIMS:
            res = base | set(_axes_of(eqn.params))
            outs = [set(res) for _ in eqn.outvars]
        elif name == "axis_index":
            outs = [set(_axes_of(eqn.params)) for _ in eqn.outvars]
        elif name in ("pvary", "pcast", "pbroadcast"):
            res = base | set(_axes_of(eqn.params))
            outs = [set(res) for _ in eqn.outvars]
        elif name == "cond":
            branches = _subjaxprs(eqn.params.get("branches", ()))
            merged: Optional[List[Set[str]]] = None
            for br in branches:
                o = run_sub(br, eqn.invars[1:])
                merged = o if merged is None else [
                    a | b for a, b in zip(merged, o)]
            outs = merged or [set(base) for _ in eqn.outvars]
        elif name == "while":
            # invars = cond_consts + body_consts + carry, but the BODY
            # jaxpr's invars are body_consts + carry — a positional zip
            # over eqn.invars would feed the carry slots the cond
            # consts' (usually empty) sets and lose the carry's axes
            cn = int(eqn.params.get("cond_nconsts", 0))
            bn = int(eqn.params.get("body_nconsts", 0))
            body = eqn.params.get("body_jaxpr")
            body_in = [get(v) for v in eqn.invars[cn:]]
            if body is not None:
                out1 = _propagate_vary(body, body_in)
                # carry fixed point: body outvars ARE the carry, fed back
                fed = [set(s) for s in body_in]
                for i, o in enumerate(out1):
                    if bn + i < len(fed):
                        fed[bn + i] |= o
                out2 = _propagate_vary(body, fed)
                outs = [a | b for a, b in zip(out1, out2)]
            else:  # pragma: no cover - malformed eqn
                outs = [set(base) for _ in eqn.outvars]
        else:
            subs = _eqn_subjaxprs(eqn)
            if subs:
                # scan invars (consts + carry + xs) align positionally
                # with its jaxpr's invars; run twice with outputs
                # union-fed back for the carry fixed point
                twice = eqn.primitive.name == "scan"
                merged = None
                for sub in subs:
                    o = run_sub(sub, eqn.invars, twice=twice)
                    merged = o if merged is None else [
                        a | b for a, b in zip(merged, o)]
                outs = ([set(s) for s in merged]
                        + [set(base)] * len(eqn.outvars))[:len(eqn.outvars)]
            else:
                outs = [set(base) for _ in eqn.outvars]
        for v, s in zip(eqn.outvars, outs):
            if _is_var(v):
                vary[v] = s
        if record is not None:
            record.append((eqn, set().union(*outs) if outs else set()))
    return [get(v) for v in inner.outvars]


def _find_shard_maps(jaxpr) -> List[Tuple[Any, List[Optional[int]]]]:
    """All shard_map equations, each with a map from its invar positions
    to the OUTER jaxpr's flattened-argument leaf index (None where the
    value was produced by intermediate computation rather than passed
    straight through pjit/call boundaries)."""
    found: List[Tuple[Any, List[Optional[int]]]] = []

    def walk(jx, var_to_leaf: Dict[Any, int]):
        inner = _inner(jx)
        for eqn in inner.eqns:
            if eqn.primitive.name == "shard_map":
                found.append(
                    (eqn, [var_to_leaf.get(v) for v in eqn.invars]))
                continue
            subs = _eqn_subjaxprs(eqn)
            for sub in subs:
                si = _inner(sub)
                sub_map = {}
                for sv, ov in zip(si.invars, eqn.invars):
                    if _is_var(ov) and ov in var_to_leaf:
                        sub_map[sv] = var_to_leaf[ov]
                walk(sub, sub_map)

    outer = _inner(jaxpr)
    walk(jaxpr, {v: i for i, v in enumerate(outer.invars)})
    return found


def _leaf_labels(args: Sequence[Any],
                 arg_labels: Optional[Sequence[str]]) -> List[str]:
    """One label per flattened arg leaf: ``<arg_label><pytree path>``."""
    import jax

    leaves, _ = jax.tree_util.tree_flatten_with_path(tuple(args))
    labels = []
    for path, _leaf in leaves:
        idx = getattr(path[0], "idx", None)
        if arg_labels and idx is not None and idx < len(arg_labels):
            root = arg_labels[idx]
        else:
            root = f"arg{idx if idx is not None else '?'}"
        labels.append(root + jax.tree_util.keystr(path[1:]))
    return labels


def replication_report(jaxpr, args: Sequence[Any], data_axis: str,
                       arg_labels: Optional[Sequence[str]] = None
                       ) -> Dict[str, Any]:
    """Which argument leaves / intermediates are replicated across
    ``data_axis``?

    Arg replication is read off the shard_map bindings' ``in_specs``
    (a leaf whose binding never splits a dimension over ``data_axis`` is
    fully materialized on every replica of that axis); intermediates come
    from varying-axes propagation through each shard_map body.  Returns::

        {"args": {root_label: {"replicated_bytes", "total_bytes",
                               "fully_replicated", "leaves": [...]}},
         "intermediates": [top-N largest replicated],
         "replicated_arg_bytes": total}
    """
    labels = _leaf_labels(args, arg_labels)
    leaf_info: Dict[int, Dict[str, Any]] = {}
    intermediates: List[Dict[str, Any]] = []

    for eqn, leaf_map in _find_shard_maps(jaxpr):
        in_specs = eqn.params.get("in_specs") or ()
        body = eqn.params.get("jaxpr")
        in_vary: List[Set[str]] = []
        for pos, spec in enumerate(in_specs):
            axes: Set[str] = set()
            for dim_axes in spec:        # PartitionSpec: one entry per dim
                if dim_axes is None:
                    continue
                axes.update(dim_axes if isinstance(dim_axes, (tuple, list))
                            else (dim_axes,))
            in_vary.append(axes)
            leaf = leaf_map[pos] if pos < len(leaf_map) else None
            if leaf is None:
                continue
            nbytes = _var_nbytes(eqn.invars[pos])
            info = leaf_info.setdefault(
                leaf, {"replicated": False, "nbytes": nbytes})
            if data_axis not in axes:
                info["replicated"] = True
        if body is not None:
            recs: List[Tuple[Any, Set[str]]] = []
            _propagate_vary(body, in_vary, record=recs)
            for sub_eqn, vset in recs:
                if data_axis in vset:
                    continue
                nbytes = sum(_var_nbytes(v) for v in sub_eqn.outvars)
                if nbytes <= 0:
                    continue
                aval = getattr(sub_eqn.outvars[0], "aval", None)
                intermediates.append({
                    "primitive": sub_eqn.primitive.name,
                    "shape": list(getattr(aval, "shape", ())),
                    "dtype": str(getattr(aval, "dtype", "?")),
                    "nbytes": nbytes,
                })

    groups: Dict[str, Dict[str, Any]] = {}
    for leaf, info in leaf_info.items():
        label = labels[leaf] if leaf < len(labels) else f"leaf{leaf}"
        root = label.split("[", 1)[0].split("/", 1)[0]
        g = groups.setdefault(root, {
            "replicated_bytes": 0, "total_bytes": 0,
            "fully_replicated": True, "leaves": []})
        g["total_bytes"] += info["nbytes"]
        if info["replicated"]:
            g["replicated_bytes"] += info["nbytes"]
            g["leaves"].append({"label": label, "nbytes": info["nbytes"]})
        else:
            g["fully_replicated"] = False
    for g in groups.values():
        g["fully_replicated"] = (g["fully_replicated"]
                                 and g["total_bytes"] > 0)

    intermediates.sort(key=lambda d: -d["nbytes"])
    return {
        "data_axis": data_axis,
        "args": groups,
        "intermediates": intermediates[:_TOP_INTERMEDIATES],
        "replicated_arg_bytes": sum(
            g["replicated_bytes"] for g in groups.values()),
    }


# --------------------------------------------------------------------------
# the per-entry-point analysis + reconciliation
# --------------------------------------------------------------------------

@dataclass
class ShardflowReport:
    """Everything the analyzer learned about one entry point."""

    name: str
    data_axis: Optional[str] = None
    costs: List[CollectiveCost] = field(default_factory=list)
    static_groups: Dict[str, int] = field(default_factory=dict)
    ledger_wrapped: Dict[str, int] = field(default_factory=dict)
    ledger_noted: Dict[str, int] = field(default_factory=dict)
    expected_static: Dict[str, int] = field(default_factory=dict)
    replication: Dict[str, Any] = field(default_factory=dict)
    peak_live_bytes: Optional[int] = None
    reconciled: Optional[bool] = None
    error: Optional[str] = None

    def to_dict(self) -> Dict[str, Any]:
        return {
            "name": self.name,
            "data_axis": self.data_axis,
            "costs": [c.to_dict() for c in self.costs],
            "static_groups": dict(self.static_groups),
            "ledger_wrapped": dict(self.ledger_wrapped),
            "ledger_noted": dict(self.ledger_noted),
            "expected_static": dict(self.expected_static),
            "replication": self.replication,
            "peak_live_bytes": self.peak_live_bytes,
            "reconciled": self.reconciled,
            "error": self.error,
        }


def _ledger_groups(rows: Dict[str, Dict[str, Any]]
                   ) -> Tuple[Dict[str, int], Dict[str, int]]:
    """Split ledger rows into (wrapped per primitive-group, noted per raw
    row key), mapping wrapper op names onto canonical primitives via
    ``ops.collective.LEDGER_TO_PRIMITIVE``.  Rows aggregate per
    ``op@axis`` and may mix wrapped calls with ``comm.note`` bookings —
    the accountant keeps the noted share in ``noted_bytes``, so the
    split is exact even on a shared key."""
    from chainermn_tpu.ops.collective import LEDGER_TO_PRIMITIVE

    wrapped: Dict[str, int] = {}
    noted: Dict[str, int] = {}
    for key, row in rows.items():
        op, _, axis = key.partition("@")
        noted_part = int(row.get("noted_bytes", 0))
        wrapped_part = int(row["bytes"]) - noted_part
        if noted_part:
            noted[key] = noted.get(key, 0) + noted_part
        if wrapped_part:
            prim = LEDGER_TO_PRIMITIVE.get(op, _canon(op))
            if prim is None:
                # composite op (quantized ring): its equations are the
                # wire-dtype ppermute/psum schedule — reconciled via
                # quantized_ring_cost by a declaring entry point; an
                # UNDECLARED composite row surfaces as a group mismatch.
                prim = op
            group = f"{prim}@{axis}"
            wrapped[group] = wrapped.get(group, 0) + wrapped_part
    return wrapped, noted


def _run_under_ledger(fn, args, name: str) -> Dict[str, Dict[str, Any]]:
    """Execute ``fn(*args)`` freshly-compiled under the accounting layer,
    returning the per-op rows booked by exactly this run.  Prior
    process-global observability state is restored afterwards (the lint
    tier shares its pytest process with the whole suite)."""
    import jax

    from chainermn_tpu import observability as obs
    from chainermn_tpu.observability.comm import get_accountant

    was_enabled = obs.enabled()
    obs.enable()
    acct = get_accountant()
    try:
        with acct.step(("shardflow", name)):
            out = fn(*args)
            jax.tree_util.tree_map(
                lambda x: getattr(x, "block_until_ready", lambda: x)(), out)
        report = acct.last_step_report or {}
        return dict(report.get("per_op", {}))
    finally:
        if not was_enabled:
            obs.disable()


def analyze_entrypoint(ep, reconcile: bool = True,
                       pad_tolerance: int = 0
                       ) -> Tuple[List[Finding], ShardflowReport]:
    """Full shard-flow analysis of one registered entry point."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax

    report = ShardflowReport(name=ep.name)
    findings: List[Finding] = []
    loc = f"entrypoint:{ep.name}"

    def fail(stage: str, e: BaseException):
        report.error = f"{stage} failed: {type(e).__name__}: {e}"
        findings.append(Finding(
            rule="shardflow-error", severity="error", path=loc, line=0,
            message=report.error, context=ep.name, snippet=ep.description))

    try:
        spec = ep.build()
    except Exception as e:  # noqa: BLE001
        fail("build", e)
        return findings, report

    fn, args = spec["trace"]
    data_axis = spec.get("data_axis")
    report.data_axis = data_axis
    expected_repl: Dict[str, str] = dict(spec.get("expected_replication", {}))

    # ---- dynamic side FIRST: a fresh build's compile must land inside
    # the accounting bracket (in-jit bookings happen at trace time) ----
    rows: Dict[str, Dict[str, Any]] = {}
    if reconcile:
        try:
            rows = _run_under_ledger(fn, args, ep.name)
        except Exception as e:  # noqa: BLE001
            fail("ledger run", e)
            return findings, report

    # ---- static side ----
    try:
        jaxpr = jax.make_jaxpr(fn)(*args)
    except Exception as e:  # noqa: BLE001
        fail("trace", e)
        return findings, report

    report.costs = static_costs(jaxpr)
    report.static_groups = group_bytes(report.costs)
    try:
        report.peak_live_bytes = peak_live_bytes(jaxpr)
    except Exception as e:  # noqa: BLE001  pragma: no cover
        report.error = f"liveness failed: {type(e).__name__}: {e}"

    # ---- replication report + findings ----
    if data_axis:
        try:
            report.replication = replication_report(
                jaxpr, args, data_axis, spec.get("arg_labels"))
        except Exception as e:  # noqa: BLE001
            fail("replication analysis", e)
            return findings, report
        groups = report.replication.get("args", {})
        for root, g in sorted(groups.items()):
            if g["replicated_bytes"] <= 0:
                continue
            if root in expected_repl:
                g["expected"] = expected_repl[root]
                continue
            full = "fully" if g["fully_replicated"] else "partially"
            findings.append(Finding(
                rule="unexpected-replication", severity="warning",
                path=loc, line=0, context=root,
                message=(
                    f"argument `{root}` is {full} replicated across data "
                    f"axis '{data_axis}' ({g['replicated_bytes']} of "
                    f"{g['total_bytes']} bytes on EVERY replica) — shard "
                    "it, or declare expected_replication with the reason "
                    "(entrypoints.py)"),
                snippet=f"replicated:{root}"))
        for root, reason in sorted(expected_repl.items()):
            g = groups.get(root)
            if g is None or g["replicated_bytes"] <= 0:
                findings.append(Finding(
                    rule="stale-replication-annotation", severity="warning",
                    path=loc, line=0, context=root,
                    message=(
                        f"expected_replication[{root!r}] ({reason!r}) no "
                        "longer matches a replicated argument — the "
                        "sharding landed; delete the annotation so the "
                        "report shows the red→green diff"),
                    snippet=f"expected:{root}"))

    # ---- static↔dynamic reconciliation ----
    if reconcile:
        wrapped, noted = _ledger_groups(rows)
        report.ledger_wrapped = wrapped
        report.ledger_noted = noted

        declared_noted: Dict[str, int] = dict(spec.get("noted", {}))

        expected: Dict[str, int] = dict(wrapped)

        # COMPOSITE rows (LEDGER_TO_PRIMITIVE → None, e.g. the quantized
        # int8 ring): the entry declares, per ledger row, (a) the bytes
        # the accountant must have booked for it (the compressed-wire
        # ledger convention) and (b) the per-primitive-group payload
        # bytes its hand-written schedule puts in the traced program
        # (``ops.collective.quantized_ring_static_groups``).  The row is
        # swapped for its equation groups before the comparison, so the
        # schedule is held byte-exact like any wrapped collective.
        composite_ok = True
        for key, decl in sorted(dict(spec.get("composite", {})).items()):
            booked = expected.pop(key, 0)
            want_row = int(decl.get("ledger_bytes", 0))
            if booked != want_row:
                composite_ok = False
                findings.append(Finding(
                    rule="comm-ledger-gap", severity="error", path=loc,
                    line=0, context=ep.name,
                    message=(
                        f"composite ledger row `{key}` books {booked} "
                        f"bytes but the entry point declares {want_row} "
                        "— the compressed-wire convention and the "
                        "declaration drifted apart"),
                    snippet=f"composite:{key}"))
            for g, b in dict(decl.get("static_groups", {})).items():
                expected[g] = expected.get(g, 0) + int(b)
        # the noted (AD-inserted) collectives ARE equations of the
        # traced program under vma typing
        from chainermn_tpu.ops.collective import LEDGER_TO_PRIMITIVE
        for key, b in declared_noted.items():
            op, _, axis = key.partition("@")
            prim = LEDGER_TO_PRIMITIVE.get(op, _canon(op)) or op
            g = f"{prim}@{axis}"
            expected[g] = expected.get(g, 0) + int(b)
        report.expected_static = expected

        ok = composite_ok
        for g in sorted(set(expected) | set(report.static_groups)):
            want = expected.get(g, 0)
            got = report.static_groups.get(g, 0)
            if abs(want - got) > pad_tolerance:
                ok = False
                findings.append(Finding(
                    rule="comm-ledger-gap", severity="error", path=loc,
                    line=0, context=ep.name,
                    message=(
                        f"collective group `{g}`: traced program carries "
                        f"{got} payload bytes but the runtime ledger "
                        f"accounts for {want} (wrapped "
                        f"{wrapped.get(g, 0)}) — the static cost model "
                        "rotted, or a "
                        "collective on this path bypasses the accounted "
                        "face (ops.collective)"),
                    snippet=f"group:{g}"))
        for key, brow in sorted(noted.items()):
            want = declared_noted.get(key)
            if want is None:
                ok = False
                findings.append(Finding(
                    rule="comm-ledger-gap", severity="error", path=loc,
                    line=0, context=ep.name,
                    message=(
                        f"noted ledger row `{key}` ({brow} bytes) has no "
                        "declaration on this entry point — declare it in "
                        "the build spec's `noted` dict (with the bytes) "
                        "so the reconciliation can hold it to account"),
                    snippet=f"noted:{key}"))
            elif abs(int(want) - brow) > pad_tolerance:
                ok = False
                findings.append(Finding(
                    rule="comm-ledger-gap", severity="error", path=loc,
                    line=0, context=ep.name,
                    message=(
                        f"noted ledger row `{key}` books {brow} bytes but "
                        f"the entry point declares {want} — the note in "
                        "the builder and the declaration drifted apart"),
                    snippet=f"noted:{key}"))
        for key, want in sorted(declared_noted.items()):
            if key not in noted:
                ok = False
                findings.append(Finding(
                    rule="comm-ledger-gap", severity="error", path=loc,
                    line=0, context=ep.name,
                    message=(
                        f"declared noted collective `{key}` ({want} "
                        "bytes) was never booked by the run — the "
                        "builder's comm.note disappeared; update the "
                        "declaration"),
                    snippet=f"noted:{key}"))
        report.reconciled = ok

    return findings, report


def analyze_entrypoints(eps: Optional[Sequence[Any]] = None,
                        reconcile: bool = True
                        ) -> Tuple[List[Finding], List[ShardflowReport]]:
    """Shard-flow analysis over registered entry points (default: all).

    Entry points registered with ``shardflow=False`` are skipped — the
    observability-tee variants re-run the very same compiled programs
    their base entries already analyze."""
    if eps is None:
        from .entrypoints import ENTRYPOINTS
        eps = ENTRYPOINTS
    findings: List[Finding] = []
    reports: List[ShardflowReport] = []
    for ep in eps:
        if not getattr(ep, "shardflow", True):
            continue
        f, r = analyze_entrypoint(ep, reconcile=reconcile)
        findings.extend(f)
        reports.append(r)
    return findings, reports


# --------------------------------------------------------------------------
# runner (scripts/shardflow_report.py / python -m chainermn_tpu.analysis.shardflow)
# --------------------------------------------------------------------------

def find_shardflow_baseline(start: Optional[str] = None) -> Optional[str]:
    """Nearest ``.shardflow-baseline.json`` at or above ``start``
    (default: the package checkout root) — the one upward walk of
    ``findings.find_baseline``, parameterized by filename."""
    from .findings import find_baseline

    d = start or os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    return find_baseline(d, filename=SHARDFLOW_BASELINE_FILENAME)


def _select_entrypoints(names: Optional[Sequence[str]]):
    from .entrypoints import select_entrypoints

    return select_entrypoints(names, for_shardflow=True)


def _render_report(r: ShardflowReport) -> str:
    lines = [f"== {r.name} (data axis: {r.data_axis or '-'}) =="]
    if r.error:
        lines.append(f"  ERROR: {r.error}")
    if r.reconciled is not None:
        lines.append("  static<->ledger: "
                     + ("RECONCILED" if r.reconciled else "MISMATCH"))
    for g in sorted(set(r.static_groups) | set(r.expected_static)):
        lines.append(
            f"    {g:28s} static {r.static_groups.get(g, 0):>10d} B   "
            f"ledger-expected {r.expected_static.get(g, 0):>10d} B")
    for k, b in sorted(r.ledger_noted.items()):
        lines.append(f"    {k:28s} noted  {b:>10d} B (declared)")
    phys = sum(c.wire_bytes * c.trip_count for c in r.costs)
    msgs = sum(c.messages * c.trip_count for c in r.costs)
    lines.append(f"  physical wire estimate: {phys} B, {msgs} messages "
                 f"(ring decomposition at the traced axis sizes)")
    if r.peak_live_bytes is not None:
        lines.append(f"  peak live memory / replica: {r.peak_live_bytes} B "
                     "(liveness upper bound, pre-fusion)")
    repl = r.replication or {}
    for root, g in sorted(repl.get("args", {}).items()):
        mark = ("expected: " + g["expected"] if "expected" in g
                else ("REPLICATED" if g["replicated_bytes"] else "sharded"))
        lines.append(
            f"    arg {root:12s} {g['replicated_bytes']:>8d}/"
            f"{g['total_bytes']:<8d} B replicated  [{mark}]")
    for it in repl.get("intermediates", []):
        lines.append(
            f"    intermediate {it['primitive']:16s} "
            f"{tuple(it['shape'])!s:14s} {it['dtype']:9s} "
            f"{it['nbytes']} B replicated")
    return "\n".join(lines)


def main(argv: Optional[List[str]] = None) -> int:
    """Shard-flow report runner.  Exit contract (the ``lint_spmd.py``
    contract): 0 = clean modulo baseline, 1 = findings, 2 = unusable
    inputs."""
    import argparse
    import json
    import sys

    from .baseline import BaselineGate

    p = argparse.ArgumentParser(
        prog="python scripts/shardflow_report.py",
        description="Shard-flow analyzer: static sharding/memory/"
                    "collective-cost model reconciled against the "
                    "runtime comm ledger (docs/ANALYSIS.md)")
    p.add_argument("--entry", action="append", default=None,
                   help="restrict to one registered entry point (repeat "
                        "for several; default: all)")
    p.add_argument("--list-entrypoints", action="store_true",
                   help="print the registered entry points and exit")
    p.add_argument("--json", action="store_true",
                   help="one machine-readable JSON document on stdout")
    p.add_argument("--baseline", default=None,
                   help=f"baseline file (default: nearest "
                        f"{SHARDFLOW_BASELINE_FILENAME} above the repo)")
    p.add_argument("--no-baseline", action="store_true",
                   help="ignore any baseline: report everything")
    p.add_argument("--fix-baseline", action="store_true",
                   help="regenerate the baseline from current findings "
                        "(keeps existing comments; entries for entry "
                        "points not selected via --entry are carried "
                        "over untouched)")
    args = p.parse_args(argv)

    if args.list_entrypoints:
        from .entrypoints import ENTRYPOINTS
        for ep in ENTRYPOINTS:
            tag = "" if getattr(ep, "shardflow", True) else "  [shardflow: skipped]"
            print(f"{ep.name:36s} {ep.description}{tag}")
        return 0

    eps, err = _select_entrypoints(args.entry)
    if err:
        print(f"error: {err}", file=sys.stderr)
        return 2

    findings, reports = analyze_entrypoints(eps)

    gate = BaselineGate(args.baseline or find_shardflow_baseline(),
                        enabled=not args.no_baseline)
    err = gate.load()
    if err:
        print(f"error: {err}", file=sys.stderr)
        return 2

    if args.fix_baseline:
        analyzed = {f"entrypoint:{r.name}" for r in reports}
        gate.fix(findings,
                 in_scope=lambda e: e["path"] in analyzed,
                 default_target=SHARDFLOW_BASELINE_FILENAME)
        return 0

    findings, accepted = gate.filter(findings)

    if args.json:
        print(json.dumps({
            "schema": SHARDFLOW_SCHEMA,
            "baseline": gate.path if gate.baseline is not None else None,
            "n_accepted_by_baseline": len(accepted),
            "findings": [f.to_dict() for f in findings],
            "reports": [r.to_dict() for r in reports],
        }, indent=2))
    else:
        for r in reports:
            print(_render_report(r))
        for f in findings:
            print(f.render())
        sev: Dict[str, int] = {}
        for f in findings:
            sev[f.severity] = sev.get(f.severity, 0) + 1
        tally = ", ".join(f"{n} {s}" for s, n in sorted(sev.items())) \
            or "no findings"
        extra = (f" ({len(accepted)} accepted by baseline)"
                 if accepted else "")
        print(f"shardflow: {tally}{extra} over {len(reports)} "
              f"entry point(s)", file=sys.stderr)
    return 1 if findings else 0


if __name__ == "__main__":  # pragma: no cover - python -m face
    import sys

    sys.exit(main())
