"""jaxpr engine — trace registered entry points, check what XLA will see.

The AST engine reads source; this engine reads the *program*.  Each
registered entry point (``entrypoints.py``) is traced with tiny shapes on
the CPU backend (``jax.make_jaxpr`` — no device execution for the axis
check) and yields:

* **unbound-axis** (error): a collective inside the traced body names a
  mesh axis absent from the entry point's declared binding.  Two ways to
  trip it: trace-time ``NameError`` ("unbound axis name"), or a collective
  equation whose ``axis_name``/``axes`` parameter escapes the declared
  set (belt and braces — sub-jaxprs are walked recursively through pjit /
  shard_map / scan / cond).
* **recompile-hazard** (warning): the entry point's jitted form compiles
  more than once across its registered call variants (probed with the
  jit cache size), or a declared static argument is unhashable.  Entry
  points that *intend* per-variant programs — the serving engine's
  per-prompt-length prefill family — register ``allow_recompile=True``
  and are reported as allowlisted info instead.

jax is imported lazily inside functions: importing this module costs
nothing and the AST half of the analyzer stays usable on jax-free boxes.
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Set, Tuple

from .findings import Finding

JAXPR_RULES: Dict[str, Tuple[str, str]] = {
    "unbound-axis": (
        "error", "collective names an axis absent from the mesh binding"),
    "recompile-hazard": (
        "warning", "entry point recompiles across registered call variants"),
    "entrypoint-error": (
        "error", "registered entry point failed to build/trace/execute"),
}

#: jax.lax collective primitive names as they appear in jaxprs.
_COLLECTIVE_PRIMS = frozenset({
    "psum", "pmax", "pmin", "ppermute", "all_gather", "all_to_all",
    "reduce_scatter", "pgather", "psum_scatter",
    # their names under vma typing (same wire collectives)
    "psum_invariant", "all_gather_invariant",
})


@dataclass
class EntryPoint:
    """One traceable program the analyzer owns end to end.

    ``build()`` runs lazily (it may import jax and chainermn_tpu) and
    returns a dict with:

    * ``trace``: ``(fn, args)`` — traced via ``jax.make_jaxpr``;
    * ``bound_axes``: set of mesh axis names the binding declares;
    * ``variants`` (optional): ``(jit_fn, [args, ...])`` — every args
      tuple is CALLED on ``jit_fn`` and the jit cache size compared to 1;
    * ``static_values`` (optional): values declared static somewhere in
      the program — probed for hashability.

    Shard-flow keys (read by ``analysis/shardflow.py``; all optional):

    * ``data_axis``: the mesh axis replication is judged against;
    * ``arg_labels``: names for the positional trace args (replication
      findings are grouped per label);
    * ``expected_replication``: ``{label: reason}`` — replication that is
      by design (or a named debt, e.g. optimizer state until ZeRO-1);
      must be DELETED when the sharding lands (stale-annotation check);
    * ``noted``: ``{ledger_row_key: bytes}`` — comm.note() bookings this
      program performs (traffic no wrapper sees), held to account.
    """

    name: str
    build: Callable[[], Dict[str, Any]]
    allow_recompile: bool = False
    description: str = ""
    #: False skips the shard-flow pass (for tee variants whose compiled
    #: program an earlier entry already analyzes byte-for-byte).
    shardflow: bool = True


@dataclass
class TraceReport:
    """What the engine learned about one entry point (returned alongside
    findings so callers can print the collective surface)."""

    name: str
    collectives: List[Tuple[str, Tuple[str, ...]]] = field(
        default_factory=list)  # (primitive, axis names) in trace order
    n_compiles: Optional[int] = None
    error: Optional[str] = None


def _axis_names(params: Dict[str, Any]) -> Tuple[str, ...]:
    for key in ("axes", "axis_name"):
        if key in params:
            v = params[key]
            if isinstance(v, str):
                return (v,)
            if isinstance(v, (tuple, list)):
                return tuple(x for x in v if isinstance(x, str))
    return ()


def _iter_eqns(jaxpr) -> Sequence[Any]:
    """All equations, recursing into every sub-jaxpr found in params."""
    out = []
    seen: Set[int] = set()

    def rec(jx):
        if id(jx) in seen:
            return
        seen.add(id(jx))
        inner = getattr(jx, "jaxpr", jx)  # ClosedJaxpr -> Jaxpr
        for eqn in getattr(inner, "eqns", ()):
            out.append(eqn)
            for v in eqn.params.values():
                for sub in _maybe_jaxprs(v):
                    rec(sub)

    rec(jaxpr)
    return out


def _maybe_jaxprs(v) -> List[Any]:
    subs = []
    if hasattr(v, "eqns") or hasattr(v, "jaxpr"):
        subs.append(v)
    elif isinstance(v, (tuple, list)):
        for item in v:
            subs.extend(_maybe_jaxprs(item))
    return subs


def collective_sequence(jaxpr) -> List[Tuple[str, Tuple[str, ...]]]:
    """(primitive name, axis names) for every collective eqn, in order."""
    seq = []
    for eqn in _iter_eqns(jaxpr):
        name = eqn.primitive.name
        if name in _COLLECTIVE_PRIMS:
            seq.append((name, _axis_names(eqn.params)))
    return seq


def _died_in_vma_cast(fn, args) -> bool:
    """Re-trace with jax's traceback filtering off and say whether the
    AssertionError comes from ``jax._src.core.pvary`` — how 0.9.0
    reports a psum/pmean/ppermute over an axis no mesh binds."""
    import traceback

    import jax

    prev = jax.config.jax_traceback_filtering
    jax.config.update("jax_traceback_filtering", "off")
    try:
        jax.make_jaxpr(fn)(*args)
    except AssertionError as e:
        last = traceback.extract_tb(e.__traceback__)[-1]
        return (last.name == "pvary"
                and os.path.join("jax", "_src") in last.filename)
    except Exception:  # noqa: BLE001 - some other failure: not ours
        return False
    finally:
        jax.config.update("jax_traceback_filtering", prev)
    return False


def check_entrypoint(ep: EntryPoint) -> Tuple[List[Finding], TraceReport]:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax

    findings: List[Finding] = []
    report = TraceReport(name=ep.name)
    loc = f"entrypoint:{ep.name}"

    def engine_error(stage: str, e: BaseException):
        # a broken entry point is a REPORTED finding, never a crash of
        # the whole lint run (the 0/1/2 exit contract must hold)
        report.error = f"{stage} failed: {type(e).__name__}: {e}"
        findings.append(Finding(
            rule="entrypoint-error", severity="error", path=loc, line=0,
            message=report.error, context=ep.name,
            snippet=ep.description))

    try:
        spec = ep.build()
    except Exception as e:  # noqa: BLE001
        engine_error("build", e)
        return findings, report

    fn, args = spec["trace"]
    bound: Set[str] = set(spec.get("bound_axes", ()))

    # ---- axis binding: trace, then walk the collective eqns ----
    def unbound_axis(why) -> Tuple[List[Finding], TraceReport]:
        findings.append(Finding(
            rule="unbound-axis", severity="error", path=loc, line=0,
            message=(f"tracing failed: {why} — the body names a mesh axis "
                     f"the enclosing binding ({sorted(bound)}) does not "
                     "provide; the compiled gang would never agree on "
                     "this collective"),
            context=ep.name, snippet=ep.description))
        report.error = str(why)
        return findings, report

    try:
        jaxpr = jax.make_jaxpr(fn)(*args)
    except NameError as e:
        # jax raises NameError("unbound axis name: ...") at trace time…
        return unbound_axis(e)
    except AssertionError as e:
        # …except for psum/pmean/ppermute, where 0.9.0 dies in the bare
        # assert of its vma cast before it gets to look the axis up
        if _died_in_vma_cast(fn, args):
            return unbound_axis("unbound axis name (jax's vma cast "
                                "asserted on an axis the mesh does not "
                                "bind)")
        engine_error("trace", e)
        return findings, report
    except Exception as e:  # noqa: BLE001
        engine_error("trace", e)
        return findings, report

    report.collectives = collective_sequence(jaxpr)
    for prim, axes in report.collectives:
        stray = [a for a in axes if a not in bound]
        if stray:
            findings.append(Finding(
                rule="unbound-axis", severity="error", path=loc, line=0,
                message=(f"collective `{prim}` runs over axis "
                         f"{stray} but the declared mesh binding is "
                         f"{sorted(bound)}"),
                context=ep.name, snippet=ep.description))

    # ---- recompilation: count actual compiles across variants ----
    variants = spec.get("variants")
    if variants is not None:
        jit_fn, arg_sets = variants
        try:
            for a in arg_sets:
                r = jit_fn(*a)
                jax.tree_util.tree_map(
                    lambda x: getattr(x, "block_until_ready", lambda: x)(),
                    r)
            n = jit_fn._cache_size()
        except Exception as e:  # noqa: BLE001
            engine_error("variant execution", e)
            return findings, report
        report.n_compiles = n
        if n > 1 and not ep.allow_recompile:
            findings.append(Finding(
                rule="recompile-hazard", severity="warning", path=loc,
                line=0,
                message=(f"{n} compiled programs for {len(arg_sets)} call "
                         "variants that should share one — per-call-"
                         "varying shapes or static args; hoist the varying "
                         "piece into traced inputs, or register "
                         "allow_recompile=True with a reason if the "
                         "program family is intentional (per-prompt-"
                         "length prefill)"),
                context=ep.name, snippet=ep.description))

    # ---- static-arg hashability ----
    for v in spec.get("static_values", ()):
        try:
            hash(v)
        except TypeError:
            findings.append(Finding(
                rule="recompile-hazard", severity="warning", path=loc,
                line=0,
                message=(f"declared static value of type "
                         f"{type(v).__name__} is unhashable — jit will "
                         "raise (or, via workarounds like str(), silently "
                         "recompile per call); use a hashable frozen "
                         "config"),
                context=ep.name, snippet=ep.description))

    return findings, report


def check_entrypoints(eps: Optional[Sequence[EntryPoint]] = None
                      ) -> Tuple[List[Finding], List[TraceReport]]:
    if eps is None:
        from .entrypoints import ENTRYPOINTS
        eps = ENTRYPOINTS
    findings: List[Finding] = []
    reports: List[TraceReport] = []
    for ep in eps:
        f, r = check_entrypoint(ep)
        findings.extend(f)
        reports.append(r)
    return findings, reports
