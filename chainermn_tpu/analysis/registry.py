"""The collective surface, *derived* from source — not hardcoded strings.

Rules need to know "what is a collective" for three vocabularies:

* the in-jit face: public functions of ``chainermn_tpu/ops/collective.py``
  (parsed from its AST, minus the explicitly non-communicating helpers);
* the eager face: ``CommunicatorBase`` collectives — read out of
  ``communicators/base.py``'s ``_ACCOUNTED_OPS`` literal plus the
  ``*_obj`` pickle-lane methods defined on the class;
* the raw ``jax.lax`` primitives those lower to.

Parsing (not importing) keeps the AST engine jax-free and means a new
collective added to ``ops/collective.py`` is linted the day it lands —
the same closure property the observability accounting test enforces
(tests/test_observability_fleet.py's completeness guard).
"""

from __future__ import annotations

import ast
import os
from dataclasses import dataclass, field
from typing import FrozenSet, Optional

#: ops/collective.py defs that are *not* gang-synchronizing: helpers,
#: per-rank queries, and the static cost-model faces.  Everything else
#: public in that module is treated as a collective.  (axis_index/
#: axis_size read topology, they don't sync; the *_cost functions are
#: pure arithmetic the shard-flow analyzer reads.)
_NON_COLLECTIVE_OPS = frozenset({
    "zeros_like_vma", "axis_index", "axis_size",
    "collective_wire_cost", "quantized_ring_cost",
    "quantized_ring_static_groups", "choose_pipeline_depth",
    "block_quantize", "block_dequantize",
})

#: jax.lax collective primitives (the fixed upstream vocabulary the named
#: wrappers lower onto).
JAX_LAX_COLLECTIVES = frozenset({
    "psum", "pmean", "pmax", "pmin", "psum_scatter", "all_gather",
    "all_to_all", "ppermute", "pshuffle", "pswapaxes",
})

#: Expressions whose value differs per rank/process — the taint sources
#: for the collective-deadlock rule.  Attribute tails (``comm.rank``) and
#: call names (``jax.process_index()``) both match by final identifier.
RANK_ATTRS = frozenset({"rank", "intra_rank", "inter_rank"})
RANK_CALLS = frozenset({"axis_index", "process_index"})


def _package_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _parse(path: str) -> Optional[ast.Module]:
    try:
        with open(path) as fh:
            return ast.parse(fh.read(), filename=path)
    except (OSError, SyntaxError):
        return None


def _public_defs(tree: ast.Module) -> FrozenSet[str]:
    return frozenset(
        n.name for n in tree.body
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
        and not n.name.startswith("_"))


def _accounted_ops(tree: ast.Module) -> FrozenSet[str]:
    """Evaluate the ``_ACCOUNTED_OPS = (...)`` literal in base.py."""
    for node in ast.walk(tree):
        if (isinstance(node, ast.Assign) and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)
                and node.targets[0].id == "_ACCOUNTED_OPS"):
            try:
                val = ast.literal_eval(node.value)
            except ValueError:
                continue
            return frozenset(v for v in val if isinstance(v, str))
    return frozenset()


def _obj_lane_methods(tree: ast.Module) -> FrozenSet[str]:
    out = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.ClassDef) and node.name == "CommunicatorBase":
            for item in node.body:
                if (isinstance(item, ast.FunctionDef)
                        and item.name.endswith("_obj")):
                    out.add(item.name)
    return frozenset(out)


@dataclass(frozen=True)
class CollectiveRegistry:
    """Names the whole collective surface for the rules."""

    ops_collectives: FrozenSet[str]       # ops/collective.py public defs
    comm_methods: FrozenSet[str]          # CommunicatorBase collectives
    lax_collectives: FrozenSet[str] = JAX_LAX_COLLECTIVES
    rank_attrs: FrozenSet[str] = RANK_ATTRS
    rank_calls: FrozenSet[str] = RANK_CALLS
    extra: FrozenSet[str] = field(default_factory=frozenset)

    @property
    def all_collective_names(self) -> FrozenSet[str]:
        """Every identifier that, used as the called name (``psum(...)``)
        or attribute tail (``comm.allreduce(...)``), marks a collective."""
        return (self.ops_collectives | self.comm_methods
                | self.lax_collectives | self.extra)

    def is_collective_call(self, node) -> bool:
        """True when an ``ast.Call``'s target names a collective."""
        import ast as _ast
        fn = node.func
        if isinstance(fn, _ast.Name):
            return fn.id in self.all_collective_names
        if isinstance(fn, _ast.Attribute):
            return fn.attr in self.all_collective_names
        return False


def default_registry(package_root: Optional[str] = None) -> CollectiveRegistry:
    """Build the registry from the shipped sources.  Falls back to a
    minimal lax-only registry when the sources are missing (running the
    engine against a foreign tree is still useful)."""
    root = package_root or _package_root()
    ops_names: FrozenSet[str] = frozenset()
    comm_names: FrozenSet[str] = frozenset()

    ops_tree = _parse(os.path.join(root, "ops", "collective.py"))
    if ops_tree is not None:
        ops_names = _public_defs(ops_tree) - _NON_COLLECTIVE_OPS

    base_tree = _parse(os.path.join(root, "communicators", "base.py"))
    if base_tree is not None:
        comm_names = _accounted_ops(base_tree) | _obj_lane_methods(base_tree)

    return CollectiveRegistry(ops_collectives=ops_names,
                              comm_methods=comm_names)
