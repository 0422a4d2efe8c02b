"""Portable array redistribution: ``reshard(tree, src_spec, dst_spec)``.

The elastic/disaggregation primitive ROADMAP items 4 and 5 both need —
"Memory-efficient array redistribution through portable collective
communication" (arxiv 2112.01075, PAPERS.md) distilled to the 1-D mesh
this repo's data/TP axes use: a redistribution between two partition
specs lowers to the MINIMAL collective for the (src, dst) pair instead
of the naive all_gather-then-slice (which moves P× the necessary bytes
and materializes the full array on every rank):

    ==================  =====================  =======================
    src → dst           collective             per-rank wire bytes
    ==================  =====================  =======================
    R → R               (none)                 0
    R → S(a)            local slice            0
    S(a) → S(a)         (none)                 0
    S(a) → R            all_gather             block × (P-1)
    S(a) → S(b), a≠b    all_to_all             block × (P-1)/P
    ==================  =====================  =======================

where ``R`` is replicated, ``S(a)`` is sharded along logical axis ``a``
across the mesh axis, and "block" is the per-rank shard.  Every wire leg
routes through the ACCOUNTED collective face (``ops.collective``), so
the PR 1 comm ledger books each call and the PR 6 shard-flow static
model reconciles the traced equations byte-exactly — the cost of a
reshard is never invisible (``reshard_cost`` is the formula the
property tests hold the ledger to).

Two faces, one spec language:

* :func:`reshard` — the in-SPMD primitive: call inside ``shard_map``
  with the axis bound, on per-rank blocks.  :func:`make_reshard` wraps
  it into a jitted whole-array program (the train→serve weight-handoff
  / KV-slab-transfer building block).
* :func:`reshard_host` — the device-free twin for checkpoint shards:
  re-partitions a list of per-process host pytrees from one world
  size/layout to another (the elastic-restore path of
  ``extensions/checkpoint.py``; no jax required at call time).

Spec language (`ShardSpec`): ``None`` = replicated; an ``int`` = that
logical axis is evenly partitioned across the mesh axis.  A spec may be
a single value (applied to every leaf) or a pytree matching ``tree``.
"""

from __future__ import annotations

from typing import Any, Callable, List, Optional, Sequence, Union

ShardSpec = Union[None, int]

__all__ = [
    "ShardSpec", "reshard", "make_reshard", "reshard_host", "reshard_cost",
    "partition_spec_of", "validate_spec", "lower_schedule",
]


def validate_spec(spec: ShardSpec, ndim: Optional[int] = None,
                  what: str = "spec") -> ShardSpec:
    """Normalize/validate one leaf spec: None, or an in-range axis int."""
    if spec is None:
        return None
    if isinstance(spec, bool) or not isinstance(spec, int):
        raise TypeError(
            f"{what} must be None (replicated) or an int logical axis, "
            f"got {spec!r}")
    if ndim is not None and not -ndim <= spec < ndim:
        raise ValueError(
            f"{what}={spec} out of range for a rank-{ndim} array")
    if ndim is not None and spec < 0:
        spec += ndim
    return spec


def _spec_tree(tree, spec):
    """Broadcast a single spec over a pytree, or validate a spec pytree."""
    import jax

    leaves, treedef = jax.tree_util.tree_flatten(tree)
    if spec is None or isinstance(spec, int):
        return [spec] * len(leaves), leaves, treedef
    spec_leaves = jax.tree_util.tree_leaves(
        spec, is_leaf=lambda x: x is None)
    if len(spec_leaves) != len(leaves):
        raise ValueError(
            f"spec pytree has {len(spec_leaves)} leaves but the array "
            f"tree has {len(leaves)}")
    return list(spec_leaves), leaves, treedef


def partition_spec_of(spec: ShardSpec, ndim: int, axis_name: str):
    """The ``jax.sharding.PartitionSpec`` a leaf spec denotes — the glue
    between this module's spec language and shard_map in/out specs."""
    from jax.sharding import PartitionSpec as P

    spec = validate_spec(spec, ndim)
    if spec is None:
        return P()
    return P(*([None] * spec + [axis_name]))


def _reshard_leaf(x, src: ShardSpec, dst: ShardSpec, axis_name: str):
    """One leaf's redistribution, on the per-rank block, inside SPMD."""
    import jax

    from ..ops import collective as _col

    ndim = x.ndim
    # src/dst describe the LOGICAL array; the block has the same rank.
    src = validate_spec(src, ndim, "src_spec")
    dst = validate_spec(dst, ndim, "dst_spec")
    if src == dst:
        return x
    p = _col.axis_size(axis_name)
    if src is None and dst is not None:
        # replicated → sharded: a local slice, zero wire bytes.  The
        # result must be typed VARYING over the axis (each rank holds a
        # different block) — axis_index makes that so.
        if x.shape[dst] % p:
            raise ValueError(
                f"cannot shard axis {dst} of shape {x.shape} across "
                f"{p} ranks: {x.shape[dst]} % {p} != 0")
        block = x.shape[dst] // p
        idx = _col.axis_index(axis_name)
        return jax.lax.dynamic_slice_in_dim(x, idx * block, block, axis=dst)
    if dst is None:
        # sharded → replicated: the textbook all_gather, tiled so the
        # blocks concatenate back along the source axis — in its
        # invariant-typed form, since "replicated" is the contract.
        return _col.all_gather(x, axis_name, axis=src, tiled=True,
                               invariant=True)
    # sharded(a) → sharded(b): ONE all_to_all — each rank keeps 1/P of
    # its block and receives 1/P from every peer; (P-1)/P of the payload
    # crosses the wire, vs (P-1)× for gather-then-slice.
    if x.shape[dst] % p:
        raise ValueError(
            f"cannot reshard to axis {dst}: block shape {x.shape} has "
            f"{x.shape[dst]} % {p} != 0")
    return _col.all_to_all(x, axis_name, split_axis=dst, concat_axis=src,
                           tiled=True)


def reshard(tree, src_spec, dst_spec, axis_name: str = "mn"):
    """Redistribute ``tree`` from ``src_spec`` to ``dst_spec`` — call
    inside ``shard_map`` with ``axis_name`` bound; leaves are per-rank
    blocks.  Specs are single values or pytrees matching ``tree``."""
    import jax

    src_leaves, leaves, treedef = _spec_tree(tree, src_spec)
    dst_leaves, _, _ = _spec_tree(tree, dst_spec)
    out = [
        _reshard_leaf(x, s, d, axis_name)
        for x, s, d in zip(leaves, src_leaves, dst_leaves)
    ]
    return jax.tree_util.tree_unflatten(treedef, out)


def make_reshard(mesh, src_spec, dst_spec, axis_name: Optional[str] = None,
                 example=None) -> Callable:
    """Compile a whole-array redistribution program over ``mesh``.

    Returns ``fn(global_tree) -> global_tree`` where the input carries
    ``src_spec``'s sharding and the output ``dst_spec``'s — the callable
    form the KV-slab transfer and train→serve weight handoff use.  One
    compiled program per (shape, dtype, spec-pair); indices are static
    by construction, so repeated transfers hit the jit cache.

    ``example`` (optional pytree of shapes/arrays) pins the spec-pytree
    structure early with a clear error instead of at first call.
    """
    import jax

    from .._compat import shard_map

    ax = axis_name or mesh.axis_names[0]
    # one compiled program per (tree structure, leaf shapes/dtypes):
    # repeated transfers of same-shaped state reuse it (the jit objects
    # live here, not per call, so the cache actually holds)
    programs = {}

    def fn(tree):
        src_leaves, leaves, treedef = _spec_tree(tree, src_spec)
        dst_leaves, _, _ = _spec_tree(tree, dst_spec)
        key = (treedef,
               tuple((tuple(x.shape), str(getattr(x, "dtype", "?")))
                     for x in leaves))
        jitted = programs.get(key)
        if jitted is None:
            in_specs = jax.tree_util.tree_unflatten(
                treedef,
                [partition_spec_of(s, x.ndim, ax)
                 for s, x in zip(src_leaves, leaves)])
            out_specs = jax.tree_util.tree_unflatten(
                treedef,
                [partition_spec_of(d, x.ndim, ax)
                 for d, x in zip(dst_leaves, leaves)])

            def body(t):
                return reshard(t, src_spec, dst_spec, ax)

            jitted = jax.jit(shard_map(body, mesh=mesh,
                                       in_specs=(in_specs,),
                                       out_specs=out_specs))
            programs[key] = jitted
        return jitted(tree)

    fn.programs = programs  # the analysis/recompile probes read this
    if example is not None:
        _spec_tree(example, src_spec)
        _spec_tree(example, dst_spec)
    return fn


def reshard_cost(shape: Sequence[int], dtype, src: ShardSpec,
                 dst: ShardSpec, axis_size: int) -> dict:
    """Static prediction for one leaf's redistribution: which collective,
    its LEDGER payload bytes (``observability.comm.payload_info``'s
    convention — the per-rank input block of the call), and the physical
    ring wire bytes via ``ops.collective.collective_wire_cost``.  This is
    the number the comm ledger must book and the shard-flow model must
    derive — the property tests hold all three to each other."""
    import numpy as np

    from ..ops.collective import collective_wire_cost

    ndim = len(shape)
    src = validate_spec(src, ndim, "src")
    dst = validate_spec(dst, ndim, "dst")
    p = int(axis_size)
    item = np.dtype(dtype).itemsize
    total = int(np.prod(shape)) * item if shape else item
    block = total // p if p else total

    def out(primitive, ledger_bytes):
        wire = (collective_wire_cost(primitive, ledger_bytes, p)
                if primitive else {"wire_bytes": 0, "messages": 0})
        return {"primitive": primitive, "ledger_bytes": int(ledger_bytes),
                "wire_bytes": int(wire["wire_bytes"]),
                "messages": int(wire["messages"])}

    if src == dst or p <= 1:
        return out(None, 0)
    if src is None and dst is not None:
        return out(None, 0)          # local slice
    if dst is None:
        return out("all_gather", block)
    return out("all_to_all", block)


def reshard_tree_cost(tree, src_spec, dst_spec, axis_size: int) -> dict:
    """Sum of :func:`reshard_cost` over a pytree — the whole transfer's
    predicted ledger/wire bytes."""
    import jax

    src_leaves, leaves, _ = _spec_tree(tree, src_spec)
    dst_leaves, _, _ = _spec_tree(tree, dst_spec)
    total = {"ledger_bytes": 0, "wire_bytes": 0, "messages": 0,
             "per_primitive": {}}
    for x, s, d in zip(leaves, src_leaves, dst_leaves):
        c = reshard_cost(x.shape, x.dtype, s, d, axis_size)
        total["ledger_bytes"] += c["ledger_bytes"]
        total["wire_bytes"] += c["wire_bytes"]
        total["messages"] += c["messages"]
        if c["primitive"]:
            row = total["per_primitive"].setdefault(
                c["primitive"], {"ledger_bytes": 0, "calls": 0})
            row["ledger_bytes"] += c["ledger_bytes"]
            row["calls"] += 1
    return total


# ---------------------------------------------------------------------------
# host-side twin: checkpoint shard re-partitioning (numpy only, no devices)
# ---------------------------------------------------------------------------

def _split_even(n: int, parts: int, what: str) -> int:
    if parts < 1:
        raise ValueError(f"{what}: need at least 1 partition, got {parts}")
    if n % parts:
        raise ValueError(
            f"{what}: axis length {n} does not divide evenly into "
            f"{parts} partitions")
    return n // parts


def lower_schedule(shape, dtype, src_spec, dst_spec, src_world: int,
                   dst_world: int, kind: str = "auto", topology=None,
                   n_chunks: int = 2, depth: int = 2):
    """Lower one (src,dst) spec pair to a VERIFIED collective schedule
    (ISSUE 19 / ROADMAP item 3).

    ``kind`` names a generator (``single`` — the monolithic lowering
    :func:`reshard` performs today — ``chunked``, ``pipelined``,
    ``hierarchical``) or ``"auto"`` to pick the cheapest verified
    candidate under the r04 cost model.  Every returned schedule has
    passed the full :mod:`~chainermn_tpu.analysis.schedule_check`
    verifier (coverage vs the array_split statics, exhaustive BFS of
    the start/done machine, interpreter byte-exactness) — an
    unverifiable schedule raises instead of escaping.
    """
    from ..analysis.schedule_check import verified_schedule

    return verified_schedule(kind, shape, dtype, src_spec, dst_spec,
                             src_world, dst_world, topology,
                             n_chunks=n_chunks, depth=depth)


def _emit_schedule_exec(prof) -> None:
    """Fan one profiled execution's records out to the observability
    plane (ISSUE 20): every op becomes an HLC-stamped journal line
    (``kind="schedule_exec"``, fingerprint-keyed), a tracer complete
    event on the live trace, and a ``schedule_exec/*`` counter bump in
    the comm ledger; one flight-note summary rides the /statusz ring.
    """
    from ..observability import comm as _comm
    from ..observability import journal as _journal
    from ..observability import trace as _trace

    recs = prof.run_records()
    if not recs:
        return
    if _journal.enabled():
        for rec in recs:
            _journal.emit("schedule_exec",
                          **{k: v for k, v in rec.items()
                             if k != "schema"})
    _comm.record_schedule_exec(recs)
    if _trace.enabled():
        # the run just finished: back-date each op from "now" so the
        # lane lines up with the surrounding spans.
        base = _trace.now_us() - prof.wall_us()
        for rec in recs:
            _trace.complete_event(
                f"sched/{rec['op']}({rec['arg']})",
                int(base + rec["t_us"]), max(1, int(rec["wall_us"])),
                cat="schedule_exec", link=rec["link"],
                rank=rec["rank"], bytes=rec["bytes"],
                fingerprint=rec["fingerprint"])


def _scheduled_leaf(vals, src_axis: int, dst_spec, dst_count: int,
                    kind: str, topology):
    """Route one sharded leaf through a verified schedule's interpreter.

    Returns the per-destination blocks, or ``None`` when the leaf falls
    outside the schedule geometry (unequal source blocks, uneven
    destination split, mixed dtypes) — the caller then takes the direct
    concatenate/slice path, which is byte-identical by the verifier's
    own oracle.

    When the journal or tracer is live the execution runs under a
    :class:`~chainermn_tpu.analysis.schedule_check.ScheduleExecProfile`
    and every op lands in the observability plane (see
    :func:`_emit_schedule_exec`); with both off, not a single record is
    built — the PR 17 zero-overhead-off discipline.
    """
    import numpy as np

    from ..analysis.schedule import block_shape
    from ..analysis.schedule_check import run_schedule

    arrs = [np.asarray(v) for v in vals]
    first = arrs[0]
    if any(a.shape != first.shape or a.dtype != first.dtype
           for a in arrs[1:]):
        return None
    if not 0 <= src_axis < first.ndim:
        return None
    shape = list(first.shape)
    shape[src_axis] = shape[src_axis] * len(arrs)
    shape = tuple(shape)
    if isinstance(dst_spec, int):
        if not 0 <= dst_spec < first.ndim:
            return None
        if shape[dst_spec] % dst_count:
            return None                  # direct path raises the error
    sched = lower_schedule(shape, str(first.dtype), src_axis, dst_spec,
                           len(arrs), dst_count, kind=kind,
                           topology=topology)
    profiler = None
    from ..observability import journal as _journal
    from ..observability import trace as _trace
    if _journal.enabled() or _trace.enabled():
        from ..analysis.schedule_check import ScheduleExecProfile
        profiler = ScheduleExecProfile(sched)
    outs = run_schedule(sched, [np.ascontiguousarray(a).reshape(-1)
                                for a in arrs], profiler=profiler)
    if profiler is not None:
        _emit_schedule_exec(profiler)
    return [outs[r].reshape(block_shape(shape, dst_spec, r, dst_count))
            for r in range(dst_count)]


def reshard_host(shards: Sequence[Any], src_layout, dst_layout,
                 dst_count: int, *, schedule: Optional[str] = None,
                 topology=None) -> List[Any]:
    """Re-partition per-process host pytrees between world sizes.

    ``shards`` is the COMPLETE old-world list (one pytree per source
    process, rank order); ``src_layout``/``dst_layout`` follow the same
    spec language as :func:`reshard` (single spec or spec pytree), with
    one host-side addition: the string ``"per_rank"`` marks state that
    is rank-SPECIFIC rather than a partition of a logical array — new
    rank ``r`` inherits old rank ``r % len(shards)``'s value (iterator
    cursors and RNG must be re-derived by the caller; the multi-node
    iterator installs the master's broadcast state, which tolerates
    this).  Returns ``dst_count`` pytrees.

    Exactness contract: for replicated leaves the output is shard 0's
    value bit-for-bit on every destination; for sharded leaves the
    concatenation of destination blocks equals the concatenation of
    source blocks (numpy arrays throughout; nothing touches a device).

    ``schedule`` (ISSUE 19) routes sharded-source array leaves through
    a VERIFIED collective schedule instead of the direct
    concatenate/slice: ``"auto"`` picks the cheapest candidate under
    the r04 cost model, or name a generator (``"single"``,
    ``"chunked"``, ``"pipelined"``, ``"hierarchical"`` — the latter
    staging cross-slice bytes over a gateway when ``topology`` has a
    DCN tier).  Every schedule has passed
    :func:`~chainermn_tpu.analysis.schedule_check.verify_schedule`
    (coverage reconciled against the same split statics, exhaustive
    BFS of its start/done machine, interpreter byte-exactness), so the
    result is bit-identical to the direct path; leaves outside the
    schedule geometry (replicated/``per_rank`` sources, unequal blocks)
    keep the direct path.
    """
    import numpy as np

    if not shards:
        raise ValueError("reshard_host: empty shard list")
    if dst_count < 1:
        raise ValueError(f"reshard_host: dst_count must be >= 1, got "
                         f"{dst_count}")
    src_count = len(shards)

    import jax

    def norm(layout):
        if layout is None or isinstance(layout, (int, str)):
            leaves0, treedef = jax.tree_util.tree_flatten(shards[0])
            return [layout] * len(leaves0), treedef
        leaves = jax.tree_util.tree_leaves(
            layout, is_leaf=lambda x: x is None)
        _, treedef = jax.tree_util.tree_flatten(shards[0])
        if len(leaves) != treedef.num_leaves:
            raise ValueError(
                f"layout has {len(leaves)} leaves but state has "
                f"{treedef.num_leaves}")
        return list(leaves), treedef

    src_specs, treedef = norm(src_layout)
    dst_specs, _ = norm(dst_layout)
    shard_leaves = [jax.tree_util.tree_flatten(s)[0] for s in shards]
    for i, ls in enumerate(shard_leaves):
        if len(ls) != len(shard_leaves[0]):
            raise ValueError(
                f"shard {i} has {len(ls)} leaves, shard 0 has "
                f"{len(shard_leaves[0])} — shards disagree on structure")

    out_leaves: List[List[Any]] = [[] for _ in range(dst_count)]
    for li in range(len(shard_leaves[0])):
        src = src_specs[li]
        dst = dst_specs[li]
        vals = [shard_leaves[p][li] for p in range(src_count)]
        if src == "per_rank" or dst == "per_rank":
            if src != dst:
                raise ValueError(
                    "per_rank state cannot be resharded to/from an array "
                    f"partition (leaf {li}: src={src!r}, dst={dst!r})")
            for r in range(dst_count):
                out_leaves[r].append(vals[r % src_count])
            continue
        if src is None:
            full = vals[0]
        else:
            src = validate_spec(src, np.asarray(vals[0]).ndim, "src_layout")
            if schedule is not None and dst != "per_rank":
                blocks = _scheduled_leaf(vals, src, dst, dst_count,
                                         schedule, topology)
                if blocks is not None:
                    for r in range(dst_count):
                        out_leaves[r].append(blocks[r])
                    continue
            full = np.concatenate([np.asarray(v) for v in vals], axis=src)
        if dst is None:
            for r in range(dst_count):
                out_leaves[r].append(full)
            continue
        full = np.asarray(full)
        dst = validate_spec(dst, full.ndim, "dst_layout")
        block = _split_even(full.shape[dst], dst_count,
                            f"reshard_host leaf {li}")
        for r in range(dst_count):
            idx = [slice(None)] * full.ndim
            idx[dst] = slice(r * block, (r + 1) * block)
            out_leaves[r].append(full[tuple(idx)])
    return [jax.tree_util.tree_unflatten(treedef, ls) for ls in out_leaves]
