"""Device time by the PROGRAM's own scopes (``jax.named_scope``).

The chip's trace names an operation after the compiler (``fusion.1047``); the
line of the program that wrote it is in the same file, as the ``tf_op`` stat
of the event's XEventMetadata: ``jit(serving_tick)/.../tick/layer/block/mlp/
dot_general``.  ``jax.profiler.ProfileData`` does not surface an event
metadata's stats, so :func:`scope_paths` walks the protobuf's wire format for
them (the ``event_metadata`` / ``stat_metadata`` maps of the planes and
nothing else: the lines, which are the bulk of the file, are skipped by their
length; no tensorflow import).  :func:`split` joins them, by the operation's
name and the program it belongs to, to the events ``trace_reduce.read_events``
gives, and books chip 0's SELF time (a ``while`` without its children:
``trace_reduce.self_times``) inside the executions of one named program
(``XLA Modules`` line, by prefix) to a bucket of :data:`BUCKETS`.

A FUSED operation is one event with ONE ``op_name``, that of the instruction
the compiler built the fusion around: the root of a loop fusion, but the
matrix product of an output fusion (``kind=kOutput``), whatever it fused in
behind the product.  It is booked there whole: the train step's AdamW update
of a weight rides as the epilogue of that weight's gradient product and reads
as ``bwd``, not ``optimizer`` (my chip run, PR 35).  Operations the compiler
made itself (the wait for a prefetched weight, ``copy-done`` / ``slice-done``;
a layout copy) carry no scope and are ``unscoped``.  Two metadata entries of the programs read that share
a name and differ in scope cannot be told apart by an event's name: their time
is booked as ``unscoped`` and printed.  A program's buckets, the unscoped
operations and the bubbles between operations sum to its mean execution time.

    python benchmark/harness/scope_trace.py <dir-or-xplane.pb> --program <prefix>

prints the whole table by leaf scope (ms an execution, share, calls) of any
profile of the program: ``serving_tick``, ``serving_prefill_1024``,
``train_step``.
"""

import os
import re
import sys
import time

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))

from benchmark.harness import (device, program_trace,          # noqa: E402
                               serve_kernel_costs)
from benchmark.harness.trace_reduce import (                    # noqa: E402
    DEVICE_PLANE, SLICE, find_xplane, read_events, short_name)

UNSCOPED = "unscoped"
#: the two phases of ``loss_grad``: the bucket of such a row is its phase
PHASE = ("fwd", "bwd")

#: The metrics' definition (as a kernel's name substring is a kernel
#: metric's): an operation belongs to the FIRST row whose scope lies on its
#: scope path, whole components in a row.  Inner scopes therefore come before
#: the scopes that hold them (``.../block/attn/core/cache_write``,
#: ``block/mlp/block/moe/route``); ``tick/layer``, ``block/mla``,
#: ``block/kda`` and ``block/attn/window`` only hold others and name no
#: bucket.  docs/OBSERVABILITY.md has the same table with what each covers.
BUCKETS = (
    # the train step
    ("optimizer", "optimizer"),
    ("loss_grad", PHASE),
    # the served programs (tick and prefills)
    ("cache_write", "cache_write"),
    ("tick/work_list", "attn_core"),
    ("block/kda/conv", "attn_core"),
    ("block/kda/gate", "attn_core"),
    ("block/kda/state_update", "attn_core"),
    ("block/attn/core", "attn_core"),
    ("block/mla/core", "attn_core"),
    ("block/attn/gate", "attn_proj"),
    ("block/attn/proj", "attn_proj"),
    ("block/mla/proj", "attn_proj"),
    ("block/kda/proj", "attn_proj"),
    ("block/moe/route", "moe_route"),
    ("block/moe/dispatch", "moe_route"),
    ("block/moe/gmm", "moe_experts"),
    ("block/moe/shared", "ffn_dense"),
    ("block/mlp", "ffn_dense"),
    ("tick/embed", "embed_head"),
    ("tick/head", "embed_head"),
    ("prefill/embed", "embed_head"),
    ("prefill/head", "embed_head"),
)
#: scopes that split a PHASE row of the printed table (never a bucket)
DETAIL = ("embed", "head_ce", "block/attn", "block/mlp")

_WRAPPER = re.compile(r"[A-Za-z_]+\(|\)")
_OP_TYPE = re.compile(r":[\w.\-]*$")


def scope_path(op_name: str) -> str:
    """``op_name`` (an HLO instruction's, the trace's ``tf_op``) as plain
    components between slashes: the ``:type`` tail cut, every transform's
    wrapper taken off (``transpose(jvp(block/attn))`` → ``block/attn``).
    Where the compiler merged operations it joined their names with ``;``:
    the first one owns the result."""
    name = _OP_TYPE.sub("", op_name.split(";", 1)[0])
    return "/" + _WRAPPER.sub("", name).strip("/") + "/"


def leaf_of(op_name: str):
    """``(bucket, leaf scope)`` of an operation, ``(None, None)`` where no
    row of :data:`BUCKETS` lies on its path.  The phase rule: under
    ``loss_grad`` an operation whose path holds ``transpose(`` runs in the
    backward pass (a rematerialised forward inside it too: it runs there),
    every other in the forward pass; the leaf is then the phase's own
    :data:`DETAIL` scope."""
    path = scope_path(op_name)
    for scope, bucket in BUCKETS:
        if f"/{scope}/" not in path:
            continue
        if bucket is PHASE:
            bucket = PHASE[1] if "transpose(" in op_name else PHASE[0]
            scope = next((d for d in DETAIL if f"/{d}/" in path), scope)
        return bucket, scope
    return None, None


def bucket_of(op_name: str):
    """The bucket of :data:`BUCKETS` an operation is booked to, by its scope
    path alone; ``None``: unscoped."""
    return leaf_of(op_name)[0]


# ---- the trace file's metadata, by its wire format ------------------------

def _varint(buf, i):
    x = shift = 0
    while True:
        c = buf[i]
        i += 1
        x |= (c & 0x7F) << shift
        if c < 0x80:
            return x, i
        shift += 7


def _fields(buf):
    """``(field number, wire type, value)`` of one message: an int for a
    varint, a memoryview for a length-delimited or fixed-width field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = buf[i], i + 1      # one byte, nearly always: no call
        if key >= 0x80:
            key, i = _varint(buf, i - 1)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            size, i = buf[i], i + 1
            if size >= 0x80:
                size, i = _varint(buf, i - 1)
            value, i = buf[i:i + size], i + size
        elif kind in (1, 5):
            size = 8 if kind == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"wire type {kind} in an .xplane.pb")
        yield key >> 3, kind, value


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _map_value(entry):
    """The value message of one protobuf map entry."""
    return next((v for f, k, v in _fields(entry) if f == 2 and k == 2), b"")


def read_metadata(path: str) -> dict:
    """``{"programs": {program id: name}, "ops": {chip: [(name, program id
    or None, tf_op or None)]}}`` of an ``.xplane.pb``: every plane's event
    metadata named ``jit_<function>(<id>)`` (the ``/host:metadata`` plane
    lists the compiled programs so), and each device plane's event metadata
    with its ``program_id`` and ``tf_op`` stats — the latter a string or a
    reference into the plane's stat metadata."""
    with open(find_xplane(path), "rb") as f:
        space = memoryview(f.read())
    programs, ops = {}, {}
    for field, kind, plane in _fields(space):
        if field != 1 or kind != 2:
            continue
        name, entries, stat_names = "", [], {}
        for f, k, v in _fields(plane):
            if f == 2 and k == 2:
                name = _text(v)
            elif f == 4 and k == 2:
                entries.append(_map_value(v))
            elif f == 5 and k == 2:
                meta = {pf: pv for pf, _, pv in _fields(_map_value(v))}
                if 1 in meta:
                    stat_names[meta[1]] = _text(meta.get(2, b""))
        chip = DEVICE_PLANE.match(name)
        wanted = {i: n for i, n in stat_names.items()
                  if n in ("tf_op", "program_id")}
        for entry in entries:
            ev_name, stats = "", []
            for f, k, v in _fields(entry):
                if f == 2 and k == 2:
                    ev_name = _text(v)
                elif f == 5 and k == 2 and chip:
                    stats.append(v)
            module = program_trace.MODULE_NAME.match(ev_name)
            if module:
                ident = re.search(r"\((\d+)\)$", ev_name)
                programs[int(ident.group(1))] = module.group(1)
            if not chip:
                continue
            program = tf_op = None
            for stat in stats:
                # a dozen stats an operation, two of them wanted: look at a
                # stat's ``metadata_id`` (its first field) before its value
                which = wanted.get(next(
                    (v for f, _, v in _fields(stat) if f == 1), None))
                if which is None:
                    continue
                got = {f: v for f, _, v in _fields(stat)}
                if which == "program_id":
                    program = got.get(3, got.get(4))
                elif which == "tf_op":
                    tf_op = (_text(got[5]) if 5 in got
                             else stat_names.get(got.get(7)))
            ops.setdefault(int(chip.group(1)), []).append(
                (ev_name, program, tf_op))
    return {"programs": programs, "ops": ops}


def scope_paths(meta: dict, chip: int, prefix: str):
    """``({operation's short name: tf_op}, {ambiguous short names})`` of the
    programs whose name starts with ``prefix`` on ``chip``.  An entry
    without a program id may belong to any program and is kept (every
    entry, where the file names no such program: the names then decide
    alone); entries that share a short name and differ in ``tf_op`` are
    ambiguous."""
    ids = {i for i, n in meta["programs"].items() if n.startswith(prefix)}
    paths, ambiguous = {}, set()
    for name, program, tf_op in meta["ops"].get(chip, ()):
        if tf_op is None or (ids and program is not None
                             and program not in ids):
            continue
        key = short_name(name)
        if paths.setdefault(key, tf_op) != tf_op:
            ambiguous.add(key)
    return paths, ambiguous


# ---- the split -------------------------------------------------------------

def self_ns(start, end):
    """``trace_reduce.self_times``' rule — an operation that holds others (a
    ``while``, a ``conditional``) counts without them — on arrays sorted by
    ``(start, -end)``: nanoseconds an event.  That function walks a Python
    stack an event; a 2 s slice of a served model holds 1–4 million, so here
    only the holders are walked, innermost first, each less what lies
    inside it."""
    import numpy as np

    own = end - start
    holders = np.flatnonzero(end[:-1] > start[1:])   # the next starts inside
    stops = np.searchsorted(start, end[holders], side="left")
    for p, q in zip(holders[::-1].tolist(), stops[::-1].tolist()):
        own[p] -= own[p + 1:q].sum()
    return own


def split_events(ops, runs, paths, ambiguous) -> dict:
    """Book ``ops`` (one chip's ``(short name, start, end)``) that start
    inside ``runs`` (sorted ``(start, end)`` of the program's executions) by
    ``paths``.  Milliseconds are per execution (the mean); ``leaves`` rows
    are ``(bucket, leaf scope, ms, calls)``, largest first."""
    import numpy as np

    names, start, end = zip(*ops) if ops else ((), (), ())
    start, end = np.asarray(start, float), np.asarray(end, float)
    run_start = np.asarray([s for s, _ in runs], float)
    run_end = np.asarray([e for _, e in runs], float)
    run = np.searchsorted(run_start, start, side="right") - 1
    inside = np.flatnonzero((run >= 0) & (start < run_end[run]))
    inside = inside[np.lexsort((-end[inside], start[inside]))]
    index = {name: i for i, name in enumerate(dict.fromkeys(names))}
    ident = np.fromiter(map(index.__getitem__, names), np.int64,
                        len(names))[inside]
    per_ms = 1e-6 / len(runs)
    self_ms = np.bincount(ident, self_ns(start[inside], end[inside]),
                          len(index)) * per_ms
    calls = np.bincount(ident, minlength=len(index))
    rows, buckets, stray = {}, {}, []
    unscoped = {"ambiguous": 0.0, "no_scope": 0.0}
    for name, i in index.items():
        if not calls[i]:
            continue
        ms = float(self_ms[i])
        if name in ambiguous:
            key = (UNSCOPED, "ambiguous")
        else:
            key = leaf_of(paths.get(name, ""))
            if key[0] is None:
                key = (UNSCOPED, "no_scope")
        if key[0] == UNSCOPED:
            unscoped[key[1]] += ms
            stray.append((ms, name, paths.get(name)))
        else:
            buckets[key[0]] = buckets.get(key[0], 0.0) + ms
        row = rows.setdefault(key, [0.0, 0])
        row[0] += ms
        row[1] += int(calls[i])
    mean_ms = sum(e - s for s, e in runs) / 1e6 / len(runs)
    unscoped["bubbles"] = mean_ms - sum(buckets.values()) \
        - unscoped["ambiguous"] - unscoped["no_scope"]
    return {"executions": len(runs), "mean_ms": mean_ms, "buckets": buckets,
            "unscoped": unscoped, "stray": sorted(stray, reverse=True)[:8],
            "leaves": sorted(((b, leaf, ms, n) for (b, leaf), (ms, n)
                              in rows.items()), key=lambda r: -r[2])}


def split(path: str, prefix: str, modules=None, ops=None):
    """The split of the programs named ``prefix*`` in the trace at ``path``:
    chip 0's executions that start inside the traced slice (the whole trace
    where it holds no slice).  Where the caller has read them already:
    ``modules``, those executions ``(name, start, end)``, and ``ops``, chip
    0's operations as ``read_events`` gives them (reading them again is the
    larger part of this function's time).  ``None`` where no such program
    ran or the trace holds no scope of it at all (an older profiler, a
    program without scopes)."""
    began = time.perf_counter()
    path = find_xplane(path)
    meta = read_metadata(path)
    if not meta["ops"]:
        return None
    chip0 = min(meta["ops"])
    if modules is None or ops is None:
        events = read_events(path)
        ops = events["devices"].get(chip0, [])
        slices = [h for h in events["host"] if h[0] == SLICE]
        lo, hi = ((slices[0][1], slices[-1][2]) if slices
                  else (float("-inf"), float("inf")))
        modules = [m for m in program_trace.read_modules(path).get(chip0, ())
                   if lo <= m[1] < hi]
    runs = sorted((s, e) for n, s, e in modules if n.startswith(prefix))
    paths, ambiguous = scope_paths(meta, chip0, prefix)
    if not runs or not any(bucket_of(p) for p in paths.values()):
        return None
    out = split_events(ops, runs, paths, ambiguous)
    out["reader_s"] = time.perf_counter() - began
    return out


def table(out: dict) -> str:
    """The split as lines: bucket, leaf scope, ms an execution, share of the
    execution, calls an execution."""
    n, mean = out["executions"], out["mean_ms"]
    lines = [f"{n} executions, mean {mean:.4f} ms; read in "
             f"{out.get('reader_s', 0.0):.2f} s"]
    rows = out["leaves"] + [(UNSCOPED, "bubbles", out["unscoped"]["bubbles"],
                             0)]
    for bucket, leaf, ms, calls in rows:
        lines.append(f"{bucket:12s} {leaf:24s} {ms:9.4f} ms "
                     f"{100.0 * ms / mean:6.2f} % {calls / n:8.1f} calls")
    return "\n".join(lines)


# ---- the readers' face ------------------------------------------------------

_SPLITS = {}


def bucket_ms(trace: dict, run: dict, prefix: str, bucket: str,
              say_table: bool = False):
    """Mean device milliseconds an execution of program ``prefix*`` spends in
    ``bucket`` over the traced slice; ``unscoped``: the mean execution less
    every named bucket, its three parts printed as a free line.  ``None``
    only where the trace holds no scope of the program at all.
    ``say_table``: print the whole table by leaf scope as free lines."""
    view = program_trace.load(trace)
    found = program_trace.newest_xplane() if view is not None else None
    if found is None:
        return None
    key = (found[0], prefix)
    if key not in _SPLITS:
        _SPLITS.clear()
        # a serving cell's kernel readers have chip 0's operations in memory
        _SPLITS[key] = split(found[0], prefix, view["modules"],
                             serve_kernel_costs._EVENTS.get(found[0]))
    out = _SPLITS[key]
    if out is None:
        return None

    def say(text):
        import jax

        device.say(jax.devices()[:run.get("chips", 1)], text)

    if say_table:
        for line in table(out).split("\n"):
            say(f"scope_trace {prefix}: {line}")
    if bucket != UNSCOPED:
        return out["buckets"].get(bucket, 0.0)
    say(f"scope_trace {prefix}: unscoped = " + ", ".join(
        f"{k} {v:.4f} ms" for k, v in out["unscoped"].items())
        + "; the largest: " + ", ".join(
            f"{name} ({op_name!r}) {ms:.4f} ms"
            for ms, name, op_name in out["stray"]))
    return out["mean_ms"] - sum(out["buckets"].values())


if __name__ == "__main__":
    import argparse

    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("trace", help="a profile directory or an .xplane.pb")
    parser.add_argument("--program", required=True,
                        help="prefix of the jitted program's name")
    args = parser.parse_args()
    result = split(args.trace, args.program)
    if result is None:
        print(f"no execution of {args.program}* with a scope in {args.trace}")
        sys.exit(1)
    print(table(result))
