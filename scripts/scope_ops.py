#!/usr/bin/env python
"""Device time of ONE scope of a traced program, by operation (ISSUE 42).

``benchmark/harness/scope_trace.py`` prints a program's table by LEAF SCOPE
(``block/moe/gmm`` is one row); this prints the rows inside one scope — what
was written there, by the tail of each operation's ``tf_op`` behind the scope
(``gather``, ``mul``, ``jit(moe_gmm)/moe_gmm/pallas_call`` ...): chip 0's
self time inside the program's executions of the traced slice, the mean an
execution, and the calls an execution.  The joins and the self-time rule are
that reader's own functions.

    python scripts/scope_ops.py benchmark/.trace/<cell> \\
        --program serving_tick --scope block/moe/gmm
"""

import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

from benchmark.harness import program_trace, scope_trace  # noqa: E402
from benchmark.harness.trace_reduce import (  # noqa: E402
    SLICE, find_xplane, read_events)


def by_operation(path: str, prefix: str, scope: str):
    """``(executions, [(tail, ms an execution, calls an execution)])`` of the
    operations under ``scope`` in the programs named ``prefix*``, largest
    first; ``None`` where no such program ran."""
    path = find_xplane(path)
    meta = scope_trace.read_metadata(path)
    if not meta["ops"]:
        return None
    chip0 = min(meta["ops"])
    events = read_events(path)
    ops = events["devices"].get(chip0, [])
    slices = [h for h in events["host"] if h[0] == SLICE]
    lo, hi = ((slices[0][1], slices[-1][2]) if slices
              else (float("-inf"), float("inf")))
    runs = sorted((s, e) for n, s, e
                  in program_trace.read_modules(path).get(chip0, ())
                  if lo <= s < hi and n.startswith(prefix))
    if not runs or not ops:
        return None
    paths, ambiguous = scope_trace.scope_paths(meta, chip0, prefix)
    names, start, end = zip(*ops)
    start, end = np.asarray(start, float), np.asarray(end, float)
    run_start = np.asarray([s for s, _ in runs], float)
    run_end = np.asarray([e for _, e in runs], float)
    run = np.searchsorted(run_start, start, side="right") - 1
    inside = np.flatnonzero((run >= 0) & (start < run_end[run]))
    inside = inside[np.lexsort((-end[inside], start[inside]))]
    own = scope_trace.self_ns(start[inside], end[inside])
    rows = {}
    for i, ns in zip(inside.tolist(), own.tolist()):
        name = names[i]
        op_name = paths.get(name, "")
        if name in ambiguous or f"/{scope}/" not in scope_trace.scope_path(
                op_name):
            continue
        tail = op_name.split(";", 1)[0].rsplit(scope + "/", 1)[-1].rstrip(":")
        row = rows.setdefault(tail, [0.0, 0])
        row[0] += ns
        row[1] += 1
    n = len(runs)
    return n, sorted(((tail, ns / 1e6 / n, calls / n)
                      for tail, (ns, calls) in rows.items()),
                     key=lambda r: -r[1])


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("trace", help="a profile directory or an .xplane.pb")
    parser.add_argument("--program", required=True)
    parser.add_argument("--scope", required=True)
    args = parser.parse_args()
    out = by_operation(args.trace, args.program, args.scope)
    if out is None or not out[1]:
        print(f"no operation of {args.program}* under {args.scope} in "
              f"{args.trace}")
        return 1
    n, rows = out
    total = sum(ms for _, ms, _ in rows)
    print(f"{n} executions; {args.scope}: {total:.4f} ms an execution")
    for tail, ms, calls in rows:
        print(f"{ms:9.4f} ms {100 * ms / total:6.2f} % {calls:8.1f} calls  "
              f"{tail}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
