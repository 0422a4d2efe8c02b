"""Share of chip 0's busy time spent in Pallas kernels (Mosaic custom
calls).  The program's kernels carry no stable ``name=`` yet, so an event
counts by its custom-call target, whichever kernel it is (PERF.md, Open
questions)."""

from benchmark.harness.trace_reduce import KERNEL_TAG


def read(trace, spans, run):
    ops = trace["op_seconds"]
    busy = sum(ops.values())
    if not busy:
        return None
    return 100.0 * sum(t for n, t in ops.items() if n.endswith(KERNEL_TAG)) / busy
