"""Device time a decode tick spends in the flash-decode attention kernel
(``decode_attn_mha`` / ``decode_attn_beam``, one call a layer), from the
traced slice: the ops line's kernel events whose name holds
``decode_attn``, over the ``serving_tick`` executions that start in the
slice.  ``None`` where the tick runs no such kernel (a program whose tick
takes the einsum path)."""

from benchmark.harness import program_trace
from benchmark.harness.trace_reduce import KERNEL_TAG


def read(trace, spans, run):
    seconds = [t for n, t in trace.get("op_seconds", {}).items()
               if n.endswith(KERNEL_TAG) and "decode_attn" in n]
    v = program_trace.load(trace) if seconds else None
    if v is None:
        return None
    ticks = sum(m[0].startswith("serving_tick") for m in v["modules"])
    return sum(seconds) / ticks * 1e3 if ticks else None
