"""Median device duration of one execution of the decode tick program
(``serving_tick`` on the device's ``XLA Modules`` line) over the traced
slice: the tick alone, without the prefill and the host gap that the
engine's ``tick_gap_p50_ms`` cadence contains."""

from benchmark.harness import program_trace


def read(trace, spans, run):
    return program_trace.program_ms(trace, "serving_tick")
