"""Median device duration of one execution of a prefill program
(``serving_prefill_<padded length>``, every bucket together) over the
traced slice."""

from benchmark.harness import program_trace


def read(trace, spans, run):
    return program_trace.program_ms(trace, "serving_prefill_")
