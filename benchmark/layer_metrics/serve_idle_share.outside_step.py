"""Share of the traced slice in which chip 0 was idle while the host was in
the caller's loop: no ``serving/step`` lay over the gap. The six
``serve_idle_share.*`` cut every idle gap along the program's spans and sum
to ``device_idle_share.serve`` (``harness/program_trace.py::idle_split``)."""

from benchmark.harness import program_trace


def read(trace, spans, run):
    return program_trace.idle_share(trace, run, "outside_step")
