"""Share of the traced slice in which chip 0 was idle while the host was in
the tick's host side (``serving/tick``: staging the four arrays, the
launch, and the readback's tail once the device has finished). The six
``serve_idle_share.*`` cut every idle gap along the program's spans and sum
to ``device_idle_share.serve`` (``harness/program_trace.py::idle_split``).
Prints the bucket's own split by ``serving/tick/stage``, ``/dispatch``,
``/readback`` as a free line."""

from benchmark.harness import program_trace


def read(trace, spans, run):
    return program_trace.idle_share(trace, run, "tick_host",
                                    parent="serving/tick")
