"""Share of the traced slice in which chip 0 was idle while the host was in a
prefill's host side (``serving/prefill``, ``serving/prefix_copy``,
``serving/spill_restore``: staging, launch, and the readback's tail once
the device has finished). The six ``serve_idle_share.*`` cut every idle gap
along the program's spans and sum to ``device_idle_share.serve``
(``harness/program_trace.py::idle_split``). Prints the bucket's own split
by ``serving/prefill/stage``, ``/dispatch``, ``/readback`` as a free line."""

from benchmark.harness import program_trace


def read(trace, spans, run):
    return program_trace.idle_share(trace, run, "prefill_host",
                                    parent="serving/prefill")
