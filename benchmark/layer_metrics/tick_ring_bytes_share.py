"""Which kind of cache sets the tick's traffic: the busy slots' ring rows
(``serving/tick_ring_bytes``: ``min(pos + 1, window)`` rows a slot a
windowed layer) over them plus the busy slots' rows in the layers that keep
every row (``serving/tick_row_bytes``), summed over the window's ticks, from
the engine's own counters.  ``None`` for a program without the counters or
a model that keeps no ring."""


def read(trace, spans, run):
    m = run.get("engine_metrics", {})
    ring, rows = (m.get("serving/tick_ring_bytes"),
                  m.get("serving/tick_row_bytes"))
    if not ring or rows is None:
        return None
    return 100.0 * ring / (ring + rows)
