"""Share of the served ticks that were launched while the tick before them
was still unread: the device had its next tick queued behind the running one
and did not wait for the host to read, emit, book and stage.  From the
engine's own counters (``serving/tick_launches_overlapped``,
``serving/tick_calls``); a program without the first launches every tick
after reading the one before and reads nothing here, one that has it and
never overlaps reads 0."""


def read(trace, spans, run):
    m = run.get("engine_metrics", {})
    overlapped, calls = (m.get("serving/tick_launches_overlapped"),
                         m.get("serving/tick_calls"))
    if overlapped is None or not calls:
        return None
    return 100.0 * overlapped / calls
