"""Share of the program calls that returned the cache pool's buffers (ticks,
prefills, prefix copies) after which the buffers they were given had been
deleted: donated, so the cache was written in place and not copied first.
From the engine's own counters (``serving/pool_calls_donated``,
``serving/pool_calls``); a program without them, or a backend that declines
the donation, is told apart: the first reads nothing, the second 0."""


def read(trace, spans, run):
    m = run.get("engine_metrics", {})
    donated, calls = (m.get("serving/pool_calls_donated"),
                      m.get("serving/pool_calls"))
    if donated is None or not calls:
        return None
    return 100.0 * donated / calls
