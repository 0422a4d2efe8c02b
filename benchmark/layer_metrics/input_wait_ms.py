"""Mean host time per step inside the feed (``it.next()`` and
``shard_batch``), from the benchmark-side ``input`` spans of the window."""


def read(trace, spans, run):
    start, steps = run.get("window_start"), run.get("steps")
    waits = [e - s for n, s, e in spans if n == "input"
             and start is not None and s >= start]
    if not waits or not steps:
        return None
    return sum(waits) / steps * 1e3
