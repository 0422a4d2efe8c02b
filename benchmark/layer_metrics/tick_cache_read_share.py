"""Share of the pool's cache blocks that the ticks' attention had to read:
blocks at or below each slot's position over the blocks the pool holds,
summed over the window's ticks, from the engine's own counters
(``serving/tick_cache_blocks_read``, ``serving/tick_cache_blocks_total``).
The flash-decode kernel reads each slot's cache up to its own length."""


def read(trace, spans, run):
    m = run.get("engine_metrics", {})
    read_, total = (m.get("serving/tick_cache_blocks_read"),
                    m.get("serving/tick_cache_blocks_total"))
    if read_ is None or not total:
        return None
    return 100.0 * read_ / total
