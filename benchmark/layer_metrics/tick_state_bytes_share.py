"""Which kind of cache sets the tick's traffic: the busy slots' recurrent
state (``serving/tick_state_bytes``, touched once a tick) over it plus the
live rows' bytes (``serving/tick_latent_bytes``), summed over the window's
ticks, from the engine's own counters.  ``None`` for a program without the
counters or a model that keeps no state."""


def read(trace, spans, run):
    m = run.get("engine_metrics", {})
    state, rows = (m.get("serving/tick_state_bytes"),
                   m.get("serving/tick_latent_bytes"))
    if not state or rows is None:
        return None
    return 100.0 * state / (state + rows)
