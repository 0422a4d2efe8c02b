"""The engine's own tick cadence (median wall time between consecutive
tick starts while work is active), from ``ServingEngine.metrics()``.  Not
the gap a client sees between two tokens of one request."""


def read(trace, spans, run):
    return run.get("engine_metrics", {}).get("serving/tick_gap_p50_ms")
