"""Share of the routed (token, expert) assignments that fall on experts this
chip holds: ``serving/moe_assignments_held`` over
``serving/moe_assignments_total`` (``ServingEngine.metrics()``, ticks and
prefills together, every row the programs ran).  6.25 % at even routing
over 16 of 256 experts; the rest is work of the chips this one stands
beside."""


def read(trace, spans, run):
    m = run.get("engine_metrics", {})
    held = m.get("serving/moe_assignments_held")
    total = m.get("serving/moe_assignments_total")
    if held is None or not total:
        return None
    return 100.0 * held / total
