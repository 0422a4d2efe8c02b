"""How late the load generator ran: 95th percentile of actual ``submit()``
minus due time.  A starved generator must not read as a fast server."""


def read(trace, spans, run):
    return run.get("summary", {}).get("gen_late_p95_ms")
