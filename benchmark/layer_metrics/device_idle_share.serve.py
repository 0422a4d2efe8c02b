"""Idle share of the chip over the traced slice of a serving window."""


def read(trace, spans, run):
    if "serve_tokens_per_s" not in run["values"] or not trace["window_s"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
