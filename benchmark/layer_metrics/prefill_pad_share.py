"""Share of the prefill programs' token rows that were padding: 1 - real /
padded over the window, from the engine's own counters
(``serving/prefill_tokens_real``, ``serving/prefill_tokens_padded``).  A
prompt is padded up to a multiple of the engine's ``prefill_bucket``."""


def read(trace, spans, run):
    m = run.get("engine_metrics", {})
    real = m.get("serving/prefill_tokens_real")
    padded = m.get("serving/prefill_tokens_padded")
    if real is None or not padded:
        return None
    return 100.0 * (1.0 - real / padded)
