"""Share of the (token, layer) pairs the prefills' selective scans walked
that were padding: 1 - real / walked over the window, from the engine's own
counters (``serving/prefill_scan_tokens``, ``serving/prefill_scan_tokens_
padded``).  The scan walks whole chunks of 128 tokens up to the one that
holds the prompt's last real token, so this lies under
``prefill_pad_share``, which counts the whole bucket."""


def read(trace, spans, run):
    m = run.get("engine_metrics", {})
    real = m.get("serving/prefill_scan_tokens")
    walked = m.get("serving/prefill_scan_tokens_padded")
    if real is None or not walked:
        return None
    return 100.0 * (1.0 - real / walked)
