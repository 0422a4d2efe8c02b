"""Peak bytes in use on the fullest chip, as the runtime reports them."""


def read(trace, spans, run):
    peak = run.get("memory_peak_bytes")
    return None if peak is None else peak / 1e9
