"""Device busy time per train step on chip 0, from the traced slice."""


def read(trace, spans, run):
    steps = run.get("steps_in_slice")
    if not steps or not trace["busy_s_per_chip"]:
        return None
    chip0 = min(trace["busy_s_per_chip"])
    return trace["busy_s_per_chip"][chip0] / steps * 1e3
