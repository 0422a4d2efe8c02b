"""Time of all-reduce / reduce-scatter / all-gather operations on chip 0
per train step, from the traced slice."""


def read(trace, spans, run):
    if not run.get("steps_in_slice") or run["chips"] < 2:
        return None
    return trace["collective_s"] / run["steps_in_slice"] * 1e3
