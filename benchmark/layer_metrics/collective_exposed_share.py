"""The part of chip 0's collective time during which no other operation
runs on that chip."""


def read(trace, spans, run):
    if run["chips"] < 2 or not trace["collective_s"]:
        return None
    return 100.0 * trace["collective_exposed_s"] / trace["collective_s"]
