"""Idle share of the chips over the traced slice of a training window."""


def read(trace, spans, run):
    if "train_samples_per_s" not in run["values"] or not trace["window_s"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
