"""Mean busy slots over the engine's slots, sampled by the client loop
after each engine iteration inside the window."""


def read(trace, spans, run):
    samples = run.get("busy_samples")
    if not samples:
        return None
    return 100.0 * sum(samples) / len(samples) / run["n_slots"]
