"""95th percentile of a request's wait in the engine's queue:
``prefill_start - submitted`` from the handle's own timestamps."""

from benchmark.harness import stats


def read(trace, spans, run):
    waits = [(r["timestamps"]["prefill_start"] - r["timestamps"]["submitted"])
             * 1e3 for r in run.get("recs", ())
             if "prefill_start" in r["timestamps"]]
    try:
        return stats.percentile(waits, 95, run.get("min_tail", 10))
    except stats.TooFewSamples:
        return None
