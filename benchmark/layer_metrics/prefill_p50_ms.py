"""Median time of a prefill as the engine stamps it:
``first_token - prefill_start``."""

from benchmark.harness import stats


def read(trace, spans, run):
    times = [(r["timestamps"]["first_token"] - r["timestamps"]["prefill_start"])
             * 1e3 for r in run.get("recs", ())
             if "first_token" in r["timestamps"]]
    return stats.median(times) if times else None
