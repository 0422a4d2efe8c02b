"""Medians and tails, with the sample count that makes a tail mean something."""

import math
import statistics


class TooFewSamples(ValueError):
    pass


def median(values):
    return statistics.median(values)


def percentile(values, q: float, min_beyond: int = 10) -> float:
    """The ``q``-th percentile (nearest rank) of ``values``.

    Refuses a tail with fewer than ``min_beyond`` samples beyond it: a p95
    of 40 requests is the second-worst request, not a tail."""
    n = len(values)
    beyond = n * (1.0 - q / 100.0)
    if n == 0 or beyond < min_beyond:
        raise TooFewSamples(
            f"p{q:g} of {n} samples has {beyond:.1f} beyond it; "
            f"{min_beyond} are needed")
    ordered = sorted(values)
    return ordered[min(n - 1, max(0, math.ceil(q / 100.0 * n) - 1))]


def spread(values) -> float:
    """Interquartile distance as a share of the median — the contract's
    measure of run-to-run spread."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
