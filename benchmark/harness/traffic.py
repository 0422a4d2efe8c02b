"""The one general generator of open-loop traffic, driven by a mix's data.

Every seed gets the same work: the arrival gaps are the stratified
quantiles of an exponential (so they sum to the window, and the count is
``rate_per_s x seconds`` exactly), the lengths the stratified quantiles of a
clipped log-normal, and their order is drawn from the mix's own
``schedule_seed``, not from ``--seed``.  ``--seed`` draws the tokens (and,
in the family, the weights).  A first version permuted the schedule by
``--seed`` too: two seeds then read a p95 TTFT 10 % apart and tokens/s 3 %
apart, because which requests meet in the queue is the work (my chip runs,
PR 24).  So runs with different seeds differ as two runs of one seed do."""

import math
from statistics import NormalDist

import numpy as np


def _lognormal_quantiles(n: int, spec: dict):
    """``n`` stratified draws of ``{"median", "sigma", "min", "max"}``."""
    nd = NormalDist()
    q = [math.exp(math.log(spec["median"])
                  + spec["sigma"] * nd.inv_cdf((i + 0.5) / n))
         for i in range(n)]
    return np.clip(np.rint(q), spec["min"], spec["max"]).astype(np.int64)


def open_loop(mix: dict, seed: int, seconds: float, vocab: int,
              rate_per_s: float = None):
    """Requests of one window: a list of dicts ``due_s``, ``prompt``
    (token ids), ``max_new``; sorted by ``due_s``, all due inside the
    window."""
    rate = mix["rate_per_s"] if rate_per_s is None else rate_per_s
    n = max(int(round(rate * seconds)), 1)
    order = np.random.default_rng(mix.get("schedule_seed", 0))
    rng = np.random.default_rng(seed)
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    gaps *= seconds / gaps.sum() * n / (n + 1)     # last one due in-window
    due = np.cumsum(order.permutation(gaps))
    prompts = order.permutation(_lognormal_quantiles(n, mix["prompt_len"]))
    outs = order.permutation(_lognormal_quantiles(n, mix["output_len"]))
    total = mix.get("max_total")
    reqs = []
    for t, p, o in zip(due, prompts, outs):
        if total is not None:
            o = min(o, total - p)
        reqs.append({"due_s": float(t),
                     "prompt": rng.integers(0, vocab, int(p), dtype=np.int32),
                     "max_new": int(o)})
    return reqs
