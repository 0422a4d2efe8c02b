"""Imbalance of the held experts' load in training: the busiest held
expert's tokens over the mean of all held experts'
(``train/moe_expert_tokens/<i>``, the process tracer's counters, summed over
the expert layers and over the check's steps, from the seeded weights).  1 is even; the busiest
expert's group is the longest run of row tiles in the grouped products."""

from benchmark.harness import train_moe_window_costs


def read(trace, spans, run):
    loads = [v for k, v in train_moe_window_costs.counters().items()
             if k.startswith("train/moe_expert_tokens/")]
    if not loads or not sum(loads):
        return None
    return max(loads) * len(loads) / sum(loads)
