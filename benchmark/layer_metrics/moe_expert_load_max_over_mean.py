"""Imbalance of the held experts' load: the busiest held expert's tokens
over the mean of all held experts' (``serving/moe_expert_tokens/<i>``, summed
over layers, ticks and prefills).  1 is even; the busiest expert's group is
the longest run of row tiles in the grouped product."""


def read(trace, spans, run):
    m = run.get("engine_metrics", {})
    loads = [v for k, v in m.items()
             if k.startswith("serving/moe_expert_tokens/")]
    if not loads or not sum(loads):
        return None
    return max(loads) * len(loads) / sum(loads)
