"""Share of the gaps between two tokens of a request that are longer than
twice the median gap: the gaps in which the engine admitted a request (a
prefill between two ticks).  It says what ``gap_p95_ms`` reads in a cell:
under ~3 % the p95 is the cadence's own tail, host jitter included; from a
tenth up it lies inside the tick + prefill cluster, which the seeded
schedule decides; between the two it sits on the edge, the noisiest place."""


def read(trace, spans, run):
    return run.get("summary", {}).get("gaps_over_2x_p50_share")
