"""Share of the routed (token, expert) assignments that fall on experts this
chip holds, in the CHECK's steps — the trainer's first three, from the seeded
weights: ``train/moe_assignments_held`` over ``train/moe_assignments_total``
(the process tracer's counters, booked by
``chainermn_tpu.parallel.moe.book_routing_counts`` from those steps' own aux
once it has reached the host; all expert layers).  25 % at even routing over
16 of 64 experts; the rest is work of the chips this one stands beside.  The
routing the deployment would see: with the absent experts' terms left out
the router LEARNS to prefer the held ones as the run goes on, which
``train_moe_slice_held_share`` reads (PERF.md, Findings PR 38)."""

from benchmark.harness import train_moe_window_costs


def read(trace, spans, run):
    return train_moe_window_costs.held_share()
