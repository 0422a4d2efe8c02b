"""Share of the routed (token, expert) assignments that fall on held experts
in the steps of the TRACED SLICE, a third of the way into the window: the
routing counts those steps' own aux carries, handed to
``harness/train_moe_window_costs.py::book_slice`` by the family once they
have reached the host.  Above ``train_moe_held_share`` by what the router
has learned since the seeded weights: only held experts answer it, so it
sends them more, and a held expert's row tiles — the grouped products' work,
whose roofline shares and ``train_mfu`` count these steps' assignments —
grow with it."""

from benchmark.harness import train_moe_window_costs


def read(trace, spans, run):
    return train_moe_window_costs.slice_held_share()
