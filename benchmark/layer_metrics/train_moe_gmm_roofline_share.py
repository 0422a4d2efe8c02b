

from benchmark.harness import train_moe_window_costs


def read(trace, spans, run):
    return train_moe_window_costs.moe_gmm_roofline_share(trace, run,
                                                         "moe_gmm")
