"""Share of its roofline that the grouped product's weight gradient
(``moe_gmm_dw``: ``dW[e] = X_e^T dY_e`` summed over an expert's row tiles in
float32) reaches in a train step: 2 x D x F operations a held assignment a
product (the counts of the traced slice's own steps:
``harness/train_moe_window_costs.py::book_slice``), the rows read and the
held experts' gradients written once, over the kernel's measured time a
step."""

from benchmark.harness import train_moe_window_costs


def read(trace, spans, run):
    return train_moe_window_costs.moe_gmm_roofline_share(trace, run,
                                                         "moe_gmm_dw")
