"""Operation counts from shapes.  Families build their per-sample numbers
from these; forward + backward of a matmul or convolution is three times
its forward (recomputation is never counted)."""


def matmul(m: int, k: int, n: int) -> float:
    """Forward FLOPs of an ``(m, k) @ (k, n)`` product."""
    return 2.0 * m * k * n


def conv2d(h_out: int, w_out: int, kh: int, kw: int, c_in: int,
           c_out: int) -> float:
    """Forward FLOPs of one image through a dense 2-D convolution."""
    return 2.0 * h_out * w_out * kh * kw * c_in * c_out


def train(forward: float) -> float:
    """Forward + backward."""
    return 3.0 * forward
