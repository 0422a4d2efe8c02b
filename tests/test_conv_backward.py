"""Parity tests for the Pallas 3x3 conv backward kernels.

Oracle: jax.vjp of the same XLA conv the forward uses.  Shapes are tiny so
interpret mode stays fast; the compiled path runs on the chip in
chip_smoke.py's kernel-parity phase and compiles for it, without one, in
tests/test_chip_compile.py.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chainermn_tpu.ops.conv_backward import (
    conv2d, conv3x3_dgrad, conv3x3_wgrad, _xla_conv, _same_pad)


def _oracle(x, w, dy, stride):
    _, vjp = jax.vjp(lambda x, w: _xla_conv(x, w, stride), x, w)
    return vjp(dy)


def _mk(n, h, w_, ci, co, stride, dtype=jnp.float32, seed=0):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(k1, (n, h, w_, ci), dtype)
    w = jax.random.normal(k2, (3, 3, ci, co), dtype)
    ho, wo = -(-h // stride), -(-w_ // stride)
    dy = jax.random.normal(k3, (n, ho, wo, co), dtype)
    return x, w, dy


SHAPES = [
    (2, 8, 8, 8, 16),
    (4, 6, 6, 16, 8),   # multi-image block (bn=n at the default budget)
    (1, 10, 8, 8, 8),   # non-square plane
    (2, 7, 5, 8, 8),    # odd plane dims: border masks on both axes
]


@pytest.mark.parametrize("n,h,w_,ci,co", SHAPES)
def test_wgrad_parity(n, h, w_, ci, co):
    x, w, dy = _mk(n, h, w_, ci, co, 1)
    want = _oracle(x, w, dy, 1)[1]
    got = conv3x3_wgrad(x, dy, 1, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("n,h,w_,ci,co", SHAPES)
def test_dgrad_parity(n, h, w_, ci, co):
    x, w, dy = _mk(n, h, w_, ci, co, 1)
    want = _oracle(x, w, dy, 1)[0]
    got = conv3x3_dgrad(dy, w, x.shape, 1, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_grid_accumulation_multi_batch_block(monkeypatch):
    """ni>1 parity: at the default VMEM budget every SHAPES case fits one
    batch block (bn=n), so the @pl.when(i==0) zeroing and cross-block dW
    accumulation never run in interpret mode.  Shrinking the budget forces
    bn<n (40 KB -> bn=2 for this shape) and exercises that path off-chip."""
    from chainermn_tpu.ops import conv_backward as cb

    monkeypatch.setattr(cb, "_VMEM_BUDGET", 40 * 1024)
    n, h, w_, ci, co = 4, 6, 6, 16, 8
    x, w, dy = _mk(n, h, w_, ci, co, 1, seed=5)
    want_x, want_w = _oracle(x, w, dy, 1)
    got_w = conv3x3_wgrad(x, dy, 1, interpret=True)
    got_x = conv3x3_dgrad(dy, w, x.shape, 1, interpret=True)
    np.testing.assert_allclose(np.asarray(got_w), np.asarray(want_w),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(got_x), np.asarray(want_x),
                               rtol=1e-4, atol=1e-4)


def test_same_pad_matches_xla():
    # The tap maps assume XLA's SAME split; check against lax's own output
    # shape arithmetic over the planes ResNet uses.
    for h, s in [(56, 2), (28, 2), (14, 2), (7, 1), (9, 2)]:
        lo, hi = _same_pad(h, 3, s)
        out = (h + lo + hi - 3) // s + 1
        assert out == -(-h // s)


def test_conv2d_custom_vjp_end_to_end():
    # 14x14 plane: h*w = 196 meets _eligible's floor, so the custom VJP
    # actually dispatches to the Pallas dgrad/wgrad (an 8x8 plane would
    # silently fall back to the XLA transpose rule and compare XLA to XLA).
    x, w, dy = _mk(2, 14, 14, 8, 8, 1, seed=3)

    def loss_custom(x, w):
        return jnp.sum(conv2d(x, w, 1, True) * dy)

    def loss_xla(x, w):
        return jnp.sum(_xla_conv(x, w, 1) * dy)

    gx, gw = jax.grad(loss_custom, argnums=(0, 1))(x, w)
    ex, ew = jax.grad(loss_xla, argnums=(0, 1))(x, w)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(ex),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(gw), np.asarray(ew),
                               rtol=1e-4, atol=1e-4)


def test_conv2d_1x1_uses_tapless_kernels():
    # 1x1 stride-1 is the k=1 degenerate case (single tapless matmul).
    x, w, dy = _mk(2, 7, 5, 8, 16, 1, seed=11)
    w1 = w[:1, :1]
    want_x, want_w = _oracle(x, w1, dy, 1)
    got_w = conv3x3_wgrad(x, dy, 1, ksize=1, interpret=True)
    got_x = conv3x3_dgrad(dy, w1, x.shape, 1, interpret=True)
    np.testing.assert_allclose(np.asarray(got_w), np.asarray(want_w),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(got_x), np.asarray(want_x),
                               rtol=1e-4, atol=1e-4)


def test_conv2d_fallback_shapes():
    # stride-2 convs must route to the XLA transpose rule.
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 7, 7, 8))
    dy = jnp.ones((2, 7, 7, 8))
    w3 = jax.random.normal(jax.random.PRNGKey(2), (3, 3, 8, 8))
    dy2 = jnp.ones((2, 4, 4, 8))
    gx = jax.grad(lambda x: jnp.sum(conv2d(x, w3, 2, True) * dy2))(x)
    ex = jax.grad(lambda x: jnp.sum(_xla_conv(x, w3, 2) * dy2))(x)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(ex),
                               rtol=1e-4, atol=1e-4)


def test_bf16_inputs_fp32_accumulation():
    x, w, dy = _mk(2, 8, 8, 8, 8, 1, dtype=jnp.bfloat16, seed=7)
    got = conv3x3_wgrad(x, dy, 1, interpret=True)
    assert got.dtype == jnp.bfloat16
    want = _oracle(x, w, dy, 1)[1]
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=0.05, atol=0.05)
