"""Differentiable-communication tests.

Reference parity: ``tests/functions_tests/test_point_to_point_communication
.py`` and ``test_collective_communication.py`` [uv] (SURVEY.md §4) —
forward values AND gradients across ranks, including the transpose
pairings the reference hand-implemented.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import chainermn_tpu as mn
from chainermn_tpu import functions as F

SIZE = 8


def spmd(fn, n_out=1):
    mesh = mn.make_mesh()
    return jax.jit(jax.shard_map(
        fn, mesh=mesh, in_specs=P("mn"),
        out_specs=P("mn") if n_out == 1 else tuple([P("mn")] * n_out)))


def rank_blocks(shape=(1, 3), seed=0):
    return np.random.RandomState(seed).randn(SIZE * shape[0], *shape[1:]).astype(np.float32)


# ---- forward values ----

def test_send_forward():
    x = rank_blocks()
    out = np.asarray(spmd(lambda b: F.send(b, dest=5, source=2))(x))
    np.testing.assert_array_equal(out[5], x[2])


def test_send_multi_pair():
    x = rank_blocks()
    out = np.asarray(spmd(lambda b: F.send(b, dest=[1, 2], source=[0, 7]))(x))
    np.testing.assert_array_equal(out[1], x[0])
    np.testing.assert_array_equal(out[2], x[7])


def test_ring_exchange_forward():
    from chainermn_tpu.functions.point_to_point import ring_exchange
    x = rank_blocks()
    out = np.asarray(spmd(lambda b: ring_exchange(b, 1))(x))
    for r in range(SIZE):
        np.testing.assert_array_equal(out[(r + 1) % SIZE], x[r])


def test_bcast_forward():
    x = rank_blocks()
    out = np.asarray(spmd(lambda b: F.bcast(b, root=3))(x))
    for r in range(SIZE):
        np.testing.assert_array_equal(out[r], x[3])


def test_allgather_forward():
    x = rank_blocks()
    out = np.asarray(spmd(lambda b: F.allgather(b)[None, :, 0])(x))
    assert out.shape == (SIZE, SIZE, 3)
    for r in range(SIZE):
        np.testing.assert_allclose(out[r], x)


def test_scatter_forward():
    # root rank's block holds SIZE slabs; rank r ends up with slab r
    x = np.arange(SIZE * SIZE, dtype=np.float32).reshape(SIZE, SIZE, 1)

    def fn(b):  # block (1, SIZE, 1): scatter root 0's 8 slabs
        return F.scatter(b[0], root=0)[None]

    out = np.asarray(spmd(fn)(x))
    for r in range(SIZE):
        np.testing.assert_array_equal(out[r, 0], x[0, r])


def test_gather_forward():
    x = rank_blocks()

    def fn(b):
        return F.gather(b, root=2)[None, :, 0]

    out = np.asarray(spmd(fn)(x))
    assert out.shape == (SIZE, SIZE, 3)
    np.testing.assert_allclose(out[2], x)
    assert np.all(out[[r for r in range(SIZE) if r != 2]] == 0)


# ---- gradients: backward is the transpose collective ----

def grad_through(fn, x):
    """d/dx of the GLOBAL sum of fn(x) via the SPMD program.

    Each rank differentiates its LOCAL partial sum; cross-rank coupling
    flows through the transpose collectives inside ``fn``, so the result
    is exactly d(Σ_r loss_r)/dx.  Deliberately NO outer ``psum`` on the
    scalar: the local-loss form needs no assumption about how the
    checker types the scalar's transpose.
    """
    mesh = mn.make_mesh()

    def local_loss(b):
        return jnp.sum(fn(b))

    g = jax.jit(jax.shard_map(
        jax.grad(local_loss), mesh=mesh,
        in_specs=P("mn"), out_specs=P("mn")))
    return np.asarray(g(x))


def test_send_backward_routes_gradient_back():
    """Cotangent at dest flows back to source — Send.backward == recv."""
    x = rank_blocks()

    def fn(b):
        moved = F.send(b, dest=5, source=2)
        idx = jax.lax.axis_index("mn")
        return jnp.where(idx == 5, moved * 3.0, jnp.zeros_like(moved))

    g = grad_through(fn, x)
    np.testing.assert_allclose(g[2], np.full_like(g[2], 3.0))  # source gets it
    for r in range(SIZE):
        if r != 2:
            np.testing.assert_allclose(g[r], 0.0)


def test_bcast_backward_sums_onto_root():
    x = rank_blocks()
    weights = np.arange(1.0, SIZE + 1, dtype=np.float32)

    def fn(b):
        y = F.bcast(b, root=3)
        w = jnp.asarray(weights)[jax.lax.axis_index("mn")]
        return y * w

    g = grad_through(fn, x)
    np.testing.assert_allclose(g[3], np.full_like(g[3], weights.sum()), rtol=1e-6)
    for r in range(SIZE):
        if r != 3:
            np.testing.assert_allclose(g[r], 0.0)


def test_allgather_backward_scatter_sums():
    x = rank_blocks()

    def fn(b):
        g = F.allgather(b)  # (SIZE, 1, 3) on every rank
        w = (jax.lax.axis_index("mn") + 1).astype(jnp.float32)
        return g * w

    g = grad_through(x=x, fn=fn)
    total = np.arange(1.0, SIZE + 1).sum()
    np.testing.assert_allclose(g, np.full_like(g, total), rtol=1e-6)


def test_pseudo_connect_preserves_values_and_grads():
    x = rank_blocks()

    def fn(b):
        delegate = F.send(b, dest=1, source=0)
        tied = F.pseudo_connect(delegate, b * 2.0)
        return tied

    out = np.asarray(spmd(fn)(x))
    np.testing.assert_allclose(out, x * 2.0)
    g = grad_through(fn, x)
    np.testing.assert_allclose(g, np.full_like(g, 2.0))


def test_pseudo_connect_multiple():
    def fn(b):
        d = F.send(b, dest=1, source=0)
        a, c = F.pseudo_connect(d, b + 1, b + 2)
        return a + c

    out = np.asarray(spmd(fn)(rank_blocks()))
    x = rank_blocks()
    np.testing.assert_allclose(out, 2 * x + 3, rtol=1e-6)


def test_pseudo_connect_requires_variables():
    with pytest.raises(ValueError):
        F.pseudo_connect(jnp.ones(3))
