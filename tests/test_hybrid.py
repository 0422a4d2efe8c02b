"""Hybrid DP×TP tests: one jitted step over a ('data','model') mesh.

Reference parity: SURVEY.md §2.8 "Hybrid DP×MP" — the reference built 2-D
layouts from ``CommunicatorBase.split`` [uv]; here both hybrid faces must
match a single-device oracle on an 8-device 4×2 mesh.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

import chainermn_tpu as mn
from chainermn_tpu.parallel import (
    init_tp_mlp_params,
    make_hybrid_shard_map_step,
    make_hybrid_train_step,
    shard_pytree,
    state_specs_like,
    tp_mlp,
    tp_mlp_specs,
)

DATA, MODEL = 4, 2
D, F, N = 8, 16, 32


def global_params():
    return init_tp_mlp_params(jax.random.PRNGKey(0), D, F)


def batch():
    rng = np.random.RandomState(0)
    return (rng.randn(N, D).astype(np.float32),
            rng.randn(N, D).astype(np.float32))


def mlp_global(p, x):
    h = jax.nn.gelu(x @ p["wi"] + p["bi"])
    return h @ p["wo"] + p["bo"]


def oracle_step(optimizer, steps=2):
    params = global_params()
    state = optimizer.init(params)
    xs, ys = batch()
    losses = []
    for _ in range(steps):
        def loss_fn(p):
            return jnp.mean((mlp_global(p, xs) - ys) ** 2)
        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, state = optimizer.update(grads, state, params)
        params = optax.apply_updates(params, updates)
        losses.append(float(loss))
    return params, losses


def make_2d_mesh():
    return mn.make_nd_mesh(("data", "model"), (DATA, MODEL))


class TestShardMapFace:
    def test_parity_with_single_device_oracle(self):
        """TP MLP inside, DP gradient mean outside, one jitted step — equals
        the single-device full-batch step (incl. SGD momentum state)."""
        mesh = make_2d_mesh()
        optimizer = optax.sgd(0.1, momentum=0.9)
        specs = tp_mlp_specs("model")
        params = global_params()

        def loss_fn(p, b):
            y = tp_mlp(b[0], p, axis_name="model")
            return jnp.mean((y - b[1]) ** 2)

        step = make_hybrid_shard_map_step(
            loss_fn, optimizer, mesh, params, specs, donate=False)
        p = shard_pytree(params, mesh, specs)
        st = shard_pytree(optimizer.init(params),
                          mesh, state_specs_like(optimizer, params, specs))
        xs, ys = batch()
        b = (jax.device_put(xs, NamedSharding(mesh, P("data"))),
             jax.device_put(ys, NamedSharding(mesh, P("data"))))

        losses = []
        for _ in range(2):
            p, st, loss = step(p, st, b)
            losses.append(float(loss))

        want_params, want_losses = oracle_step(optimizer)
        np.testing.assert_allclose(losses, want_losses, rtol=1e-5)
        for k in want_params:
            np.testing.assert_allclose(
                np.asarray(p[k]), np.asarray(want_params[k]),
                rtol=2e-5, atol=1e-6)

    def test_state_specs_like_momentum(self):
        """Momentum trace inherits the TP specs; scalars replicate."""
        specs = tp_mlp_specs("model")
        st = state_specs_like(optax.sgd(0.1, momentum=0.9),
                              global_params(), specs)
        trace = st[0].trace
        assert trace["wi"] == P(None, "model")
        assert trace["wo"] == P("model", None)


class TestPjitFace:
    def test_parity_and_sharding_preserved(self):
        """pjit face: shardings alone drive the 2-D layout; results match
        the oracle and params keep their TP sharding across steps."""
        mesh = make_2d_mesh()
        optimizer = optax.adam(1e-2)
        specs = tp_mlp_specs("model")
        params = global_params()

        def loss_fn(p, b):
            return jnp.mean((mlp_global(p, b[0]) - b[1]) ** 2)

        step = make_hybrid_train_step(loss_fn, optimizer, donate=False)
        p = shard_pytree(params, mesh, specs)
        st = jax.jit(optimizer.init)(p)
        xs, ys = batch()
        b = (jax.device_put(xs, NamedSharding(mesh, P("data"))),
             jax.device_put(ys, NamedSharding(mesh, P("data"))))

        losses = []
        for _ in range(2):
            p, st, loss = step(p, st, b)
            losses.append(float(loss))

        want_params, want_losses = oracle_step(optimizer)
        np.testing.assert_allclose(losses, want_losses, rtol=1e-5)
        for k in want_params:
            np.testing.assert_allclose(
                np.asarray(p[k]), np.asarray(want_params[k]),
                rtol=2e-5, atol=1e-6)
        # the TP layout survived the step (XLA did not silently replicate)
        assert p["wi"].sharding.spec == P(None, "model")
        assert len(p["wi"].sharding.device_set) == DATA * MODEL
