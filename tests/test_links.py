"""Model-parallel link tests.

Reference parity: ``tests/links_tests/test_multi_node_chain_list.py`` and
``test_multi_node_batch_normalization.py`` [uv] (SURVEY.md §4) — multi-rank
model graphs (chain, branching, multi-model) and synced-BN vs
single-process BN on the gathered batch.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import PartitionSpec as P

import chainermn_tpu as mn
from chainermn_tpu.links import MultiNodeBatchNormalization, MultiNodeChainList

SIZE = 8


def dense(key, n_in, n_out):
    k = jax.random.PRNGKey(key)
    return {"w": jax.random.normal(k, (n_in, n_out)) * 0.1,
            "b": jnp.zeros((n_out,))}


def dense_apply(p, x):
    return jnp.tanh(x @ p["w"] + p["b"])


def test_chain_list_pipeline_forward_matches_sequential():
    comm = mn.create_communicator("xla")
    mnc = MultiNodeChainList(comm)
    params = [dense(i, 4, 4) for i in range(3)]
    mnc.add_link(dense_apply, params[0], rank=0, rank_in=None, rank_out=1)
    mnc.add_link(dense_apply, params[1], rank=1, rank_in=0, rank_out=2)
    mnc.add_link(dense_apply, params[2], rank=2, rank_in=1, rank_out=None)

    x = jax.random.normal(jax.random.PRNGKey(9), (5, 4))
    out = jax.jit(mnc)(x)

    want = x
    for p in params:
        want = dense_apply(p, want)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-5)


def test_chain_list_branching_graph():
    """Fan-out from rank 0 to ranks 1,2; join on rank 3 (reference's
    branching model graphs)."""
    comm = mn.create_communicator("xla")
    mnc = MultiNodeChainList(comm)
    p0, p1, p2 = dense(0, 4, 4), dense(1, 4, 4), dense(2, 4, 4)

    def join_apply(p, xs):
        return dense_apply(p, xs[0] + xs[1])

    p3 = dense(3, 4, 4)
    mnc.add_link(dense_apply, p0, rank=0, rank_in=None, rank_out=[1, 2])
    mnc.add_link(dense_apply, p1, rank=1, rank_in=0, rank_out=3)
    mnc.add_link(dense_apply, p2, rank=2, rank_in=0, rank_out=3)
    mnc.add_link(join_apply, p3, rank=3, rank_in=[1, 2], rank_out=None)

    x = jax.random.normal(jax.random.PRNGKey(7), (5, 4))
    out = jax.jit(mnc)(x)
    h = dense_apply(p0, x)
    want = join_apply(p3, [dense_apply(p1, h), dense_apply(p2, h)])
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-5)


def test_chain_list_differentiable_end_to_end():
    """Gradients flow across stage/chip boundaries (autograd crossing the
    'process boundary', reference §3.5) — train the pipeline."""
    comm = mn.create_communicator("xla")
    mnc = MultiNodeChainList(comm)
    params = [dense(i, 3, 3) for i in range(2)]
    mnc.add_link(dense_apply, params[0], rank=0, rank_in=None, rank_out=1)
    mnc.add_link(dense_apply, params[1], rank=1, rank_in=0, rank_out=None)

    x = jax.random.normal(jax.random.PRNGKey(1), (16, 3))
    y = jax.random.normal(jax.random.PRNGKey(2), (16, 3))

    def loss_fn(plist):
        return jnp.mean((mnc(x, params=plist) - y) ** 2)

    opt = optax.adam(1e-2)
    # fused-jit face: the params list is ONE jit argument, so the default
    # (uncommitted) params() is required — placed=True would pin to chips
    plist = mnc.params()
    state = opt.init(plist)
    l0 = None
    step = jax.jit(lambda pl, st: _step(pl, st))

    def _step(pl, st):
        l, g = jax.value_and_grad(loss_fn)(pl)
        up, st = opt.update(g, st, pl)
        return optax.apply_updates(pl, up), st, l

    for i in range(60):
        plist, state, l = step(plist, state)
        if l0 is None:
            l0 = float(l)
    assert float(l) < l0 * 0.75, (l0, float(l))


def test_chain_list_places_stages_on_their_chips():
    """Placement must be REAL.  Eagerly, each stage's
    params live on its declared rank's chip and each transfer edge commits
    the activation to the consumer's chip — verified from the committed
    devices of params and output."""
    devices = jax.devices()
    comm = mn.create_communicator("xla")
    mnc = MultiNodeChainList(comm)
    params = [dense(i, 4, 4) for i in range(3)]
    mnc.add_link(dense_apply, params[0], rank=0, rank_in=None, rank_out=2)
    mnc.add_link(dense_apply, params[1], rank=2, rank_in=0, rank_out=5)
    mnc.add_link(dense_apply, params[2], rank=5, rank_in=2, rank_out=None)

    for stage, want_rank in zip(mnc._stages, (0, 2, 5)):
        for leaf in jax.tree_util.tree_leaves(stage.params):
            assert leaf.devices() == {devices[want_rank]}, (
                f"stage params not pinned to chip {want_rank}")

    x = jax.random.normal(jax.random.PRNGKey(9), (5, 4))
    out = mnc(x)  # eager: placed execution with real cross-chip copies
    assert out.devices() == {devices[5]}, "output not committed to last stage's chip"

    want = x
    for p in params:
        want = dense_apply(p, want)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-5)


def test_chain_list_placed_execution_differentiable():
    """Gradients through the placed (eager, cross-chip) execution match the
    single-device oracle — device_put transposes move cotangents back."""
    comm = mn.create_communicator("xla")
    mnc = MultiNodeChainList(comm)
    params = [dense(i, 3, 3) for i in range(2)]
    mnc.add_link(dense_apply, params[0], rank=1, rank_in=None, rank_out=6)
    mnc.add_link(dense_apply, params[1], rank=6, rank_in=1, rank_out=None)

    x = jax.random.normal(jax.random.PRNGKey(1), (4, 3))

    def dist_loss(plist):
        return jnp.mean(mnc(x, params=plist) ** 2)

    def ref_loss(plist):
        return jnp.mean(dense_apply(plist[1], dense_apply(plist[0], x)) ** 2)

    got = jax.grad(dist_loss)(mnc.params())
    want = jax.grad(ref_loss)(params)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-5)


def test_chain_list_errors():
    comm = mn.create_communicator("xla")
    mnc = MultiNodeChainList(comm)
    try:
        mnc.add_link(dense_apply, {}, rank=99)
        assert False
    except ValueError:
        pass
    mnc.add_link(dense_apply, dense(0, 2, 2), rank=0, rank_in=3, rank_out=None)
    try:
        mnc(jnp.ones((1, 2)))
        assert False, "expected missing-message error"
    except RuntimeError:
        pass


def test_sync_bn_matches_global_batchnorm():
    """Synced BN over shards == plain BN over the gathered batch
    (the reference's equivalence test)."""
    rng = np.random.RandomState(0)
    x = rng.randn(SIZE * 4, 6).astype(np.float32) * 3 + 1

    model = MultiNodeBatchNormalization(axis_name="mn")
    variables = model.init(jax.random.PRNGKey(0), jnp.zeros((4, 6)))

    mesh = mn.make_mesh()
    def fwd(v, b):
        y, updated = model.apply(v, b, mutable=["batch_stats"])
        return y, updated["batch_stats"]

    smapped = jax.jit(jax.shard_map(
        fwd, mesh=mesh,
        in_specs=(P(), P("mn")), out_specs=(P("mn"), P())))
    y, stats = smapped(variables, x)

    # oracle: normalize with the GLOBAL batch moments
    mean, var = x.mean(0), x.var(0)
    want = (x - mean) / np.sqrt(var + 1e-5)
    np.testing.assert_allclose(np.asarray(y), want, rtol=1e-3, atol=1e-4)
    # running stats track the global moments
    np.testing.assert_allclose(
        np.asarray(stats["mean"]), 0.1 * mean, rtol=1e-3, atol=1e-4)


def test_sync_bn_local_fallback_without_axis():
    x = np.random.RandomState(0).randn(8, 4).astype(np.float32)
    model = MultiNodeBatchNormalization(axis_name=None)
    variables = model.init(jax.random.PRNGKey(0), jnp.zeros((8, 4)))
    y, _ = model.apply(variables, x, mutable=["batch_stats"])
    np.testing.assert_allclose(
        np.asarray(y), (x - x.mean(0)) / np.sqrt(x.var(0) + 1e-5),
        rtol=1e-3, atol=1e-4)


def test_sync_bn_running_average_mode():
    model = MultiNodeBatchNormalization(axis_name=None, use_running_average=True)
    variables = model.init(jax.random.PRNGKey(0), jnp.zeros((4, 3)))
    x = np.random.RandomState(1).randn(4, 3).astype(np.float32)
    y = model.apply(variables, x)  # mean 0 var 1 stats -> identity transform
    np.testing.assert_allclose(np.asarray(y), x / np.sqrt(1 + 1e-5), rtol=1e-5)
