"""Expert-parallel MoE tests vs a dense single-device oracle.

Reference relationship: EP is absent from the reference (SURVEY.md §2.8 —
"alltoall primitive exists, which is the EP substrate"); the oracle is the
dense per-token computation: route each token to its argmax expert, scale
by the gate, zero if over capacity.  Forward AND gradients are checked
across the 8-device mesh (two all_to_alls on the dispatch path).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import chainermn_tpu as mn
from chainermn_tpu.parallel import init_moe_mlp_params, make_moe_mlp

T, D, F, E = 64, 8, 16, 8  # tokens, d_model, d_hidden, experts (= devices)


@pytest.fixture(scope="module")
def mesh(devices):
    return mn.make_mesh(devices)


def params_and_tokens(seed=0, num_experts=E):
    params = init_moe_mlp_params(
        jax.random.PRNGKey(seed), D, F, num_experts)
    x = np.random.RandomState(seed).randn(T, D).astype(np.float32)
    return params, x


def oracle(x, params, capacity_per_device_expert=None, tokens_per_device=None):
    """Dense reference: each token → argmax expert, gated; tokens beyond an
    expert's capacity WITHIN THEIR DEVICE SHARD are dropped to zero."""
    probs = np.asarray(jax.nn.softmax(x @ np.asarray(params["router"]), axis=-1))
    out = np.zeros_like(x)
    e = probs.shape[-1]
    tpd = tokens_per_device or len(x)
    for dev_start in range(0, len(x), tpd):
        counts = np.zeros(e, int)
        for t in range(dev_start, dev_start + tpd):
            ei = int(probs[t].argmax())
            counts[ei] += 1
            if (capacity_per_device_expert is not None
                    and counts[ei] > capacity_per_device_expert):
                continue  # dropped
            h = np.asarray(jax.nn.gelu(
                jnp.asarray(x[t] @ np.asarray(params["wi"][ei])
                            + np.asarray(params["bi"][ei]))))
            y = h @ np.asarray(params["wo"][ei]) + np.asarray(params["bo"][ei])
            out[t] = probs[t, ei] * y
    return out


class TestForward:
    def test_matches_dense_oracle_no_drops(self, mesh):
        params, x = params_and_tokens()
        # capacity_factor=E → capacity = local T, nothing ever drops.
        fn = make_moe_mlp(E, mesh=mesh, capacity_factor=float(E))
        y, aux = fn(x, params)
        want = oracle(x, params)
        np.testing.assert_allclose(np.asarray(y), want, rtol=1e-4, atol=1e-5)
        assert float(aux) > 0

    def test_capacity_drops_tokens(self, mesh):
        params, x = params_and_tokens(seed=1)
        fn = make_moe_mlp(E, mesh=mesh, capacity_factor=1.0)
        y, _ = fn(x, params)
        # capacity = (T/P)/E * 1.0 = 1 token per (device, expert)
        want = oracle(x, params, capacity_per_device_expert=1,
                      tokens_per_device=T // 8)
        np.testing.assert_allclose(np.asarray(y), want, rtol=1e-4, atol=1e-5)

    def test_bf16_dtype_preserved(self, mesh):
        params, x = params_and_tokens(seed=2)
        params = jax.tree_util.tree_map(
            lambda a: a.astype(jnp.bfloat16), params)
        fn = make_moe_mlp(E, mesh=mesh, capacity_factor=float(E))
        y, aux = fn(jnp.asarray(x, jnp.bfloat16), params)
        assert y.dtype == jnp.bfloat16

    def test_experts_divisibility_error(self, mesh):
        params, x = params_and_tokens(num_experts=6)
        with pytest.raises(ValueError, match="divisible"):
            make_moe_mlp(6, mesh=mesh)(x, params)


class TestBackward:
    def test_gradients_match_dense(self, mesh):
        """Grad of a no-drop MoE == grad of the dense gated computation
        (exercises the transposes of both all_to_alls)."""
        params, x = params_and_tokens(seed=3)
        fn = make_moe_mlp(E, mesh=mesh, capacity_factor=float(E))

        def dist_loss(p):
            y, _ = fn(x, p)
            return (y ** 2).sum()

        def ref_loss(p):
            probs = jax.nn.softmax(x @ p["router"], axis=-1)
            ei = jnp.argmax(probs, axis=-1)
            gate = jnp.take_along_axis(probs, ei[:, None], axis=-1)[:, 0]
            h = jax.nn.gelu(
                jnp.einsum("td,tdf->tf", x, p["wi"][ei]) + p["bi"][ei])
            y = jnp.einsum("tf,tfd->td", h, p["wo"][ei]) + p["bo"][ei]
            return ((gate[:, None] * y) ** 2).sum()

        got = jax.grad(dist_loss)(params)
        want = jax.grad(ref_loss)(params)
        for k in params:
            np.testing.assert_allclose(
                np.asarray(got[k]), np.asarray(want[k]),
                rtol=2e-3, atol=1e-4, err_msg=f"grad wrt {k}")


class TestLoadBalanceAux:
    def test_uniform_routing_gives_min_aux(self, mesh):
        """With a zero router every expert gets prob 1/E → aux ≈ 1 (its
        theoretical minimum for top-1)."""
        params, x = params_and_tokens(seed=4)
        params = dict(params, router=jnp.zeros_like(params["router"]))
        _, aux = make_moe_mlp(E, mesh=mesh, capacity_factor=float(E))(x, params)
        assert float(aux) == pytest.approx(1.0, rel=1e-3)


class TestMoeTrainsEndToEnd:
    """EP training end-to-end (the examples/moe workload): loss falls and
    routing stays balanced under the aux loss, through ONE jitted step
    composing DP (tokens sharded) and EP (experts sharded) on the same
    axis via make_hybrid_shard_map_step."""

    def test_loss_falls_and_routing_balanced(self, mesh):
        import optax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from chainermn_tpu.parallel import (
            init_moe_mlp_params, make_hybrid_shard_map_step, moe_mlp,
            moe_mlp_specs, shard_pytree, state_specs_like)

        ax = mesh.axis_names[0]
        e, d_in, d_model, n_cls = 8, 8, 16, 4
        rng = jax.random.PRNGKey(0)
        k_in, k_moe, k_head = jax.random.split(rng, 3)
        params = {
            "w_in": jax.random.normal(k_in, (d_in, d_model)) * 0.3,
            "moe": init_moe_mlp_params(k_moe, d_model, 32, e),
            "w_head": jax.random.normal(k_head, (d_model, n_cls)) * 0.3,
        }
        specs = {"w_in": P(), "moe": moe_mlp_specs(ax), "w_head": P()}

        def loss_fn(p, batch):
            xs, ys = batch
            h = jnp.tanh(xs @ p["w_in"])
            y, aux = moe_mlp(h, p["moe"], axis_name=ax, num_experts=e,
                             capacity_factor=2.0)
            logits = y @ p["w_head"]
            logp = jax.nn.log_softmax(logits.astype(jnp.float32))
            ce = -jnp.mean(jnp.take_along_axis(logp, ys[:, None], 1))
            probs = jax.nn.softmax(
                (h @ p["moe"]["router"]).astype(jnp.float32), -1)
            frac = jax.lax.pmean(
                jnp.mean(jax.nn.one_hot(probs.argmax(-1), e), 0), ax)
            return ce + 0.01 * aux, {"ce": ce, "max_frac": frac.max()}

        opt = optax.adam(3e-2)
        step = make_hybrid_shard_map_step(
            loss_fn, opt, mesh, params, specs, data_axis=ax,
            batch_spec=P(ax), has_aux=True, donate=False)
        p = shard_pytree(params, mesh, specs)
        st = shard_pytree(opt.init(params), mesh,
                         state_specs_like(opt, params, specs))

        nprng = np.random.RandomState(0)
        cents = nprng.randn(n_cls, d_in).astype(np.float32) * 2
        ys_np = nprng.randint(0, n_cls, 128).astype(np.int32)
        xs_np = (cents[ys_np] + nprng.randn(128, d_in)).astype(np.float32)
        batch = tuple(jax.device_put(a, NamedSharding(mesh, P(ax)))
                      for a in (xs_np, ys_np))
        ces = []
        for _ in range(25):
            p, st, loss, aux = step(p, st, batch)
            ces.append(float(aux["ce"]))
        assert ces[-1] < ces[0] * 0.5, ces[::6]
        # expert params must have MOVED (gradients really flow through the
        # two all_to_alls to the per-device expert shards)
        assert float(jnp.abs(p["moe"]["wi"] - params["moe"]["wi"]).sum()) > 0
        # aux loss keeps top-1 routing from collapsing onto one expert
        assert float(aux["max_frac"]) < 0.6, float(aux["max_frac"])


class TestTop2Routing:
    """GShard-style top-2: two experts per token with normalized gates,
    second choices queueing behind first choices under capacity."""

    def _dense_top2_oracle(self, x, params):
        """No-drop oracle: y = g1'·e_i1(x) + g2'·e_i2(x), gates normalized
        over the two choices."""
        probs = jax.nn.softmax(x @ params["router"], axis=-1)
        i1 = jnp.argmax(probs, axis=-1)
        p2 = probs * (1 - jax.nn.one_hot(i1, probs.shape[-1]))
        i2 = jnp.argmax(p2, axis=-1)
        g1 = jnp.take_along_axis(probs, i1[:, None], 1)[:, 0]
        g2 = jnp.take_along_axis(probs, i2[:, None], 1)[:, 0]
        denom = g1 + g2

        def expert(idx, xx):
            h = jax.nn.gelu(
                jnp.einsum("td,tdf->tf", xx, params["wi"][idx])
                + params["bi"][idx])
            return (jnp.einsum("tf,tfd->td", h, params["wo"][idx])
                    + params["bo"][idx])

        return ((g1 / denom)[:, None] * expert(i1, x)
                + (g2 / denom)[:, None] * expert(i2, x))

    def test_matches_dense_oracle_no_drops(self, mesh):
        params, x = params_and_tokens(seed=7)
        fn = make_moe_mlp(E, mesh=mesh, capacity_factor=float(2 * E),
                          router_topk=2)
        y, aux = fn(x, params)
        want = self._dense_top2_oracle(jnp.asarray(x), params)
        np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                                   rtol=2e-4, atol=2e-5)
        assert np.isfinite(float(aux))

    def test_second_choice_rescues_dropped_tokens(self, mesh):
        """The REAL top-2 property: under tight capacity, tokens whose
        first choice overflowed still get output through their second
        expert — strictly fewer all-zero output rows than top-1.  (y2 != y1
        alone would hold from gate renormalization even with a broken
        second-choice dispatch.)"""
        params, x = params_and_tokens(seed=8)
        # capacity = ceil(topk*T/E*cf): these two configs have IDENTICAL
        # per-expert capacity, so any zero-row reduction is second-choice
        # dispatch, not extra slots.
        y1, _ = make_moe_mlp(E, mesh=mesh, capacity_factor=1.0,
                             router_topk=1)(x, params)
        y2, _ = make_moe_mlp(E, mesh=mesh, capacity_factor=0.5,
                             router_topk=2)(x, params)
        zero1 = int((np.abs(np.asarray(y1)).sum(-1) == 0).sum())
        zero2 = int((np.abs(np.asarray(y2)).sum(-1) == 0).sum())
        assert zero1 > 0, "top-1 at cf=0.5 must drop some tokens"
        assert zero2 < zero1, (zero2, zero1)

    def test_gradients_flow_and_train(self, mesh):
        import optax

        params, x = params_and_tokens(seed=9)
        fn = make_moe_mlp(E, mesh=mesh, capacity_factor=2.0, router_topk=2)
        target = np.random.RandomState(9).randn(*np.asarray(x).shape
                                                ).astype(np.float32) * 0.1

        def loss(p):
            y, aux = fn(x, p)
            return jnp.mean((y - target) ** 2) + 0.01 * aux

        opt = optax.adam(1e-2)
        st = opt.init(params)
        l0 = None
        for _ in range(15):
            l, g = jax.value_and_grad(loss)(params)
            up, st = opt.update(g, st, params)
            params = optax.apply_updates(params, up)
            l0 = float(l) if l0 is None else l0
        assert float(l) < l0

    def test_invalid_topk_raises(self, mesh):
        params, x = params_and_tokens(seed=10)
        with pytest.raises(ValueError, match="router_topk"):
            make_moe_mlp(E, mesh=mesh, router_topk=3)(x, params)


# --------------------------------------------------------------------------
# the grouped expert product differentiates (ISSUE 38): moe_gmm's VJP —
# dX by the forward kernel on the transposed weights, dW by ``moe_gmm_dw``
# --------------------------------------------------------------------------

def _grouped(counts, tm, dead_tiles):
    """Tile-aligned groups for per-expert ``counts``: ``(tile_expert,
    n_valid, rows, live rows mask)`` with ``dead_tiles`` tiles of worst-case
    padding past ``n_valid``."""
    counts = np.asarray(counts)
    padded = -(-counts // tm) * tm
    ends = np.cumsum(padded)
    n_valid = int(ends[-1]) // tm
    m = (n_valid + dead_tiles) * tm
    tile_expert = np.minimum(np.searchsorted(
        ends, np.arange(m // tm) * tm, side="right"),
        len(counts) - 1).astype(np.int32)
    live = np.zeros(m, bool)
    for e, (end, pad, n) in enumerate(zip(ends, padded, counts)):
        live[end - pad:end - pad + n] = True
    return tile_expert, n_valid, m, live


@pytest.mark.parametrize("counts,dead", [
    ((13, 0, 5, 8), 0),       # an empty expert, ragged groups
    ((8, 16, 1, 24), 3),      # n_valid below the tile count
    ((0, 0, 9, 0), 2),        # one expert has every row
    ((0, 0, 0, 0), 2),        # nobody has any: nothing is live
], ids=["empty+ragged", "dead-tiles", "one-expert", "no-rows"])
def test_moe_gmm_vjp_is_the_dense_loops(counts, dead):
    from chainermn_tpu.ops.moe_gmm import moe_gmm

    tm, k, n = 8, 16, 24
    tile_expert, n_valid, m, live = _grouped(counts, tm, dead)
    rng = np.random.default_rng(sum(counts) + dead)
    x = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(len(counts), k, n)), jnp.float32)
    ct = jnp.asarray(rng.normal(size=(m, n)), jnp.float32)
    # the caller's contract: dead tiles' rows are masked, padding rows of a
    # live tile are computed and never read back
    keep = jnp.asarray(live)[:, None]

    def kernel(x, w):
        y = moe_gmm(x, w, tile_expert, n_valid, tm=tm, interpret=True)
        return (jnp.where(keep, y, 0.0) * ct).sum()

    def dense(x, w):
        y = jnp.zeros((m, n))
        for e in range(len(counts)):       # a plain loop over the experts
            mine = jnp.asarray(np.repeat(tile_expert, tm) == e)[:, None]
            y = y + jnp.where(mine, x @ w[e], 0.0)
        return (jnp.where(keep, y, 0.0) * ct).sum()

    got = jax.grad(kernel, (0, 1))(x, w)
    want = jax.grad(dense, (0, 1))(x, w)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)
    # an expert with no rows gets zeros, not what the buffer held
    for e, c in enumerate(counts):
        if c == 0:
            assert not np.asarray(got[1][e]).any()
    # and nothing flows into the dead tiles' rows
    assert not np.asarray(got[0])[n_valid * tm:].any()


def test_moe_gmm_dead_rows_may_hold_anything():
    """The forward leaves dead tiles unwritten; a NaN there must reach
    neither gradient."""
    from chainermn_tpu.ops.moe_gmm import moe_gmm

    tm = 8
    tile_expert, n_valid, m, live = _grouped((8, 8), tm, 2)
    x = jnp.ones((m, 16)).at[n_valid * tm:].set(jnp.nan)
    w = jnp.ones((2, 16, 8))
    dx, dw = jax.grad(lambda x, w: jnp.where(
        jnp.asarray(live)[:, None],
        moe_gmm(x, w, tile_expert, n_valid, tm=tm, interpret=True),
        0.0).sum(), (0, 1))(x, w)
    assert np.isfinite(np.asarray(dw)).all()
    assert np.isfinite(np.asarray(dx)).all()
    np.testing.assert_allclose(np.asarray(dw), 8.0)


# --------------------------------------------------------------------------
# the layer's first two products and their activation are ONE grouped kernel
# with its own backward (ISSUE 47): ``moe_gmm_glu`` / ``moe_gmm_glu_dx``
# against two ``moe_gmm`` calls, the element-wise line between them and
# autodiff's sum of their two ``dX``, interpret mode, bit for bit
# --------------------------------------------------------------------------

def _three_products(xs, w_gate, w_up, tile_expert, n_valid, tm):
    """``parallel/moe.py::_staged_product``'s ``hidden`` as it was."""
    from chainermn_tpu.ops.moe_gmm import moe_gmm

    gmm = lambda w: moe_gmm(xs, w, tile_expert, n_valid, tm=tm,
                            interpret=True)
    return (jax.nn.silu(gmm(w_gate).astype(jnp.float32))
            * gmm(w_up).astype(jnp.float32)).astype(xs.dtype)


#: ``counts`` a held expert, dead tiles past ``n_valid``, ``tm``, ``D``,
#: ``F``, the bytes a weight block may take (None: the module's)
GLU_CASES = {
    "held25": ((16, 8, 24, 16), 24, 8, 64, 40, None),     # 8 of 32 tiles live
    "held57": ((40, 24, 32, 32), 12, 8, 64, 40, None),    # 16 of 28
    "empty-expert": ((13, 0, 5, 8), 1, 8, 16, 24, None),
    "no-rows": ((0, 0, 0, 0), 3, 8, 16, 24, None),        # n_valid == 0
    # two (256, 128) blocks a step: F in two tiles forward, D in two back
    "n-tiled": ((40, 16, 1, 24), 3, 16, 256, 256, 2 * 256 * 128 * 2),
}


def _glu_inputs(case, monkeypatch):
    from chainermn_tpu.ops import moe_gmm as mod

    counts, dead, tm, d, f, block_bytes = GLU_CASES[case]
    if block_bytes:
        monkeypatch.setattr(mod, "_W_BLOCK_BYTES", block_bytes)
        assert mod.pick_tn(2 * d, f) == f // 2
        assert mod.pick_tn(2 * f, d) == d // 2
    tile_expert, n_valid, m, _ = _grouped(counts, tm, dead)
    ks = jax.random.split(jax.random.PRNGKey(sorted(GLU_CASES).index(case)),
                          4)
    xs = jax.random.normal(ks[0], (m, d), jnp.bfloat16)
    w_gate, w_up = (jax.random.normal(k, (len(counts), d, f), jnp.bfloat16)
                    * d ** -0.5 for k in ks[1:3])
    # ``d_hidden`` as the down product hands it over: zero in the dead tiles
    live = jnp.asarray(np.arange(m) // tm < n_valid)[:, None]
    ct = jnp.where(live, jax.random.normal(ks[3], (m, f), jnp.bfloat16), 0)
    return (xs, w_gate, w_up), ct, jnp.asarray(tile_expert), n_valid, tm, live


def _as_numbers(a):
    return np.asarray(a.astype(jnp.float32))


@pytest.mark.parametrize("case", sorted(GLU_CASES))
def test_moe_gmm_glu_is_the_three_products_bit_for_bit(case, monkeypatch):
    """``hidden`` over the live tiles and the VJP — ``dxs`` over the live
    tiles, ``dW_gate``, ``dW_up`` whole — are the separate products' bits."""
    from chainermn_tpu.ops.moe_gmm import moe_gmm_glu

    args, ct, tile_expert, n_valid, tm, live = _glu_inputs(case, monkeypatch)
    want, vjp0 = jax.vjp(lambda *a: _three_products(
        *a, tile_expert, n_valid, tm), *args)
    got, vjp = jax.vjp(lambda *a: moe_gmm_glu(
        *a, tile_expert, n_valid, tm=tm, interpret=True), *args)
    assert got.dtype == want.dtype == jnp.bfloat16
    n = n_valid * tm
    np.testing.assert_array_equal(_as_numbers(got)[:n], _as_numbers(want)[:n])
    assert bool(n) == bool(_as_numbers(want)[:n].any())
    (dx, dwg, dwu), (dx0, dwg0, dwu0) = vjp(ct), vjp0(ct)
    np.testing.assert_array_equal(_as_numbers(dx)[:n], _as_numbers(dx0)[:n])
    for a, b in ((dwg, dwg0), (dwu, dwu0)):
        assert a.dtype == b.dtype and np.isfinite(_as_numbers(a)).all()
        np.testing.assert_array_equal(_as_numbers(a), _as_numbers(b))
    assert bool(n) == bool(_as_numbers(dwg).any())
    # an expert with no rows gets zeros
    for e, c in enumerate(GLU_CASES[case][0]):
        if c == 0:
            assert not _as_numbers(dwu)[e].any()


@pytest.mark.parametrize("case", ["held25", "held57", "no-rows", "n-tiled"])
def test_moe_gmm_glu_never_reads_a_dead_tile(case, monkeypatch):
    """NaN in every dead tile of the rows and of ``d_hidden`` (the forward
    left its own dead tiles unwritten: the interpreter fills them with NaN)
    reaches neither the live tiles of ``hidden`` and ``dxs`` nor a weight
    gradient."""
    from chainermn_tpu.ops.moe_gmm import moe_gmm_glu

    (xs, w_gate, w_up), ct, tile_expert, n_valid, tm, live = _glu_inputs(
        case, monkeypatch)
    n = n_valid * tm
    assert n < xs.shape[0]
    glu = lambda xs: jax.vjp(lambda *a: moe_gmm_glu(
        *a, tile_expert, n_valid, tm=tm, interpret=True), xs, w_gate, w_up)
    clean, vjp0 = glu(xs)
    dirty, vjp = glu(jnp.where(live, xs, jnp.nan))
    np.testing.assert_array_equal(_as_numbers(dirty)[:n],
                                  _as_numbers(clean)[:n])
    assert np.isfinite(_as_numbers(dirty)[:n]).all()
    got, want = vjp(jnp.where(live, ct, jnp.nan)), vjp0(ct)
    np.testing.assert_array_equal(_as_numbers(got[0])[:n],
                                  _as_numbers(want[0])[:n])
    for a, b in zip(got[1:], want[1:]):
        assert np.isfinite(_as_numbers(a)).all()
        np.testing.assert_array_equal(_as_numbers(a), _as_numbers(b))


def test_moe_gmm_glu_counts_a_forward_and_a_vjp_a_staged_layer(monkeypatch):
    """``moe/glu_products_fused`` (+ ``_vjp``): one a traced forward of a
    staged layer and one a traced backward of it, whatever the layers'
    shapes share — a tick's resident layer books none forward and
    differentiates through the staged path, whose forward its backward
    runs."""
    from chainermn_tpu import observability as obs
    from chainermn_tpu.observability import trace
    from chainermn_tpu.parallel import moe
    from chainermn_tpu.parallel.moe import moe_dropless

    cfg, p, x = _tick_layer("softmax", seed=3)
    # (a fresh function a reading: tracing is cached by the function)
    layers = lambda: lambda x: moe_dropless(moe_dropless(
        x, p, cfg, interpret=True)[0].astype(x.dtype), p, cfg,
        interpret=True)[0].sum()
    fused = lambda: {k: v for k, v in trace.get_tracer().counters().items()
                     if k.startswith("moe/glu_products_fused")}
    was = trace.get_tracer().enabled
    obs.enable()
    try:
        trace.get_tracer().reset()
        jax.make_jaxpr(layers())(x)               # resident: two other kernels
        assert fused() == {}
        both = {"moe/glu_products_fused": 2.0,
                "moe/glu_products_fused_vjp": 2.0}
        jax.make_jaxpr(jax.grad(layers()))(x)     # ... staged in the backward
        assert fused() == both
        monkeypatch.setattr(moe, "_rows_resident", lambda t, d, a: False)
        trace.get_tracer().reset()
        jax.make_jaxpr(layers())(x)
        assert fused() == {"moe/glu_products_fused": 2.0}
        trace.get_tracer().reset()
        jax.make_jaxpr(jax.grad(layers()))(x)
        assert fused() == both
    finally:
        trace.get_tracer().reset()
        if not was:
            obs.disable()


@pytest.mark.parametrize("router", ["softmax", "sigmoid_group"])
def test_moe_dropless_kernel_path_gradients_are_the_dense_loops(router):
    """Through the gates, the rows' gather, the three grouped products and
    the gather-combine: the kernel path's gradients (every parameter and the
    input) equal the dense fallback's, with and without a shared expert."""
    from chainermn_tpu.parallel.blocks import MoEConfig
    from chainermn_tpu.parallel.moe import moe_dropless

    shared = router == "sigmoid_group"
    cfg = MoEConfig(n_experts=8, top_k=2, n_group=2, topk_group=1,
                    routed_scaling_factor=1.5, held=(2, 4), router=router,
                    n_shared=int(shared))
    ks = jax.random.split(jax.random.PRNGKey(11), 8)
    d, f, t = 16, 24, 40
    p = {"router": jax.random.normal(ks[0], (d, 8)),
         "w_gate": jax.random.normal(ks[1], (4, d, f)) * d ** -0.5,
         "w_up": jax.random.normal(ks[2], (4, d, f)) * d ** -0.5,
         "w_down": jax.random.normal(ks[3], (4, f, d)) * f ** -0.5}
    if shared:
        p["router_bias"] = jnp.zeros((8,))
        p["shared"] = {"w_gate": jax.random.normal(ks[4], (d, f)) * 0.2,
                       "w_up": jax.random.normal(ks[5], (d, f)) * 0.2,
                       "w_down": jax.random.normal(ks[6], (f, d)) * 0.2}
    x = jax.random.normal(ks[7], (t, d))
    ct = jax.random.normal(jax.random.PRNGKey(12), (t, d))
    loss = lambda x, p, interpret: (moe_dropless(
        x, p, cfg, interpret=interpret)[0] * ct).sum()
    got = jax.grad(loss, (0, 1))(x, p, True)
    want = jax.grad(loss, (0, 1))(x, p, None)
    assert float(jnp.abs(want[1]["router"]).max()) > 0
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


# --------------------------------------------------------------------------
# a tick's expert layer takes its rows and gives its sum inside the grouped
# products (ISSUE 42): ``moe_gmm_rows`` + ``moe_gmm_sum`` where the layer's
# rows stay resident, against the staged path (gather, three ``moe_gmm``
# calls, gather-combine), interpret mode
# --------------------------------------------------------------------------

def _tick_layer(router="softmax", *, top_k=2, held=(2, 4), t=24, seed=0,
                dtype=jnp.bfloat16):
    from chainermn_tpu.parallel.blocks import MoEConfig

    cfg = MoEConfig(n_experts=8, top_k=top_k, n_group=2, topk_group=2,
                    routed_scaling_factor=1.5, held=held, router=router,
                    n_shared=0)
    d, f, n = 128, 256, held[1]
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    p = {"router": jax.random.normal(ks[0], (d, 8), dtype),
         "w_gate": (jax.random.normal(ks[1], (n, d, f)) * d ** -0.5
                    ).astype(dtype),
         "w_up": (jax.random.normal(ks[2], (n, d, f)) * d ** -0.5
                  ).astype(dtype),
         "w_down": (jax.random.normal(ks[3], (n, f, d)) * f ** -0.5
                    ).astype(dtype)}
    if router == "sigmoid_group":
        p["router_bias"] = jax.random.normal(ks[4], (8,)) * 0.1
    return cfg, p, jax.random.normal(ks[5], (t, d), dtype)


def _both_paths(monkeypatch, fn):
    """``fn()`` under the resident path, then under the staged one."""
    from chainermn_tpu.parallel import moe

    assert moe._rows_resident(24, 128, 24 * 4)
    got = fn()
    monkeypatch.setattr(moe, "_rows_resident", lambda t, d, a: False)
    return got, fn()


def _resident_router(monkeypatch, router):
    from chainermn_tpu.parallel.moe import moe_dropless

    cfg, p, x = _tick_layer(router)
    got, want = _both_paths(monkeypatch, lambda: moe_dropless(
        x, p, cfg, interpret=True))
    assert int(want[1][1]) > 0                  # some assignment is held
    for a, b in zip(got, want):                 # y, counts, idx: to the bit
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def _resident_free_rows(monkeypatch):
    from chainermn_tpu.parallel.moe import moe_dropless

    cfg, p, x = _tick_layer("softmax", seed=1)
    live = jnp.asarray(np.arange(24) % 3 != 1)
    got, want = _both_paths(monkeypatch, lambda: moe_dropless(
        x, p, cfg, live=live, interpret=True))
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
    idle = ~np.asarray(live)
    assert not np.asarray(got[0], np.float32)[idle].any()
    assert (np.asarray(got[2])[idle] == cfg.n_experts).all()


def _resident_nothing_held(monkeypatch):
    from chainermn_tpu.parallel.moe import moe_dropless

    cfg, p, x = _tick_layer("softmax", seed=2)
    y, counts, _ = moe_dropless(x, p, cfg, live=jnp.zeros((24,), bool),
                                interpret=True)     # n_valid == 0
    assert not np.asarray(y, np.float32).any()
    assert not np.asarray(counts).any()


def _resident_three_held(monkeypatch):
    """Every choice held: a token's four rows are summed by expert, where
    the staged path sums them by choice — float32 rounding apart, no more."""
    from chainermn_tpu.parallel import moe

    cfg, p, x = _tick_layer("softmax", top_k=4, held=(0, 8), seed=3)
    idx, gates = moe.softmax_topk_route(x, p["router"], cfg)
    got, want = _both_paths(monkeypatch, lambda: moe._held_experts_product(
        x, p, idx, gates, 0, 8, True, True))
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))
    assert int(got[1].sum()) == 24 * 4
    y, y_staged = np.asarray(got[0]), np.asarray(want[0])
    assert y.dtype == np.float32
    np.testing.assert_allclose(y, y_staged, rtol=0,
                               atol=4 * np.finfo(np.float32).eps
                               * np.abs(y_staged).max())


def _resident_dead_rows_hold_nan(monkeypatch):
    """``hidden``'s rows in dead tiles are never written and a live tile's
    padding rows belong to no token: a NaN in either reaches no row of
    ``y`` — and ``x``'s own NaN row reaches only the token that has it."""
    from chainermn_tpu.ops.moe_gmm import moe_gmm_rows, moe_gmm_sum

    tm, t, d, f = 8, 6, 128, 128
    tile_expert, n_valid, m, live = _grouped((3, 0, 9), tm, 2)
    rng = np.random.default_rng(5)
    row_token = np.where(live, rng.integers(0, t - 1, m), t).astype(np.int32)
    row_gate = np.where(live, rng.random(m), 0.0).astype(np.float32)
    w = jnp.asarray(rng.normal(size=(3, f, d)) * 0.1, jnp.bfloat16)
    hidden = jnp.asarray(rng.normal(size=(m, f)), jnp.bfloat16)
    dirty = jnp.where(jnp.asarray(live)[:, None], hidden, jnp.nan)
    y, y_dirty = (np.asarray(moe_gmm_sum(
        h, w, row_gate, row_token, tile_expert, n_valid, n_tokens=t, tm=tm,
        interpret=True)) for h in (hidden, dirty))
    assert np.isfinite(y_dirty).all()
    np.testing.assert_array_equal(y, y_dirty)
    rows = np.asarray(hidden, np.float32)
    want = np.zeros((t, d), np.float32)
    for r in np.flatnonzero(live):
        e = tile_expert[r // tm]
        prod = jnp.dot(hidden[r], w[e], preferred_element_type=jnp.float32
                       ).astype(jnp.bfloat16).astype(jnp.float32)
        want[row_token[r]] += np.asarray(prod) * row_gate[r]
    np.testing.assert_allclose(y, want, rtol=1e-6, atol=1e-6)
    assert not y[t - 1].any() and rows.any()    # no row names the last token
    # the rows' side: token t-1 holds a NaN and no row names it
    x = jnp.asarray(rng.normal(size=(t, d)), jnp.bfloat16).at[t - 1].set(
        jnp.nan)
    wg = jnp.asarray(rng.normal(size=(3, d, f)) * 0.1, jnp.bfloat16)
    got = np.asarray(moe_gmm_rows(x, wg, wg, row_token, tile_expert, n_valid,
                                  tm=tm, interpret=True), np.float32)
    assert np.isfinite(got[:n_valid * tm]).all()
    assert not got[:n_valid * tm][~live[:n_valid * tm]].any()   # padding: 0


def _resident_gradients(monkeypatch):
    """A tick's size differentiates through the staged path."""
    from chainermn_tpu.parallel.moe import moe_dropless

    cfg, p, x = _tick_layer("sigmoid_group", seed=6, dtype=jnp.float32)
    ct = jax.random.normal(jax.random.PRNGKey(7), x.shape)
    loss = lambda x, p: (moe_dropless(x, p, cfg, interpret=True)[0]
                         * ct).sum()
    got, want = _both_paths(monkeypatch,
                            lambda: jax.grad(loss, (0, 1))(x, p))
    assert float(jnp.abs(want[1]["w_down"]).max()) > 0
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


RESIDENT_CASES = {
    "softmax": lambda mp: _resident_router(mp, "softmax"),
    "sigmoid_group": lambda mp: _resident_router(mp, "sigmoid_group"),
    "free-rows": _resident_free_rows,
    "nothing-held": _resident_nothing_held,
    "three-held-choices": _resident_three_held,
    "dead-rows-hold-nan": _resident_dead_rows_hold_nan,
    "gradients": _resident_gradients,
}


@pytest.mark.parametrize("case", sorted(RESIDENT_CASES))
def test_resident_expert_layer_is_the_staged_one(monkeypatch, case):
    RESIDENT_CASES[case](monkeypatch)


@pytest.mark.parametrize("name,t,d,top_k,resident", [
    # the three expert cells' ticks (slots, hidden size, choices a token)
    ("laguna-tick", 24, 2048, 8, True),
    ("kimi-tick", 64, 2304, 8, True),
    ("deepseek-tick", 64, 7168, 8, True),
    # ... every prefill bucket of theirs ...
    ("prefill-1024", 1024, 2048, 8, False),
    ("prefill-2048", 2048, 2304, 8, False),
    ("prefill-3072", 3072, 7168, 8, False),
    # ... a prompt short enough by its assignments, too wide by its bytes ...
    ("256-rows-of-7168", 256, 7168, 8, False),
    # ... and Mellum's train step (2 x 8192 tokens)
    ("mellum2-train-step", 16384, 2304, 8, False),
])
def test_rows_resident_is_a_ticks_size(name, t, d, top_k, resident):
    from chainermn_tpu.parallel.moe import _row_chunk, _row_tile, \
        _rows_resident

    assert _rows_resident(t, d, t * top_k) is resident
    if resident:    # the tick's row tile, no chunked backward
        assert _row_tile(t * top_k) == 32
        assert _row_chunk(t * top_k, 32) is None
