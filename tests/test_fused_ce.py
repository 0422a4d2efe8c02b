"""Fused softmax-cross-entropy kernel tests (Pallas interpret on CPU).

Oracle: the materializing logsumexp form.  Values and gradients, the
single-shard API and the vocab-parallel composition over the 8-device
mesh (global-LSE backward through the pmax/psum combine).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

import chainermn_tpu as mn
from chainermn_tpu.ops import fused_ce
from chainermn_tpu.ops.fused_ce import fused_cross_entropy

T, D, V = 64, 32, 256


def data(seed=0):
    rs = np.random.RandomState(seed)
    return (jnp.asarray(rs.randn(T, D).astype(np.float32)),
            jnp.asarray(rs.randn(V, D).astype(np.float32)),
            jnp.asarray(rs.randint(0, V, (T,)).astype(np.int32)))


def oracle_nll(h, tab, tgt):
    logits = h @ tab.T
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    return lse - jnp.take_along_axis(logits, tgt[:, None], 1)[:, 0]


class TestSingleShard:
    @pytest.mark.parametrize("bt,bv", [(16, 64), (32, 32), (64, 256)])
    def test_forward_matches_oracle(self, bt, bv):
        h, tab, tgt = data()
        got = fused_cross_entropy(h, tab, tgt, bt, bv)
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(oracle_nll(h, tab, tgt)),
                                   rtol=1e-5, atol=1e-5)

    def test_gradients_match_oracle(self):
        h, tab, tgt = data(seed=1)

        def lf(h, tab):
            return jnp.sum(jnp.sin(fused_cross_entropy(h, tab, tgt, 16, 64)))

        def lo(h, tab):
            return jnp.sum(jnp.sin(oracle_nll(h, tab, tgt)))

        gf = jax.grad(lf, argnums=(0, 1))(h, tab)
        go = jax.grad(lo, argnums=(0, 1))(h, tab)
        for name, a, b in zip(("dh", "dtable"), gf, go):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-5,
                                       err_msg=f"grad wrt {name}")

    @pytest.mark.parametrize("mode", ["plain", "tpu"])
    @pytest.mark.parametrize("nv", [1, 3])
    @pytest.mark.parametrize("nt,resident,d,dtype", [
        # all rows resident in VMEM
        (1, None, 128, "float32"), (2, None, 128, "float32"),
        (3, None, 128, "bfloat16"), (5, None, 128, "float32"),
        # rows cut into super-blocks: dtable carried in HBM
        (5, 2, 128, "float32"), (6, 1, 128, "bfloat16"),
        (8, 3, 128, "float32"), (9, 4, 128, "float32"),
        # wide enough to outgrow the default scoped VMEM
        (6, 1, 8192, "float32")])
    def test_one_backward_kernel_matches_the_emulation(
            self, monkeypatch, nt, resident, d, dtype, nv, mode):
        """``fused_ce_grads`` against ``_grads_xla`` over (T blocks, V
        blocks): all rows resident, and rows cut into super-blocks whose
        ``dtable`` is carried in HBM (``resident`` = the T blocks of ``dh``
        the VMEM budget is set to hold) — 5 row blocks admit no cut into
        super-blocks of three or more, so they stay whole: the floor the
        revisit invariant sets.  Target ids outside the shard's range match
        nothing (the vocab-parallel caller's masking); ``dnll`` is not
        uniform.  ``tpu``: the interpreter that keeps an aliased input and
        its result in ONE buffer, as the chip does."""
        from jax.experimental.pallas import tpu as pltpu

        bt, bv = 8, 128
        t, v = nt * bt, nv * bv
        rs = np.random.RandomState(nt * 10 + nv)
        h = jnp.asarray(rs.randn(t, d) * 0.1, dtype)
        tab = jnp.asarray(rs.randn(v, d) * 0.1, dtype)
        tgt = jnp.asarray(rs.randint(-v // 2, v + v // 2, (t,)), jnp.int32)
        assert int(((tgt < 0) | (tgt >= v)).sum()) > 0
        dnll = jnp.asarray(rs.rand(t) + 0.1, jnp.float32)
        if resident is not None:
            monkeypatch.setattr(fused_ce, "_DH_RESIDENT_BYTES",
                                resident * bt * d * 4)
        ni = fused_ce._super_block(nt, bt * d * 4)
        assert nt % ni == 0 and (ni == nt or ni >= 3)
        assert (ni < nt) == (resident is not None and nt != 5)
        asked = []
        params = fused_ce._compiler_params
        monkeypatch.setattr(
            fused_ce, "_compiler_params",
            lambda sem, nbytes: asked.append(nbytes) or params(sem, nbytes))
        interpret = pltpu.InterpretParams() if mode == "tpu" else True
        m, l, _ = fused_ce.ce_stats(h, tab, tgt, bt, bv)
        lse = m + jnp.log(l)
        dh, dtable = fused_ce.ce_grads(h, tab, tgt, lse, dnll, bt, bv,
                                       interpret)
        assert (asked[-1] > fused_ce._DEFAULT_VMEM_ROOM) == (d == 8192)
        dh_x, dtable_x = fused_ce._grads_xla(h, tab, tgt, lse, dnll)
        assert dh.dtype == h.dtype and dh.shape == h.shape
        assert dtable.dtype == tab.dtype and dtable.shape == tab.shape
        tol = dict(rtol=1e-4, atol=1e-5) if dtype == "float32" \
            else dict(rtol=2e-2, atol=1e-3)
        f32 = lambda x: np.asarray(x.astype(jnp.float32))
        np.testing.assert_allclose(f32(dh), f32(dh_x), **tol)
        np.testing.assert_allclose(f32(dtable), f32(dtable_x), **tol)

    def test_backward_asks_for_the_vmem_it_holds(self):
        """The limit follows the shapes: nothing asked where blocks and
        scratch fit the default, the real count plus the tile's room where
        they do not (``d`` sets every block's width)."""
        small = fused_ce._compiler_params(("arbitrary",), 2 << 20)
        assert small.vmem_limit_bytes is None
        wide = fused_ce._compiler_params(("arbitrary",), 80 << 20)
        assert wide.vmem_limit_bytes == (96 << 20)

    def test_small_row_count_uses_full_dim_block(self):
        """T smaller than the block is legal (full-dim blocks always are)."""
        h, tab, tgt = data()
        got = fused_cross_entropy(h[:13], tab, tgt[:13])
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(oracle_nll(h[:13], tab, tgt[:13])),
            rtol=1e-5, atol=1e-5)

    def test_unalignable_rows_raise(self):
        """T=258 (> block, 8 ∤ every divisor) has no Mosaic-legal tiling."""
        rs = np.random.RandomState(3)
        h = jnp.asarray(rs.randn(258, D).astype(np.float32))
        tab = jnp.asarray(rs.randn(V, D).astype(np.float32))
        tgt = jnp.asarray(rs.randint(0, V, (258,)).astype(np.int32))
        with pytest.raises(ValueError, match="Mosaic-aligned"):
            fused_cross_entropy(h, tab, tgt)


class TestVocabParallel:
    def test_loss_and_grads_match_unsharded_oracle(self, devices):
        """ce_impl='fused' over the 8-way vocab sharding: loss equals the
        xla path, gradients equal the UNSHARDED dense oracle (the shard_map
        conventions of the two impls differ under check_vma=False — the
        fused custom_vjp psums dh itself, mirroring inside-shard_map
        training use, so the oracle is the right yardstick)."""
        from chainermn_tpu.parallel.transformer import (
            vocab_parallel_logits_loss)

        mesh = mn.make_mesh(devices)
        rs = np.random.RandomState(2)
        b, s = 2, 32
        h = rs.randn(b, s, D).astype(np.float32)
        tab = rs.randn(V, D).astype(np.float32)
        tgt = rs.randint(0, V, (b, s)).astype(np.int32)

        def run(ce_impl):
            def spmd(hh, tt, gg):
                loss, grads = jax.value_and_grad(
                    lambda a, c: vocab_parallel_logits_loss(
                        a, c, gg, axis_name="mn", ce_impl=ce_impl),
                    argnums=(0, 1))(hh, tt)
                return loss, grads[0], grads[1]

            fn = jax.jit(shard_map(
                spmd, mesh=mesh, in_specs=(P(), P("mn"), P()),
                out_specs=(P(), P(), P("mn")), check_vma=False))
            return fn(h, tab, tgt)

        lx, _, _ = run("xla")
        lf, dhf, dtf = run("fused")

        def dense(hh, tt):
            nll = oracle_nll(hh.reshape(-1, D), tt, tgt.reshape(-1))
            return jnp.mean(nll)

        lo, (dho, dto) = jax.value_and_grad(dense, argnums=(0, 1))(
            jnp.asarray(h), jnp.asarray(tab))
        np.testing.assert_allclose(float(lf), float(lx), rtol=1e-6)
        np.testing.assert_allclose(float(lf), float(lo), rtol=1e-6)
        np.testing.assert_allclose(np.asarray(dhf), np.asarray(dho),
                                   rtol=1e-4, atol=1e-6)
        np.testing.assert_allclose(np.asarray(dtf), np.asarray(dto),
                                   rtol=1e-4, atol=1e-6)

    def test_bad_impl_name(self, devices):
        from chainermn_tpu.parallel.transformer import (
            vocab_parallel_logits_loss)

        mesh = mn.make_mesh(devices)
        h = np.zeros((1, 8, D), np.float32)
        tab = np.zeros((V, D), np.float32)
        tgt = np.zeros((1, 8), np.int32)
        with pytest.raises(ValueError, match="ce_impl"):
            jax.jit(shard_map(
                lambda a, b, c: vocab_parallel_logits_loss(
                    a, b, c, axis_name="mn", ce_impl="nope"),
                mesh=mesh, in_specs=(P(), P("mn"), P()),
                out_specs=P(), check_vma=False))(h, tab, tgt)

    def test_dp_tp_training_trajectory_matches_xla(self, devices):
        """3 training steps on a (2, 4) DP×TP mesh: ce_impl='fused' must
        reproduce the xla path's loss trajectory exactly (the pcast
        promotions route dtable's data-psum and dh's model-psum through
        the custom_vjp boundary)."""
        import optax

        from functools import partial
        from jax.sharding import NamedSharding
        from chainermn_tpu.parallel import (
            init_tp_transformer_lm, make_hybrid_shard_map_step, shard_pytree,
            state_specs_like, tp_transformer_lm_loss, transformer_lm_specs)

        vocab, d, heads, layers, seq, b = 64, 16, 4, 1, 16, 4
        mesh = mn.make_nd_mesh(("data", "model"), (2, 4))
        params = init_tp_transformer_lm(
            jax.random.PRNGKey(0), vocab, d, heads, layers, max_len=seq)
        params = jax.tree_util.tree_map(np.asarray, params)  # vs donation
        specs = transformer_lm_specs(params, "model")
        opt = optax.sgd(1e-2)
        out = {}
        for impl in ("xla", "fused"):
            loss_fn = partial(tp_transformer_lm_loss, head_dim=d // heads,
                              axis_name="model", attn_impl="xla",
                              ce_impl=impl)
            step = make_hybrid_shard_map_step(
                loss_fn, opt, mesh, params, specs, data_axis="data",
                batch_spec=P("data"))
            p = shard_pytree(params, mesh, specs)
            st = shard_pytree(opt.init(params), mesh,
                              state_specs_like(opt, params, specs))
            toks = np.random.RandomState(0).randint(
                0, vocab, (b, seq + 1)).astype(np.int32)
            batch = (jax.device_put(toks, NamedSharding(mesh, P("data"))),)
            losses = []
            for _ in range(3):
                p, st, loss, *_ = step(p, st, batch)
                losses.append(float(loss))
            out[impl] = losses
        np.testing.assert_allclose(out["fused"], out["xla"], rtol=1e-5)
