"""Collective-matmul overlap primitive tests.

Beyond-reference (the reference's only comm/compute overlap was the
double-buffered allreduce): ring-decomposed ``all_gather@matmul`` and
``matmul@reduce_scatter`` must equal their unfused two-op forms — values
AND gradients (the unrolled ring's autodiff is the transposed ring).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

import chainermn_tpu as mn
from chainermn_tpu.parallel import (
    make_all_gather_matmul,
    make_matmul_reduce_scatter,
)

SIZE = 8
S, D, F = 32, 16, 24  # gathered rows, contraction, output features


@pytest.fixture(scope="module")
def mesh(devices):
    return mn.make_mesh(devices)


class TestAllGatherMatmul:
    def test_matches_unfused(self, mesh):
        rng = np.random.RandomState(0)
        x = rng.randn(S, D).astype(np.float32)       # row-sharded input
        w = rng.randn(D, F).astype(np.float32)       # column-sharded weight
        got = np.asarray(make_all_gather_matmul(mesh)(x, w))
        np.testing.assert_allclose(got, x @ w, rtol=1e-5, atol=1e-5)

    def test_gradients_match_unfused(self, mesh):
        rng = np.random.RandomState(1)
        x = rng.randn(S, D).astype(np.float32)
        w = rng.randn(D, F).astype(np.float32)
        fn = make_all_gather_matmul(mesh)

        def loss(x, w):
            return (fn(x, w) ** 2).sum()

        gx, gw = jax.grad(loss, argnums=(0, 1))(x, w)
        wx, ww = jax.grad(lambda x, w: ((x @ w) ** 2).sum(),
                          argnums=(0, 1))(x, w)
        np.testing.assert_allclose(np.asarray(gx), np.asarray(wx),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(gw), np.asarray(ww),
                                   rtol=1e-4, atol=1e-4)

    def test_row_order_is_global(self, mesh):
        """Chunk deposit indices must reconstruct the GLOBAL row order —
        a distinguishable pattern catches any ring-index bookkeeping slip."""
        x = np.arange(S, dtype=np.float32)[:, None] * np.ones((1, D), np.float32)
        w = np.eye(D, F).astype(np.float32)
        got = np.asarray(make_all_gather_matmul(mesh)(x, w))
        np.testing.assert_allclose(got[:, 0], np.arange(S, dtype=np.float32))


class TestMatmulReduceScatter:
    def test_matches_unfused(self, mesh):
        rng = np.random.RandomState(2)
        x = rng.randn(S, D * SIZE).astype(np.float32)  # contraction-sharded
        w = rng.randn(D * SIZE, F).astype(np.float32)
        got = np.asarray(make_matmul_reduce_scatter(mesh)(x, w))
        np.testing.assert_allclose(got, x @ w, rtol=1e-4, atol=1e-4)

    def test_gradients_match_unfused(self, mesh):
        rng = np.random.RandomState(3)
        x = rng.randn(S, D * SIZE).astype(np.float32)
        w = rng.randn(D * SIZE, F).astype(np.float32)
        fn = make_matmul_reduce_scatter(mesh)

        def loss(x, w):
            return (fn(x, w) ** 2).sum()

        gx, gw = jax.grad(loss, argnums=(0, 1))(x, w)
        wx, ww = jax.grad(lambda x, w: ((x @ w) ** 2).sum(),
                          argnums=(0, 1))(x, w)
        np.testing.assert_allclose(np.asarray(gx), np.asarray(wx),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(gw), np.asarray(ww),
                                   rtol=1e-4, atol=1e-4)

    def test_indivisible_rows_error(self, mesh):
        x = np.zeros((S + 1, D * SIZE), np.float32)
        w = np.zeros((D * SIZE, F), np.float32)
        with pytest.raises(ValueError, match="divisible"):
            make_matmul_reduce_scatter(mesh)(x, w)


class TestComposition:
    def test_megatron_sp_mlp_roundtrip(self, mesh):
        """AG-matmul into RS-matmul is the Megatron-SP MLP wiring: x enters
        sequence-sharded and leaves sequence-sharded, weights stay
        TP-sharded, with NO standalone all_gather/psum in between."""
        rng = np.random.RandomState(4)
        x = rng.randn(S, D).astype(np.float32)
        w1 = rng.randn(D, F * SIZE).astype(np.float32)  # columns sharded
        w2 = rng.randn(F * SIZE, D).astype(np.float32)  # rows sharded

        def spmd(x_loc, w1_loc, w2_loc):
            from chainermn_tpu.parallel import (all_gather_matmul,
                                                matmul_reduce_scatter)

            h = all_gather_matmul(x_loc, w1_loc, axis_name="mn")
            h = jnp.tanh(h)
            return matmul_reduce_scatter(h, w2_loc, axis_name="mn")

        fn = jax.jit(shard_map(
            spmd, mesh=mesh,
            in_specs=(P("mn"), P(None, "mn"), P("mn")),
            out_specs=P("mn")))
        got = np.asarray(fn(x, w1, w2))
        want = np.tanh(x @ w1) @ w2
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


class TestMegatronSPBlocks:
    """The wired-in Megatron-SP layers (round-3: collective_matmul finally
    has model call sites): sequence-sharded tp_mlp_sp / tp_attention_sp /
    tp_block_sp must match the replicated-activation tp_* oracles on the
    gathered sequence, values and gradients."""

    B, SEQ, D_MODEL, HEADS = 2, 32, 32, 8  # heads divisible by the 8-way axis

    def _params(self, rng):
        from chainermn_tpu.parallel import init_tp_transformer_lm

        full = init_tp_transformer_lm(
            jax.random.PRNGKey(7), vocab=64, d_model=self.D_MODEL,
            n_heads=self.HEADS, n_layers=1, max_len=self.SEQ)
        return full["blocks"][0]

    def _shard_specs(self):
        from chainermn_tpu.parallel import transformer_lm_specs
        from chainermn_tpu.parallel import init_tp_transformer_lm

        full = init_tp_transformer_lm(
            jax.random.PRNGKey(7), vocab=64, d_model=self.D_MODEL,
            n_heads=self.HEADS, n_layers=1, max_len=self.SEQ)
        return transformer_lm_specs(full, "mn")["blocks"][0]

    def test_block_sp_matches_replicated_block(self, mesh):
        from chainermn_tpu.parallel import tp_block, tp_block_sp

        blk = self._params(np.random.RandomState(0))
        specs = self._shard_specs()
        x = np.random.RandomState(1).randn(
            self.B, self.SEQ, self.D_MODEL).astype(np.float32)
        hd = self.D_MODEL // self.HEADS

        ref_fn = jax.jit(shard_map(
            lambda xx, bb: tp_block(xx, bb, head_dim=hd, axis_name="mn",
                                    causal=True, attn_impl="xla"),
            mesh=mesh, in_specs=(P(), specs), out_specs=P()))
        sp_fn = jax.jit(shard_map(
            lambda xx, bb: tp_block_sp(xx, bb, head_dim=hd, axis_name="mn",
                                       causal=True, attn_impl="xla"),
            mesh=mesh, in_specs=(P(None, "mn"), specs),
            out_specs=P(None, "mn")))
        want = np.asarray(ref_fn(x, blk))
        got = np.asarray(sp_fn(x, blk))
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)

    def test_block_sp_gradients_match(self, mesh):
        from chainermn_tpu.parallel import tp_block, tp_block_sp

        blk = self._params(np.random.RandomState(2))
        specs = self._shard_specs()
        x = np.random.RandomState(3).randn(
            self.B, self.SEQ, self.D_MODEL).astype(np.float32)
        hd = self.D_MODEL // self.HEADS

        def loss_of(block_fn, in_spec):
            def spmd(xx, bb):
                y = block_fn(xx, bb, head_dim=hd, axis_name="mn",
                             causal=True, attn_impl="xla")
                return jax.lax.psum(jnp.sum(y ** 2), "mn") if in_spec else \
                    jnp.sum(y ** 2)
            if in_spec:  # sequence-sharded input: local sums need a psum
                return jax.jit(shard_map(
                    jax.grad(spmd, argnums=1), mesh=mesh,
                    in_specs=(P(None, "mn"), specs), out_specs=specs))
            return jax.jit(shard_map(
                jax.grad(spmd, argnums=1), mesh=mesh,
                in_specs=(P(), specs), out_specs=specs))

        g_ref = loss_of(tp_block, False)(x, blk)
        g_sp = loss_of(tp_block_sp, True)(x, blk)
        flat_r, _ = jax.tree_util.tree_flatten(g_ref)
        flat_s, _ = jax.tree_util.tree_flatten(g_sp)
        for a, b in zip(flat_s, flat_r):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=5e-3, atol=5e-4)

    def test_mlp_sp_matches_mlp(self, mesh):
        from chainermn_tpu.parallel import tp_mlp, tp_mlp_sp

        blk = self._params(np.random.RandomState(4))["mlp"]
        x = np.random.RandomState(5).randn(
            self.B, self.SEQ, self.D_MODEL).astype(np.float32)
        mlp_specs = {"wi": P(None, "mn"), "bi": P("mn"),
                     "wo": P("mn", None), "bo": P()}
        ref = jax.jit(shard_map(
            lambda xx, bb: tp_mlp(xx, bb, axis_name="mn"),
            mesh=mesh, in_specs=(P(), mlp_specs), out_specs=P()))
        sp = jax.jit(shard_map(
            lambda xx, bb: tp_mlp_sp(xx, bb, axis_name="mn"),
            mesh=mesh, in_specs=(P(None, "mn"), mlp_specs),
            out_specs=P(None, "mn")))
        np.testing.assert_allclose(np.asarray(sp(x, blk)),
                                   np.asarray(ref(x, blk)),
                                   rtol=2e-4, atol=2e-4)

    def test_attention_sp_gqa_layout(self, mesh):
        """The wq/wkv GQA projection branch of tp_attention_sp: 16 q heads
        sharing 8 KV heads (the KV count must stay divisible by the 8-way
        mesh axis)."""
        from chainermn_tpu.parallel import (init_tp_transformer_lm,
                                            tp_attention, tp_attention_sp,
                                            transformer_lm_specs)

        full = init_tp_transformer_lm(
            jax.random.PRNGKey(9), vocab=64, d_model=self.D_MODEL,
            n_heads=16, n_layers=1, max_len=self.SEQ, n_kv_heads=8)
        blk = full["blocks"][0]["attn"]
        specs = transformer_lm_specs(full, "mn")["blocks"][0]["attn"]
        hd = self.D_MODEL // 16
        x = np.random.RandomState(6).randn(
            self.B, self.SEQ, self.D_MODEL).astype(np.float32)
        ref = jax.jit(shard_map(
            lambda xx, bb: tp_attention(xx, bb, head_dim=hd, axis_name="mn",
                                        causal=True, attn_impl="xla"),
            mesh=mesh, in_specs=(P(), specs), out_specs=P()))
        sp = jax.jit(shard_map(
            lambda xx, bb: tp_attention_sp(xx, bb, head_dim=hd,
                                           axis_name="mn", causal=True,
                                           attn_impl="xla"),
            mesh=mesh, in_specs=(P(None, "mn"), specs),
            out_specs=P(None, "mn")))
        np.testing.assert_allclose(np.asarray(sp(x, blk)),
                                   np.asarray(ref(x, blk)),
                                   rtol=2e-4, atol=2e-4)
