"""Flash-attention kernel tests (Pallas interpret mode on CPU).

The oracle is plain softmax attention; forward and gradients checked, plus
the Ulysses integration (``attn_impl='flash'``) on the 8-device mesh.  On
TPU the same code compiles via Mosaic — interpret mode runs identical math.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import chainermn_tpu as mn
from chainermn_tpu.ops import flash_attention
from chainermn_tpu.parallel import make_ulysses_attention

B, S, H, D = 2, 64, 4, 16


def reference(q, k, v, causal=False):
    d, seq = q.shape[-1], q.shape[1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / (d ** 0.5)
    if causal:
        mask = np.tril(np.ones((seq, seq), bool))
        s = jnp.where(mask[None, None], s, -1e30)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)


def qkv(seed=0, s=S):
    rng = np.random.RandomState(seed)
    return tuple(rng.randn(B, s, H, D).astype(np.float32) for _ in range(3))


class TestForward:
    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("block", [16, 32, 64])
    def test_matches_reference(self, causal, block):
        q, k, v = qkv()
        got = flash_attention(q, k, v, causal=causal,
                              block_q=block, block_k=block)
        want = reference(q, k, v, causal)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-5)

    def test_block_shrinks_to_divide_seq(self):
        q, k, v = qkv(s=48)  # 48 not divisible by 128 → picks 48
        got = flash_attention(q, k, v, block_q=128, block_k=128)
        want = reference(q, k, v)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-5)

    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("s", [97, 130])  # prime / small-factor lengths
    def test_pad_and_mask_awkward_seq_len(self, causal, s):
        """S with tiny divisors pads up to the block and masks the tail
        instead of degrading to Mosaic-hostile size-1 blocks."""
        q, k, v = qkv(s=s)
        got = flash_attention(q, k, v, causal=causal, block_q=32, block_k=32)
        assert got.shape == q.shape
        want = reference(q, k, v, causal)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-5)

    @pytest.mark.parametrize("backward", ["pallas", "xla"])
    def test_pad_and_mask_gradients(self, backward):
        q, k, v = qkv(s=97)

        def loss_flash(q, k, v):
            return (flash_attention(q, k, v, causal=True, block_q=32,
                                    block_k=32, backward=backward) ** 2).sum()

        def loss_ref(q, k, v):
            return (reference(q, k, v, causal=True) ** 2).sum()

        got = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        want = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for g, w in zip(got, want):
            assert np.all(np.isfinite(np.asarray(g)))
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=2e-3, atol=2e-4)

    def test_bf16(self):
        q, k, v = (jnp.asarray(x, jnp.bfloat16) for x in qkv(seed=1))
        got = flash_attention(q, k, v, block_q=32, block_k=32)
        assert got.dtype == jnp.bfloat16
        want = reference(np.float32(q), np.float32(k), np.float32(v))
        np.testing.assert_allclose(np.float32(got), np.asarray(want),
                                   rtol=0.1, atol=0.05)


class TestBackward:
    @pytest.mark.parametrize("backward", ["pallas", "xla"])
    @pytest.mark.parametrize("causal", [False, True])
    def test_gradients_match_reference(self, causal, backward):
        q, k, v = qkv(seed=2)

        def floss(q, k, v):
            return (flash_attention(q, k, v, causal=causal, block_q=16,
                                    block_k=16, backward=backward) ** 2).sum()

        def rloss(q, k, v):
            return (reference(q, k, v, causal) ** 2).sum()

        got = jax.grad(floss, argnums=(0, 1, 2))(q, k, v)
        want = jax.grad(rloss, argnums=(0, 1, 2))(q, k, v)
        for g, w, name in zip(got, want, "qkv"):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=5e-4, atol=5e-5,
                                       err_msg=f"grad wrt {name}")

    def test_pallas_matches_xla_backward_with_lse_cotangent(self):
        """The two backends must agree when gradients also flow through the
        LSE output (ring attention's block-merge weights)."""
        q, k, v = qkv(seed=4)

        def loss(backward):
            def f(q, k, v):
                o, lse = flash_attention(q, k, v, causal=True,
                                         return_lse=True, backward=backward)
                return (o ** 2).sum() + jnp.sin(lse).sum()
            return jax.grad(f, argnums=(0, 1, 2))(q, k, v)

        for g, w, name in zip(loss("pallas"), loss("xla"), "qkv"):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=1e-5, atol=1e-6,
                                       err_msg=f"grad wrt {name}")

    def test_bad_backward_name_raises(self):
        q, k, v = qkv()
        with pytest.raises(ValueError, match="backward"):
            jax.grad(lambda q: flash_attention(
                q, k, v, backward="nope").sum())(q)


def qkv8(seed=0):
    """8 heads — Ulysses needs heads divisible by the 8-device axis."""
    rng = np.random.RandomState(seed)
    return tuple(rng.randn(B, S, 8, D).astype(np.float32) for _ in range(3))


class TestUlyssesFlash:
    def test_sequence_parallel_flash(self, devices):
        """Ulysses(all_to_all) + flash local attention == full attention,
        across the 8-device mesh, forward and grad."""
        mesh = mn.make_mesh(devices)
        q, k, v = qkv8(seed=3)
        fn = make_ulysses_attention(mesh=mesh, causal=True, attn_impl="flash")
        got = np.asarray(fn(q, k, v))
        want = np.asarray(reference(q, k, v, causal=True))
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)

        g = jax.grad(lambda q: (fn(q, k, v) ** 2).sum())(q)
        w = jax.grad(lambda q: (reference(q, k, v, True) ** 2).sum())(q)
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=5e-4, atol=5e-5)

    def test_bad_impl_name(self, devices):
        mesh = mn.make_mesh(devices)
        q, k, v = qkv8()
        with pytest.raises(ValueError, match="attn_impl"):
            make_ulysses_attention(mesh=mesh, attn_impl="nope")(q, k, v)


class TestGQA:
    """Grouped-query attention: fewer KV heads than Q heads, shared via the
    kernel's block index map (forward) / repeat+fold (backward)."""

    def _reference_gqa(self, q, k, v, causal=False):
        group = q.shape[2] // k.shape[2]
        kf = jnp.repeat(k, group, axis=2)
        vf = jnp.repeat(v, group, axis=2)
        return reference(q, jnp.asarray(kf), jnp.asarray(vf), causal)

    @pytest.mark.parametrize("h_kv", [1, 2])  # MQA and 2-group GQA
    @pytest.mark.parametrize("causal", [False, True])
    def test_forward_matches_reference(self, h_kv, causal):
        rng = np.random.RandomState(0)
        q = rng.randn(B, S, H, D).astype(np.float32)
        k = rng.randn(B, S, h_kv, D).astype(np.float32)
        v = rng.randn(B, S, h_kv, D).astype(np.float32)
        got = flash_attention(q, k, v, causal=causal, block_q=32, block_k=32)
        want = self._reference_gqa(q, k, v, causal)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-5)

    @pytest.mark.parametrize("backward", ["pallas", "xla"])
    def test_gradients_match_reference(self, backward):
        rng = np.random.RandomState(1)
        q = rng.randn(B, S, H, D).astype(np.float32)
        k = rng.randn(B, S, 2, D).astype(np.float32)
        v = rng.randn(B, S, 2, D).astype(np.float32)

        def loss_flash(q, k, v):
            return (flash_attention(q, k, v, causal=True, block_q=32,
                                    block_k=32, backward=backward) ** 2).sum()

        def loss_ref(q, k, v):
            return (self._reference_gqa(q, k, v, causal=True) ** 2).sum()

        got = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        want = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for g, w, name in zip(got, want, "qkv"):
            assert g.shape == w.shape, name  # dk/dv folded back to h_kv heads
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=2e-3, atol=2e-4,
                                       err_msg=f"grad wrt {name}")

    def test_lse_path_with_gqa(self):
        rng = np.random.RandomState(2)
        q = rng.randn(B, S, H, D).astype(np.float32)
        k = rng.randn(B, S, 1, D).astype(np.float32)
        v = rng.randn(B, S, 1, D).astype(np.float32)
        out, lse = flash_attention(q, k, v, return_lse=True)
        assert lse.shape == (B, H, S)  # LSE per Q head, not per KV head
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(self._reference_gqa(q, k, v)),
                                   rtol=2e-4, atol=2e-5)

    def test_bad_head_ratio_raises(self):
        q, k, v = qkv()
        with pytest.raises(ValueError, match="multiple"):
            flash_attention(q, k[:, :, :3], v[:, :, :3])


class TestBf16PartialPrecision:
    """bf16 inputs route the fused backward's dq partials through a bf16
    slab (each of the nk per-K-block partials rounds once before the fp32
    sum).  The error budget is bf16-grade, not fp32-grade — this pins it."""

    def test_bf16_gradients_match_xla_backward(self):
        rs = np.random.RandomState(7)
        S = 512  # several K blocks at block_k=128 -> a multi-partial sum
        q = jnp.asarray(rs.randn(2, S, 4, 64), jnp.bfloat16)
        k = jnp.asarray(rs.randn(2, S, 4, 64), jnp.bfloat16)
        v = jnp.asarray(rs.randn(2, S, 4, 64), jnp.bfloat16)

        def grads(backward):
            def loss(q, k, v):
                o = flash_attention(q, k, v, causal=True, block_q=128,
                                    block_k=128, backward=backward)
                return (o.astype(jnp.float32) ** 2).sum()
            return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

        got = grads("pallas")
        want = grads("xla")
        for g, w, name in zip(got, want, "qkv"):
            g = np.asarray(g, np.float32)
            w = np.asarray(w, np.float32)
            rel = np.max(np.abs(g - w)) / (np.max(np.abs(w)) + 1e-9)
            # bf16 grade: one bf16 rounding per partial (~2^-8 relative)
            assert rel < 2e-2, (name, rel)


class TestLaneBlockPicker:
    """Round-4 advisor finding: the backward q-block must be a 128-multiple
    for compiled Mosaic's LSE row slices, and the plain 8-aligned pick
    returned non-lane divisors (320 for S=640/1280), silently dropping
    those shapes to the XLA scan."""

    def test_prefers_lane_multiple_divisors(self):
        from chainermn_tpu.ops.flash_attention import _pick_lane_block
        assert _pick_lane_block(640, 512) == 128    # 320 is 8- not 128-aligned
        assert _pick_lane_block(1280, 512) == 256
        assert _pick_lane_block(8192, 512) == 512
        assert _pick_lane_block(2048, 2048) == 2048
        # no 128-multiple divisor ≤ budget → falls back to the 8-aligned
        # pick (dispatch then routes to the XLA scan)
        assert _pick_lane_block(200, 512) % 128 != 0

    def test_s640_parity_on_pallas_route(self):
        # S=640 now picks bwd_bq=128: verify backward parity at that block.
        q, k, v = qkv(s=640)
        def loss(f):
            return lambda t: (f(t, k, v) ** 2).sum()
        g_pallas = jax.grad(loss(lambda *a: flash_attention(
            *a, causal=True, backward="pallas", bwd_block_q=128)))(q)
        g_xla = jax.grad(loss(lambda *a: flash_attention(
            *a, causal=True, backward="xla")))(q)
        np.testing.assert_allclose(np.asarray(g_pallas), np.asarray(g_xla),
                                   rtol=2e-4, atol=2e-4)


def _module():
    """The MODULE (``chainermn_tpu.ops`` re-exports the function under the
    same name, which shadows the attribute)."""
    import sys

    return sys.modules["chainermn_tpu.ops.flash_attention"]


def _qkv_default(s, d=64, h=2, h_kv=None, seed=5, scale=1.0):
    rng = np.random.RandomState(seed)
    q = (rng.randn(1, s, h, d) * scale).astype(np.float32)
    k, v = ((rng.randn(1, s, h_kv or h, d) * scale).astype(np.float32)
            for _ in range(2))
    return q, k, v


def _assert_grads_match(q, k, v, ref_fn, rtol, atol, **flash_kw):
    """d/d(q, k, v) of sum(out²): ``flash_attention(**flash_kw)`` against
    the materialising ``ref_fn``."""
    got = jax.grad(lambda *a: (flash_attention(*a, **flash_kw) ** 2).sum(),
                   argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: (ref_fn(*a) ** 2).sum(),
                    argnums=(0, 1, 2))(q, k, v)
    for g, w, name in zip(got, want, "qkv"):
        assert g.shape == w.shape, name
        assert np.all(np.isfinite(np.asarray(g))), name
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=rtol,
                                   atol=atol, err_msg=f"grad wrt {name}")


class TestSubBlockSchedule:
    """The DEFAULT blocks (no explicit ``block_*``), where a grid cell is
    wider than a sub-block: the kernels walk it by sub-block, skip the
    pairs above the diagonal, run the pairs below it unmasked and mask only
    those the diagonal (or a padded tail) crosses."""

    @pytest.mark.parametrize("s", [256, 768, 1024])
    def test_causal_forward_at_default_blocks(self, s):
        q, k, v = _qkv_default(s)
        got = flash_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(reference(q, k, v, True)),
                                   rtol=2e-4, atol=2e-5)

    @pytest.mark.parametrize("backward", ["pallas", "xla"])
    @pytest.mark.parametrize("s", [256, 768, 1024])
    def test_causal_gradients_at_default_blocks(self, s, backward):
        q, k, v = _qkv_default(s)
        _assert_grads_match(q, k, v, lambda *a: reference(*a, True), 5e-4,
                            5e-5, causal=True, backward=backward)

    @pytest.mark.parametrize("backward", ["pallas", "xla"])
    def test_latent_prefill_widths(self, backward):
        """q/k 192 wide, v zero-padded from 128 to 192 (the MLA prefill's
        call, ``parallel/blocks.py``), S 1024."""
        q, k, v = _qkv_default(1024, d=192, scale=0.5)
        v[..., 128:] = 0.0
        out = flash_attention(q, k, v, causal=True)
        assert not np.asarray(out)[..., 128:].any()
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(reference(q, k, v, True)),
                                   rtol=2e-4, atol=2e-5)
        _assert_grads_match(q, k, v, lambda *a: reference(*a, True), 5e-4,
                            5e-5, causal=True, backward=backward)

    @pytest.mark.parametrize("backward", ["pallas", "xla"])
    @pytest.mark.parametrize("causal", [False, True])
    def test_padded_tail_at_default_blocks(self, causal, backward):
        """S 1000 pads to 1024: the tail mask stays on the sub-blocks that
        hold the tail, and only there."""
        q, k, v = _qkv_default(1000)
        got = flash_attention(q, k, v, causal=causal)
        assert got.shape == q.shape
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(reference(q, k, v, causal)),
                                   rtol=2e-4, atol=2e-5)
        _assert_grads_match(q, k, v, lambda *a: reference(*a, causal), 2e-3,
                            2e-4, causal=causal, backward=backward)

    @pytest.mark.parametrize("backward", ["pallas", "xla"])
    def test_gqa_group_2_at_default_blocks(self, backward):
        q, k, v = _qkv_default(1024, h=4, h_kv=2)
        ref = TestGQA()._reference_gqa
        np.testing.assert_allclose(
            np.asarray(flash_attention(q, k, v, causal=True)),
            np.asarray(ref(q, k, v, causal=True)), rtol=2e-4, atol=2e-5)
        _assert_grads_match(q, k, v, lambda *a: ref(*a, causal=True), 2e-3,
                            2e-4, causal=True, backward=backward)

    def test_return_lse_at_default_blocks(self):
        """The LSE output and the gradient through it (ring attention's
        merge weights), Pallas against the XLA oracle."""
        q, k, v = _qkv_default(1024)
        out, lse = flash_attention(q, k, v, causal=True, return_lse=True)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / 8.0
        s = jnp.where(np.tril(np.ones((1024, 1024), bool))[None, None],
                      s, -1e30)
        np.testing.assert_allclose(
            np.asarray(lse), np.asarray(jax.nn.logsumexp(s, -1)),
            rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(reference(q, k, v, True)),
                                   rtol=2e-4, atol=2e-5)

        def grads(backward):
            def f(q, k, v):
                o, l = flash_attention(q, k, v, causal=True,
                                       return_lse=True, backward=backward)
                return (o ** 2).sum() + jnp.sin(l).sum()
            return jax.grad(f, argnums=(0, 1, 2))(q, k, v)

        for g, w, name in zip(grads("pallas"), grads("xla"), "qkv"):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=1e-4, atol=1e-5,
                                       err_msg=f"grad wrt {name}")

    @pytest.mark.parametrize("backward", ["pallas", "xla"])
    def test_non_causal_at_default_blocks(self, backward):
        """``causal=False`` computes every sub-block, none masked, inside
        the tolerances the non-causal tests above hold."""
        q, k, v = _qkv_default(1024)
        np.testing.assert_allclose(np.asarray(flash_attention(q, k, v)),
                                   np.asarray(reference(q, k, v)),
                                   rtol=2e-4, atol=2e-5)
        _assert_grads_match(q, k, v, reference, 5e-4, 5e-5, backward=backward)

    # (s, block_q, block_k, causal, seq_len, edge) -> (run, masked, total)
    SCHEDULES = [
        ((1024, 512, 1024, True, None, 256), (10, 4, 16)),
        ((1024, 512, 1024, True, None, 128), (36, 8, 64)),
        # nothing to mask or skip: the cell is not divided (2 cells a head)
        ((1024, 512, 1024, False, None, 256), (2, 0, 2)),
        ((1024, 512, 1024, False, None, 128), (2, 0, 2)),
        # the grid's own skip still counts: 4 cells of 256, 3 run
        ((512, 256, 256, True, None, 256), (3, 2, 4)),
        # a block no lane multiple divides is one sub-block
        ((64, 32, 32, True, None, 256), (3, 2, 4)),
        # S 1000 padded to 1024: the last Q sub-block holds padded rows and
        # is masked throughout; the last K sub-block holds padded columns
        ((1024, 512, 1024, True, 1000, 256), (10, 7, 16)),
        ((1024, 512, 1024, False, 1000, 256), (16, 7, 16)),
        # the serving prefill's 768 bucket, blocks 384 x 768: 256 does not
        # divide 384, so Q goes in 128s against K in 256s
        ((768, 384, 768, True, None, 256), (12, 6, 18)),
        ((2048, 1024, 1024, True, None, 256), (36, 8, 64)),
    ]

    @pytest.mark.parametrize("args,want", SCHEDULES)
    def test_causal_schedule_table(self, args, want, monkeypatch):
        s, bq, bk, causal, seq_len, edge = args
        mod = _module()
        monkeypatch.setattr(mod, "_SUB_BLOCK", edge)
        got = mod.causal_schedule(s, bq, bk, causal, seq_len)
        assert (got["run"], got["masked"], got["total"]) == want
        assert got["total"] == (s // got["sub_q"]) * (s // got["sub_k"])
        # a plan says, for each Q sub-block of a cell, how many of the
        # cell's K sub-blocks it computes unmasked and masked
        assert all(len(plan) == bq // got["sub_q"] for plan in got["plans"])
        assert all(n_full + n_masked <= bk // got["sub_k"]
                   for plan in got["plans"] for n_full, n_masked in plan)

    def test_schedule_is_what_the_kernels_compute(self, monkeypatch):
        """A K sub-block the schedule skips is never read: poison every
        key and value above each Q sub-block's diagonal reach with NaN-free
        but enormous values at a row the mask would hide anyway — the
        result must not move (it would through a 0·inf if computed)."""
        mod = _module()
        s = 1024
        q, k, v = _qkv_default(s, h=1)
        base = np.asarray(flash_attention(q, k, v, causal=True))
        # rows 0..255 may only see keys 0..255: an inf in v beyond is
        # multiplied by an exact 0 (NaN) if its sub-block is computed
        v2 = v.copy()
        v2[:, 256:] = np.inf
        got = np.asarray(flash_attention(q, k, v2, causal=True))
        sub = mod.causal_schedule(s, 512, 1024)["sub_q"]
        assert sub <= 256
        np.testing.assert_array_equal(got[:, :256], base[:, :256])

    def test_counters_recorded_once_per_traced_call(self):
        from chainermn_tpu import observability as obs
        from chainermn_tpu.observability import trace

        mod = _module()
        # a head count no other test has: the kernels are traced once per
        # shape in a process (flash_attention's inner jit), and booked then
        q, k, v = (jnp.asarray(x) for x in _qkv_default(1024, h=3))
        sched = mod.causal_schedule(1024, 512, 1024)
        heads = q.shape[0] * q.shape[2]
        was = trace.get_tracer().enabled
        obs.enable()
        try:
            trace.get_tracer().reset()
            fwd = jax.jit(lambda q, k, v: flash_attention(q, k, v,
                                                          causal=True))
            fwd(q, k, v)
            want = {f"flash/score_blocks_{key}": float(heads * sched[key])
                    for key in ("run", "masked", "total")}
            counters = trace.get_tracer().counters()
            assert {n: counters[n] for n in want} == want
            fwd(q, k, v)        # a cache hit traces nothing, books nothing
            counters = trace.get_tracer().counters()
            assert {n: counters[n] for n in want} == want
            # nor does a second call site of the same shape (a model's
            # next layer): one trace, one booking
            jax.jit(lambda q, k, v: flash_attention(
                flash_attention(q, k, v, causal=True), k, v,
                causal=True))(q, k, v)
            counters = trace.get_tracer().counters()
            assert {n: counters[n] for n in want} == want
            # forward + backward
            trace.get_tracer().reset()
            jax.jit(jax.grad(lambda q: flash_attention(
                q, k, v, causal=True, backward="pallas").sum()))(q)
            counters = trace.get_tracer().counters()
            # whole traces only: the forward rule's and the backward's at
            # the least (the primal's too where jit traces it)
            traces, rest = divmod(counters["flash/score_blocks_total"],
                                  heads * sched["total"])
            assert rest == 0 and traces >= 2
            assert (counters["flash/score_blocks_run"]
                    == traces * heads * sched["run"])
            assert (counters["flash/score_blocks_masked"]
                    == traces * heads * sched["masked"])
        finally:
            trace.get_tracer().reset()
            if not was:
                obs.disable()


# --------------------------------------------------------------------------
# the band in the backward (ISSUE 38): window_flash_bwd and the XLA fallback
# --------------------------------------------------------------------------

def _banded_attention(q, k, v, window):
    """Explicit-mask attention: query ``t`` sees keys ``0 <= t - s <
    window``; GQA by repeating the KV heads."""
    s, d = q.shape[1], q.shape[-1]
    g = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / d ** 0.5
    dist = jnp.arange(s)[:, None] - jnp.arange(s)[None, :]
    seen = (dist >= 0) & (dist < window)
    p = jax.nn.softmax(jnp.where(seen, scores, -1e30), -1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


BAND_CASES = [
    # s, window, heads (q, kv), backward blocks (q, k)
    (512, 64, (2, 2), (256, 256)),      # W < the sub-block (128)
    (512, 128, (2, 1), (256, 256)),     # W = the sub-block
    (512, 256, (1, 1), (256, 256)),     # W = the block
    (256, 1000, (2, 2), (128, 128)),    # W > S: the causal kernel's work
    (200, 48, (2, 1), (128, 128)),      # S no block multiple: pad and tail
    (512, 200, (8, 1), (256, 512)),     # group 8, an edge off a sub-block
    (768, 130, (1, 1), (128, 256)),     # group 1, cells wholly off the band
]


@pytest.mark.parametrize("backward", ["pallas", "xla"])
@pytest.mark.parametrize("s,window,heads,blocks", BAND_CASES,
                         ids=lambda v: str(v).replace(" ", ""))
def test_banded_backward_is_the_masked_softmaxs(s, window, heads, blocks,
                                                backward):
    h, h_kv = heads
    keys = jax.random.split(jax.random.PRNGKey(s + window), 4)
    q = jax.random.normal(keys[0], (1, s, h, 16))
    k = jax.random.normal(keys[1], (1, s, h_kv, 16))
    v = jax.random.normal(keys[2], (1, s, h_kv, 16))
    ct = jax.random.normal(keys[3], (1, s, h, 16))
    got = jax.grad(lambda *a: (flash_attention(
        *a, causal=True, window=window, backward=backward,
        bwd_block_q=blocks[0], bwd_block_k=blocks[1]) * ct).sum(),
        (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: (_banded_attention(*a, window) * ct).sum(),
                    (0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)


def test_banded_backward_runs_the_bands_sub_blocks_and_books_them():
    """At S 8192, W 1024 (the training cell's sliding layers) the backward's
    schedule computes about a quarter of the causal triangle's sub-block
    pairs, and a window that covers the sequence degenerates to the causal
    schedule's work; a traced banded call books its own schedule."""
    from chainermn_tpu import observability as obs
    from chainermn_tpu.observability import trace

    mod = _module()
    band = mod.causal_schedule(8192, 512, 2048, True, None, 1024)
    full = mod.causal_schedule(8192, 512, 2048)
    assert full["total"] == 64 * 64 and full["run"] == 64 * 65 // 2
    # a row of Q sub-blocks: the diagonal's, eight behind it, less the
    # first rows' missing history
    assert band["run"] == 64 * 9 - 36
    assert band["run"] / full["run"] < 0.27
    wide = mod.causal_schedule(1024, 512, 1024, True, None, 4096)
    plain = mod.causal_schedule(1024, 512, 1024)
    assert (wide["run"], wide["masked"]) == (plain["run"], plain["masked"])
    q, k, v = (jnp.asarray(x) for x in _qkv_default(512, h=5))
    sched = mod.causal_schedule(512, 256, 512, True, None, 128)
    heads = q.shape[0] * q.shape[2]
    was = trace.get_tracer().enabled
    obs.enable()
    try:
        trace.get_tracer().reset()
        jax.jit(jax.grad(lambda q: flash_attention(
            q, k, v, causal=True, window=128, block_q=256, block_k=512,
            bwd_block_q=256, bwd_block_k=512,
            backward="pallas").sum()))(q)
        counters = trace.get_tracer().counters()
        traces, rest = divmod(counters["flash/score_blocks_total"],
                              heads * sched["total"])
        assert rest == 0 and traces >= 2
        assert (counters["flash/score_blocks_run"]
                == traces * heads * sched["run"])
    finally:
        trace.get_tracer().reset()
        if not was:
            obs.disable()
