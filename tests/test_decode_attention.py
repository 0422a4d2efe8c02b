"""Parity tests for the flash-decode attention kernel (interpret mode).

Oracle: the einsum attend from parallel/decode.py's decode tick — same
masking (positions ≤ pos), same fp32 softmax.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chainermn_tpu.ops.decode_attention import (decode_attend,
                                                decode_attend_gqa)


def oracle(q, kc, vc, pos, h, hd):
    b, s, d = kc.shape
    q4 = q.reshape(b, 1, h, hd)
    k4 = kc.reshape(b, s, h, hd)
    v4 = vc.reshape(b, s, h, hd)
    sc = jnp.einsum("bqhd,bkhd->bhqk", q4, k4,
                    preferred_element_type=jnp.float32) / (hd ** 0.5)
    # pos: a scalar, or one position per cache row
    pos = jnp.asarray(pos).reshape(-1, 1, 1, 1)
    sc = jnp.where(jnp.arange(s)[None, None, None, :] <= pos, sc, -1e30)
    p = jax.nn.softmax(sc, -1)
    ctx = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v4.dtype), v4,
                     preferred_element_type=jnp.float32)
    return ctx.reshape(b, d)


def oracle_gqa(q, kc, vc, pos, hq, hkv, hd):
    """parallel/decode.py's grouped-einsum fallback."""
    b, s, _ = kc.shape
    q5 = q.reshape(b, 1, hkv, hq // hkv, hd)
    kc4 = kc.reshape(b, s, hkv, hd)
    vc4 = vc.reshape(b, s, hkv, hd)
    sc = jnp.einsum("bqhgd,bkhd->bhgqk", q5, kc4,
                    preferred_element_type=jnp.float32) / (hd ** 0.5)
    pos = jnp.asarray(pos).reshape(-1, 1, 1, 1, 1)
    sc = jnp.where(jnp.arange(s)[None, None, None, None, :] <= pos,
                   sc, -1e30)
    p = jax.nn.softmax(sc, axis=-1)
    ctx = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(vc4.dtype), vc4,
                     preferred_element_type=jnp.float32)
    return ctx.reshape(b, hq * hd)


@pytest.mark.parametrize("b,s,h,hd,pos", [
    (2, 64, 4, 16, 31),
    (2, 64, 4, 16, 63),   # full cache valid
    (1, 96, 2, 32, 0),    # single valid position
    (3, 128, 8, 8, 100),  # pos mid-block
])
def test_matches_einsum_oracle(b, s, h, hd, pos):
    rs = np.random.RandomState(0)
    d = h * hd
    q = jnp.asarray(rs.randn(b, d), jnp.float32)
    kc = jnp.asarray(rs.randn(b, s, d), jnp.float32)
    vc = jnp.asarray(rs.randn(b, s, d), jnp.float32)
    got = decode_attend(q, kc, vc, pos, n_heads=h, head_dim=hd,
                        block_s=32, interpret=True)
    want = oracle(q, kc, vc, pos, h, hd)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


def test_bf16_cache():
    rs = np.random.RandomState(1)
    b, s, h, hd = 2, 128, 4, 16
    d = h * hd
    q = jnp.asarray(rs.randn(b, d), jnp.bfloat16)
    kc = jnp.asarray(rs.randn(b, s, d), jnp.bfloat16)
    vc = jnp.asarray(rs.randn(b, s, d), jnp.bfloat16)
    got = decode_attend(q, kc, vc, 77, n_heads=h, head_dim=hd,
                        block_s=64, interpret=True)
    want = oracle(q.astype(jnp.float32), kc.astype(jnp.float32),
                  vc.astype(jnp.float32), 77, h, hd)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want), rtol=2e-2, atol=2e-2)


# The serving tick's face: one position per cache row (slot), each row
# read up to its own length.  S = 128 in blocks of 32; the vectors hold 0,
# a block's last and first row, S - 1, and values >= S (a free slot, whose
# position the engine advances without bound).
ROW_S, ROW_BLOCK = 128, 32
ROW_POS = {
    "edges": [0, ROW_BLOCK - 1, ROW_BLOCK, ROW_S - 1, ROW_S, 5 * ROW_S],
    "mixed": [77, 3, 127, 64, 31, 96],
    "equal": [70] * 6,
}


def _row_case(dtype, d, seed=4):
    rs = np.random.RandomState(seed)
    b = len(ROW_POS["edges"])
    return (jnp.asarray(rs.randn(b, d), dtype),
            jnp.asarray(rs.randn(b, ROW_S, d), dtype),
            jnp.asarray(rs.randn(b, ROW_S, d), dtype))


def _row_attend(heads, q, kc, vc, pos):
    """MHA (``heads = (h,)``) or GQA (``(hq, hkv)``) at head_dim 16."""
    if len(heads) == 1:
        return decode_attend(q, kc, vc, pos, n_heads=heads[0], head_dim=16,
                             block_s=ROW_BLOCK, interpret=True)
    return decode_attend_gqa(q, kc, vc, pos, n_q_heads=heads[0],
                             n_kv_heads=heads[1], head_dim=16,
                             block_s=ROW_BLOCK, interpret=True)


def _row_oracle(heads, q, kc, vc, pos):
    f32 = [a.astype(jnp.float32) for a in (q, kc, vc)]
    if len(heads) == 1:
        return oracle(*f32, pos, heads[0], 16)
    return oracle_gqa(*f32, pos, heads[0], heads[1], 16)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("heads", [(4,), (8, 2)], ids=["mha", "gqa"])
@pytest.mark.parametrize("case", sorted(ROW_POS))
def test_per_row_positions_match_einsum_oracle(case, heads, dtype):
    q, kc, vc = _row_case(dtype, heads[0] * 16)
    kv = tuple(a[..., :heads[-1] * 16] for a in (kc, vc))
    pos = jnp.asarray(ROW_POS[case], jnp.int32)
    got = _row_attend(heads, q, *kv, pos)
    assert got.dtype == dtype and got.shape == q.shape
    tol = (dict(rtol=2e-4, atol=2e-5) if dtype == jnp.float32
           else dict(rtol=2e-2, atol=2e-2))
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(_row_oracle(heads, q, *kv, pos)),
                               **tol)
    if case == "equal":
        # all rows at one position IS the scalar call (lm_generate's
        # face), bit for bit: same mask, same block walk
        np.testing.assert_array_equal(
            np.asarray(got, np.float32),
            np.asarray(_row_attend(heads, q, *kv, ROW_POS[case][0]),
                       np.float32))


@pytest.mark.parametrize("heads", [(4,), (8, 2)], ids=["mha", "gqa"])
def test_blocks_above_a_rows_position_are_never_read(heads):
    """Every block lying WHOLLY above ``pos[b]`` is filled with NaN: the
    result is unchanged and finite, so a skipped block never enters the
    sum.  (Rows above ``pos`` inside the last live block are masked by a
    zero weight, in the kernel and in the einsum alike.)"""
    q, kc, vc = _row_case(jnp.float32, heads[0] * 16)
    kv = tuple(a[..., :heads[-1] * 16] for a in (kc, vc))
    pos = np.asarray(ROW_POS["edges"], np.int32)
    dead = (np.arange(ROW_S)[None, :] // ROW_BLOCK
            > np.minimum(pos, ROW_S - 1)[:, None] // ROW_BLOCK)
    assert dead.any() and not dead[-1].any()
    poisoned = tuple(jnp.where(dead[:, :, None], jnp.nan, a) for a in kv)
    want = _row_attend(heads, q, *kv, jnp.asarray(pos))
    got = _row_attend(heads, q, *poisoned, jnp.asarray(pos))
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_block_must_divide():
    q = jnp.zeros((1, 32))
    kc = jnp.zeros((1, 100, 32))
    with pytest.raises(ValueError, match="8-aligned"):
        decode_attend(q, kc, kc, 5, n_heads=2, head_dim=16, block_s=64,
                      interpret=True)


class TestBeamAttendParts:
    """The two-segment beam kernel + flash combine vs a joint-softmax
    einsum oracle (interpret mode)."""

    def _oracle_joint(self, q, pk, pv, gk, gv, amask, b, beams, h, hd):
        # joint softmax over prompt (all valid) + generated (amask)
        d = h * hd
        sp = pk.shape[1]
        q4 = q.reshape(b, beams, h, hd)
        pk4 = pk.reshape(b, sp, h, hd)
        pv4 = pv.reshape(b, sp, h, hd)
        gt = gk.shape[1]
        gk4 = gk.reshape(b, gt, h, hd)
        gv4 = gv.reshape(b, gt, h, hd)
        s_p = jnp.einsum("bshd,bthd->bsht", q4, pk4,
                         preferred_element_type=jnp.float32) / (hd ** 0.5)
        s_g = jnp.einsum("bshd,bthd->bsht", q4, gk4,
                         preferred_element_type=jnp.float32) / (hd ** 0.5)
        s_g = jnp.where(amask[:, :, None, :] != 0, s_g, -1e30)
        joint = jnp.concatenate([s_p, s_g], axis=-1)
        p = jax.nn.softmax(joint, axis=-1)
        ctx = (jnp.einsum("bsht,bthd->bshd", p[..., :sp], pv4,
                          preferred_element_type=jnp.float32)
               + jnp.einsum("bsht,bthd->bshd", p[..., sp:], gv4,
                            preferred_element_type=jnp.float32))
        return ctx.reshape(b * beams, d)

    def test_two_segment_merge_matches_joint_softmax(self):
        from chainermn_tpu.ops.decode_attention import (beam_attend_parts,
                                                        merge_attend_parts)

        rs = np.random.RandomState(0)
        b, beams, h, hd, sp, gt = 2, 3, 4, 16, 32, 24
        d = h * hd
        q = jnp.asarray(rs.randn(b * beams, d), jnp.float32)
        pk = jnp.asarray(rs.randn(b, sp, d), jnp.float32)
        pv = jnp.asarray(rs.randn(b, sp, d), jnp.float32)
        gk = jnp.asarray(rs.randn(b, gt, d), jnp.float32)
        gv = jnp.asarray(rs.randn(b, gt, d), jnp.float32)
        amask = jnp.asarray(rs.rand(b, beams, gt) > 0.4, jnp.int8)
        # every row must have ≥1 valid generated position for the oracle
        amask = amask.at[:, :, 0].set(1)

        part_p = beam_attend_parts(q, pk, pv, beams=beams, n_heads=h,
                                   head_dim=hd, block_s=16, interpret=True)
        part_g = beam_attend_parts(q, gk, gv, amask, beams=beams, n_heads=h,
                                   head_dim=hd, block_s=8, interpret=True)
        got = merge_attend_parts([part_p, part_g], n_heads=h, head_dim=hd,
                                 dtype=jnp.float32)
        want = self._oracle_joint(q, pk, pv, gk, gv, amask, b, beams, h, hd)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-5)

    def test_fully_masked_rows_are_prompt_only(self):
        from chainermn_tpu.ops.decode_attention import (beam_attend_parts,
                                                        merge_attend_parts)

        rs = np.random.RandomState(1)
        b, beams, h, hd, sp, gt = 1, 2, 2, 8, 16, 8
        d = h * hd
        q = jnp.asarray(rs.randn(b * beams, d), jnp.float32)
        pk = jnp.asarray(rs.randn(b, sp, d), jnp.float32)
        pv = jnp.asarray(rs.randn(b, sp, d), jnp.float32)
        gk = jnp.asarray(rs.randn(b, gt, d), jnp.float32)
        gv = jnp.asarray(rs.randn(b, gt, d), jnp.float32)
        amask = jnp.zeros((b, beams, gt), jnp.int8)  # tick 1: nothing yet

        part_p = beam_attend_parts(q, pk, pv, beams=beams, n_heads=h,
                                   head_dim=hd, block_s=8, interpret=True)
        part_g = beam_attend_parts(q, gk, gv, amask, beams=beams, n_heads=h,
                                   head_dim=hd, block_s=8, interpret=True)
        got = merge_attend_parts([part_p, part_g], n_heads=h, head_dim=hd,
                                 dtype=jnp.float32)
        acc, m, l = part_p
        segt = (jnp.arange(h)[:, None]
                == jnp.arange(d)[None, :] // hd).astype(jnp.float32)
        want = acc / (l @ segt)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-6)


class TestGQADecode:
    def test_matches_grouped_einsum_oracle(self):
        from chainermn_tpu.ops.decode_attention import decode_attend_gqa

        rs = np.random.RandomState(2)
        b, s, hq, hkv, hd, pos = 2, 64, 8, 2, 16, 40
        q = jnp.asarray(rs.randn(b, hq * hd), jnp.float32)
        kc = jnp.asarray(rs.randn(b, s, hkv * hd), jnp.float32)
        vc = jnp.asarray(rs.randn(b, s, hkv * hd), jnp.float32)
        got = decode_attend_gqa(q, kc, vc, pos, n_q_heads=hq,
                                n_kv_heads=hkv, head_dim=hd, block_s=16,
                                interpret=True)
        want = oracle_gqa(q, kc, vc, pos, hq, hkv, hd)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-5)

    def test_mqa_single_kv_head(self):
        from chainermn_tpu.ops.decode_attention import decode_attend_gqa

        rs = np.random.RandomState(3)
        b, s, hq, hkv, hd = 1, 32, 4, 1, 32
        q = jnp.asarray(rs.randn(b, hq * hd), jnp.float32)
        kc = jnp.asarray(rs.randn(b, s, hkv * hd), jnp.float32)
        vc = jnp.asarray(rs.randn(b, s, hkv * hd), jnp.float32)
        got = decode_attend_gqa(q, kc, vc, 31, n_q_heads=hq, n_kv_heads=hkv,
                                head_dim=hd, block_s=8, interpret=True)
        assert got.shape == (b, hq * hd)
        assert np.isfinite(np.asarray(got)).all()
