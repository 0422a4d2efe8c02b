"""Parity tests for the flash-decode attention kernel (interpret mode).

Oracle: the einsum attend from parallel/decode.py's decode tick — same
masking (positions ≤ pos), same fp32 softmax.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chainermn_tpu.ops.decode_attention import (decode_attend,
                                                decode_attend_gqa,
                                                decode_attend_mla,
                                                live_blocks, work_list)


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


@pytest.mark.parametrize("g", [2, 4])
def test_query_groups_of_one_cache_row_match_the_grouped_einsum(g):
    """What narrow-head GQA decode rides on, called as ``decode_attend_gqa``
    calls it: ``g`` query rows a cache row through ``beam_attend_parts``,
    each cache row up to its own position, normalized by the flash combine;
    a row that is not busy gives the exact-zero part, which merges to 0."""
    from chainermn_tpu.ops.decode_attention import (beam_attend_parts,
                                                    merge_attend_parts)

    rs = np.random.RandomState(43)
    b, s, hkv, hd = 3, 64, 2, 16
    q = jnp.asarray(rs.randn(b, hkv * g * hd), jnp.float32)
    kc = jnp.asarray(rs.randn(b, s, hkv * hd), jnp.float32)
    vc = jnp.asarray(rs.randn(b, s, hkv * hd), jnp.float32)
    pos = jnp.asarray([40, 0, 63], jnp.int32)
    busy = jnp.asarray([True, True, False])
    # head-major (Hkv, g, hd) -> group-major rows (B·g, Hkv·hd)
    q_g = q.reshape(b, hkv, g, hd).transpose(0, 2, 1, 3).reshape(
        b * g, hkv * hd)
    part = beam_attend_parts(q_g, kc, vc, pos, busy, beams=g, n_heads=hkv,
                             head_dim=hd, block_s=16, interpret=True)
    assert [p.shape for p in part] == [(b * g, hkv * hd), (b * g, hkv),
                                       (b * g, hkv)]
    got = merge_attend_parts([part], n_heads=hkv, head_dim=hd,
                             dtype=jnp.float32)
    got = np.asarray(got.reshape(b, g, hkv, hd).transpose(0, 2, 1, 3)
                     .reshape(b, -1))
    want = np.asarray(oracle_gqa(q, kc, vc, pos, hkv * g, hkv, hd))
    np.testing.assert_allclose(got[:2], want[:2], rtol=2e-4, atol=2e-5)
    np.testing.assert_array_equal(got[2], 0.0)
    for p in part:
        np.testing.assert_array_equal(np.asarray(p)[2 * g:], 0.0)


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


# The work list (PR 34): every face that takes one position a cache row
# walks the busy slots' live blocks only.  Four slots of S = 128 in blocks
# of 32; a slot that is not busy reads exact 0, a busy one what the einsum
# oracle reads; ``busy=None`` is every slot, bit for bit, and bit for bit
# what the (B, S / block) walk of PR 33 gave (recorded:
# fixtures/decode_attn_pr33.npz, with the oracle's own result beside it to
# tell whether this host rounds as the recording host did).
WORK_S, WORK_BLOCK, WORK_SLOTS = 128, 32, 4
WORK_POS = {
    "zero": [0, 0, 0, 0],
    "inside": [40, 5, 70, 100],
    "edge": [31, 32, 63, 127],              # a block's last row, first row
    "wrapped": [128, 300, 129, 640],        # >= S: a ring past its first lap
}
WORK_BUSY = {
    "none": [0, 0, 0, 0],
    "one": [0, 0, 1, 0],
    "alternating": [1, 0, 1, 0],
    "all": [1, 1, 1, 1],
}
#: face -> (query heads, KV heads, head size); ``mla``: (heads, rank, rope)
WORK_FACES = {"mha": (4, 4, 16), "gqa128": (4, 2, 128), "gqa64": (4, 2, 64),
              "mla": (4, 32, 16)}
WORK_RECORD = os.path.join(os.path.dirname(__file__), "fixtures",
                           "decode_attn_pr33.npz")


def _work_inputs(face):
    rs = np.random.RandomState(34)
    b, s = WORK_SLOTS, WORK_S
    if face == "mla":
        h, rank, rope = WORK_FACES[face]
        return (jnp.asarray(rs.randn(b, h, rank + rope), jnp.float32),
                jnp.asarray(rs.randn(b, s, rank + rope), jnp.float32), None)
    hq, hkv, hd = WORK_FACES[face]
    return (jnp.asarray(rs.randn(b, hq * hd), jnp.float32),
            jnp.asarray(rs.randn(b, s, hkv * hd), jnp.float32),
            jnp.asarray(rs.randn(b, s, hkv * hd), jnp.float32))


def _work_call(face, q, kc, vc, pos, busy=None):
    kw = dict(block_s=WORK_BLOCK, interpret=True)
    if face == "mla":
        out = decode_attend_mla(q, kc, pos, busy, rank=WORK_FACES[face][1],
                                scale=0.25, **kw)
        return out.reshape(WORK_SLOTS, -1)
    hq, hkv, hd = WORK_FACES[face]
    if face == "mha":
        return decode_attend(q, kc, vc, pos, busy, n_heads=hq, head_dim=hd,
                             **kw)
    return decode_attend_gqa(q, kc, vc, pos, busy, n_q_heads=hq,
                             n_kv_heads=hkv, head_dim=hd, **kw)


def _work_oracle(face, q, kc, vc, pos):
    if face == "mla":
        rank = WORK_FACES[face][1]
        sc = jnp.einsum("bhw,bkw->bhk", q, kc) * 0.25
        sc = jnp.where(jnp.arange(WORK_S)[None, None, :]
                       <= pos[:, None, None], sc, -1e30)
        return jnp.einsum("bhk,bkr->bhr", jax.nn.softmax(sc, -1),
                          kc[..., :rank]).reshape(WORK_SLOTS, -1)
    hq, hkv, hd = WORK_FACES[face]
    return oracle_gqa(q, kc, vc, pos, hq, hkv, hd)


@pytest.mark.parametrize("case", list(WORK_POS))
@pytest.mark.parametrize("mask", list(WORK_BUSY))
@pytest.mark.parametrize("face", list(WORK_FACES))
def test_work_list_reads_the_busy_slots_and_nothing_else(face, mask, case):
    q, kc, vc = _work_inputs(face)
    pos = jnp.asarray(WORK_POS[case], jnp.int32)
    busy = np.asarray(WORK_BUSY[mask], bool)
    # every block of a slot that is not busy is NaN: read, it would show
    idle = jnp.asarray(~busy)[:, None, None]
    poisoned = [None if a is None else jnp.where(idle, jnp.nan, a)
                for a in (kc, vc)]
    got = np.asarray(_work_call(face, q, *poisoned, pos, jnp.asarray(busy)))
    want = np.asarray(_work_oracle(face, q, kc, vc, pos))
    np.testing.assert_allclose(got[busy], want[busy], rtol=2e-4, atol=2e-5)
    np.testing.assert_array_equal(got[~busy], 0.0)
    if mask == "all":
        # no mask IS every slot busy, and is the walk PR 33 had, bit for
        # bit (on a host that rounds as the recording host did: the
        # recorded oracle says; elsewhere to float32's last digits)
        plain = np.asarray(_work_call(face, q, kc, vc, pos))
        np.testing.assert_array_equal(plain, got)
        with np.load(WORK_RECORD) as rec:
            then, then_oracle = rec[f"{face}-{case}"], \
                rec[f"oracle-{face}-{case}"]
        if np.array_equal(then_oracle, want):
            np.testing.assert_array_equal(plain, then)
        else:
            np.testing.assert_allclose(plain, then, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("mask", list(WORK_BUSY))
@pytest.mark.parametrize("case", list(WORK_POS))
def test_work_list_is_the_busy_slots_live_blocks_in_order(case, mask):
    """The device's list against the definition, and its length against
    the host's twin (what the engine's counters sum)."""
    pos, busy = WORK_POS[case], np.asarray(WORK_BUSY[mask], bool)
    want = [(i, j) for i in range(WORK_SLOTS) if busy[i]
            for j in range(min(pos[i], WORK_S - 1) // WORK_BLOCK + 1)]
    work = work_list(jnp.asarray(pos, jnp.int32), jnp.asarray(busy),
                     WORK_SLOTS, WORK_S, WORK_BLOCK)
    n = int(work.n[0])
    pairs = list(zip(np.asarray(work.slot).tolist(),
                     np.asarray(work.block).tolist()))
    assert len(pairs) == WORK_SLOTS * (WORK_S // WORK_BLOCK)
    assert pairs[:n] == want
    # past the list: the pair the last step held (nothing busy: one block)
    assert set(pairs[n:]) <= {want[-1] if want else (WORK_SLOTS - 1, 0)}
    assert live_blocks(pos, WORK_S, WORK_BLOCK, busy) == (n, len(pairs))
    assert live_blocks(pos, WORK_S, WORK_BLOCK) == live_blocks(
        pos, WORK_S, WORK_BLOCK, np.ones(WORK_SLOTS, bool))
