"""Flash-decode attention: one Pallas pass over the KV cache per tick.

The decode tick's attention is bandwidth-bound — read every cached K and
V byte a token needs once, at full HBM rate.  XLA's lowering of the
per-head einsums (``bqhgd,bhkd->bhgqk`` with q-length 1) misses that floor
~2.4× in the
compiled decode loop: with M=1 the dots lower to VPU multiply+reduce
fusions over ``(S, head_dim=64)`` tiles whose minor dim fills only half
of each 128-lane vreg (the round-4 HLO dump ranks these fusions top of
the while body; the same chain STANDALONE compiles to MXU dots and hits
1028 GB/s — the miss is a fusion/layout decision inside the big loop,
not op cost).

This kernel sidesteps the shape problem instead of fighting the fusion
heuristics:

* the cache is stored FLAT — ``(B, S, H·head_dim)`` — so every load
  streams dense 128-lane rows (1024 lanes at the bench config);
* per-head score reduction is a SEGMENTED MATMUL: ``scores (S_b, H) =
  (K ⊙ q) @ SEG`` where ``SEG (H·hd, H)`` is the 0/1 head-membership
  matrix — the MXU does the 64-wide segment sums, no reshapes, no
  per-head GEMVs;
* softmax is the standard online (m, l, acc) flash recursion over
  S-blocks, entirely in VMEM/registers;
* the probability-weighted V sum expands ``p (S_b, H)`` back to lanes
  with ``SEGᵀ`` (MXU again) and reduces over the block's sublanes.

Grid: ONE axis over a WORK LIST — the ``(slot, block)`` pairs ``[(i, j)
for busy slot i in slot order for j in 0 .. min(pos[i], S - 1) // block_s]``,
built on the device from ``pos`` and ``busy`` (:func:`work_list`: a cumulative
sum of each busy slot's block count and a comparison, a few hundred entries)
and handed to the kernel by scalar prefetch beside ``pos``; the grid's bound
is the list's length, a traced value.  Step ``t`` fetches block ``block[t]``
of slot ``slot[t]``, so every live block's copy is issued while the block
before it computes — no dead step stands between two live blocks, of one row
or of two — and a block no pair names is neither fetched nor computed (it
would have contributed exact zeros, or fed a row nobody reads).  The online
softmax state resets where a pair's block is 0 and a slot's result is written
where the pair is the slot's last; both come from the list.  ``pos`` is ONE
ENTRY PER CACHE ROW: the closed batch of ``lm_generate`` passes a scalar,
which is broadcast, and the serving tick passes each slot's own length.
``busy`` names the rows that carry a token (the serving tick's busy slots;
None, every direct caller's and the closed batch's: every row, and then the
list is every row's live blocks in row order, bit for bit the walk a ``(B,
S / block_s)`` grid made).  A row that is NOT busy gets no pair, reads
nothing and is 0 — a free slot's rows, a cached prefix, a ring nobody
decodes from cost the tick nothing.  Positions beyond ``pos[i]`` inside a
row's last live block are masked before the online max.  A position at or
beyond ``S`` masks nothing and indexes nothing out of range; ``pos`` must
be ≥ 0.  A tick builds the list once a cache shape and hands it to every
layer's call (``work=``).
``decode_attend`` covers h_q == h_kv.  GQA decode (``decode_attend_gqa``)
has the same face — one position per cache row, the same list — through
its own kernel where a head is whole lane tiles (``head_dim % 128 == 0``):
a KV head's columns are then an aligned slice of the flat row, and its
``g`` query heads meet it as two plain MXU matmuls (``(g, hd) x (hd,
S_b)``, ``(g, S_b) x (S_b, hd)``), the cache read once.  Narrower heads
ride the QUERY-GROUP kernel (:func:`beam_attend_parts`, kernel name
``decode_attn_beam``: the g query groups of a batch row are g query rows
that share its cache row).  A cache of ``W``
rows that is a RING (a windowed layer: position ``p`` at row ``p % W``,
every key rotated at its own position before it was cached) needs nothing
more: a position at or beyond ``W`` masks nothing, so ``min(pos + 1, W)``
rows are read, the softmax does not care about their order, and ``W`` rows
are one block a slot, so a ring's list is the busy slots.

``decode_attend_mla`` is the face for a LATENT cache (multi-head latent
attention in its absorbed form): every query head of a slot attends ONE
shared ``(S, rank + rope)`` row set whose first ``rank`` columns are also
the values, so keys and values are the same block, read once, and the
scores and the weighted sum are two plain MXU matmuls (heads x block).
Same list, same one position per cache row.

Reference relationship: no analog — the reference decoded by re-running
the full decoder per token (SURVEY.md §2.9 seq2seq).  Parity oracle:
the einsum attend in ``parallel/decode.py`` (``impl='xla'``), tested in
tests/test_decode_attention.py.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax

import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .._compat import shape_dtype_struct as _sds
from .kv_cache import _inherit_vma

__all__ = ["decode_attend", "decode_attend_gqa", "decode_attend_mla",
           "DecodeWork", "work_list", "live_blocks", "beam_attend_parts",
           "merge_attend_parts"]

_NEG = -1e30
DEFAULT_BLOCK_S = 512  # single source for the kernel AND dispatch gates


def _seg(d: int, n_heads: int):
    """The 0/1 head-membership matrix ``(D, H)``: SEG[j, h] = 1 iff lane
    ``j`` belongs to head ``h`` — single source for the kernels and the
    merge (its transpose)."""
    return (jnp.arange(d)[:, None] // (d // n_heads)
            == jnp.arange(n_heads)[None, :]).astype(jnp.float32)


def _pick_block_s(s: int, want: int = DEFAULT_BLOCK_S) -> int:
    """Largest 8-aligned divisor of ``s`` ≤ ``want`` (0 = none)."""
    if s <= want:
        return s if s % 8 == 0 or s == 1 else 0
    for b in range(want, 7, -1):
        if s % b == 0 and b % 8 == 0:
            return b
    return 0


def _row_pos(pos, b: int):
    """``pos`` — a scalar (every row at the same position) or a ``(B,)``
    vector (each row at its own) — as the kernels' one ``(B,)`` int32
    scalar-prefetch operand."""
    return jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))


class DecodeWork(NamedTuple):
    """The kernels' walk, on the device: ``slot[t], block[t]`` is grid step
    ``t``'s cache block, for ``t < n[0]``, the grid's bound; the entries
    from there on repeat the last pair (:func:`work_list`)."""
    slot: jax.Array     # (B * S / block_s,) int32
    block: jax.Array    # the same
    n: jax.Array        # (1,) int32


def work_list(pos, busy, b: int, s: int,
              block_s: int = DEFAULT_BLOCK_S) -> DecodeWork:
    """The work list of one call over a ``(b, s, D)`` cache: the pairs
    ``(i, j) for busy slot i in slot order for j in 0 .. min(pos[i], s - 1)
    // block_s``, in vectors of the static length ``b * (s // block_s)``
    whose tail repeats the last pair (no step visits it: the grid's bound
    is the list's length; a walk over all of it would fetch nothing there,
    a step that maps the block the step before held copies nothing).
    ``pos`` a scalar or ``(b,)``, ``busy (b,) bool`` or None
    (every row).  The clamp to ``s - 1`` keeps a position beyond the cache
    inside it.  A few vector operations on ``b`` and ``b * s // block_s``
    entries: a tick builds one list a cache shape and hands it to every
    layer's call."""
    bs = _pick_block_s(s, block_s)
    if bs == 0:
        raise ValueError(f"S={s} has no 8-aligned block ≤ {block_s}")
    count = jnp.minimum(_row_pos(pos, b), s - 1) // bs + 1
    if busy is not None:
        count = jnp.where(busy, count, 0)
    ends = jnp.cumsum(count)
    n = ends[-1]
    t = jnp.minimum(jnp.arange(b * (s // bs), dtype=jnp.int32),
                    jnp.maximum(n - 1, 0))
    # the slot whose run of blocks holds step t (nothing busy: the last)
    slot = jnp.minimum((t[:, None] >= ends[None, :]).sum(-1), b - 1)
    block = t - (ends - count)[slot]
    return DecodeWork(slot.astype(jnp.int32), block.astype(jnp.int32),
                      n.astype(jnp.int32).reshape(1))


def live_blocks(pos, s: int, block_s: int = DEFAULT_BLOCK_S, busy=None):
    """HOST-side count of one kernel call's read, ``(read, total)``: the
    length of :func:`work_list`'s list by its own arithmetic — the
    S-blocks of a ``(len(pos), s, D)`` cache at or below each ``busy`` row's
    position (None: every row's) — and the blocks the cache holds.  ``(0,
    0)`` where ``s`` admits no block (the einsum path)."""
    bs = _pick_block_s(s, block_s)
    if bs == 0:
        return 0, 0
    pos = np.asarray(pos)
    count = np.minimum(pos, s - 1) // bs + 1
    if busy is not None:
        count = count[np.asarray(busy, bool)]
    return int(count.sum()), pos.size * (s // bs)


def _work_spec(work, pos, busy, b: int, s: int, block_s: int):
    """What every face needs of the walk: the block size, the (one-axis)
    grid, the scalar-prefetch operands ``(slot, block, n, pos)`` and the
    index maps of a per-slot operand and of a cache block.  ``work`` None:
    the list is built here."""
    bs = _pick_block_s(s, block_s)
    if bs == 0:
        raise ValueError(f"S={s} has no 8-aligned block ≤ {block_s}")
    if work is None:
        work = work_list(pos, busy, b, s, bs)
    assert work.slot.shape == (b * (s // bs),), (work.slot.shape, b, s, bs)
    return (bs, _work_grid(work), (*work, _row_pos(pos, b)),
            lambda t, slot, block, n, p_: (slot[t], 0, 0),
            lambda t, slot, block, n, p_: (slot[t], block[t], 0))


def _work_grid(work: DecodeWork):
    """The grid of a walk: one step a listed pair (a traced bound), and one
    step where nothing is listed, which zeroes what is resident."""
    return (jnp.maximum(work.n[0], 1),)


def _step(slot_ref, block_ref, n_ref, pos_ref, s: int, block_s: int):
    """This grid step's ``(start, slot, block, position, run, first,
    last)``: whether it is the walk's first step, its pair and the slot's
    position, whether the step holds a pair of the list at all, whether the
    pair opens the slot's online softmax, and whether it closes it."""
    t = pl.program_id(0)
    i, j = slot_ref[t], block_ref[t]
    run = t < n_ref[0]
    return (t == 0, i, j, pos_ref[i], run, run & (j == 0),
            run & (j == jnp.minimum(pos_ref[i], s - 1) // block_s))


def _resident_zero(start, *refs):
    """Whole-array results stay in VMEM over the walk and fill in row by
    row: the rows of a slot that has no pair read 0."""
    @pl.when(start)
    def _zero():
        for ref in refs:
            ref[...] = jnp.zeros_like(ref)


def _open_softmax(first, m_ref, l_ref, acc_ref):
    """A slot's first pair opens its online softmax: max, sum, weighted sum."""
    @pl.when(first)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)


def _kernel(slot_ref, block_ref, n_ref, pos_ref, q_ref, k_ref, v_ref,
            seg_ref, segt_ref, o_ref, m_ref, l_ref, acc_ref, *, s, block_s,
            scale):
    start, i, j, pos_i, run, first, last = _step(
        slot_ref, block_ref, n_ref, pos_ref, s, block_s)
    _resident_zero(start, o_ref)

    _open_softmax(first, m_ref, l_ref, acc_ref)

    @pl.when(run)
    def _block():
        k = k_ref[0]                                   # (S_b, D)
        # q/o blocks stay whole-(B, D) resident (a (1, D) block would
        # break the (8, 128) tiling rule, and Mosaic rejects unaligned
        # dynamic sublane indexing) — the batch row is selected by iota
        # mask
        bidx = jax.lax.broadcasted_iota(jnp.int32, q_ref.shape, 0)
        q = jnp.where(bidx == i, q_ref[...], 0).astype(jnp.float32).sum(
            axis=0, keepdims=True)                     # (1, D)
        seg = seg_ref[...]                             # (D, H) 0/1 f32
        # segmented per-head dot: (K ⊙ q) @ SEG — MXU does the 64-wide sums
        t = k.astype(jnp.float32) * q                  # (S_b, D)
        s_blk = jax.lax.dot_general(
            t, seg, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (S_b, H)
        idx = j * block_s + jax.lax.broadcasted_iota(
            jnp.int32, s_blk.shape, 0)
        s_blk = jnp.where(idx <= pos_i, s_blk, _NEG)

        m_prev = m_ref[...]                            # (1, H)
        l_prev = l_ref[...]
        m_new = jnp.maximum(m_prev, s_blk.max(axis=0, keepdims=True))
        corr = jnp.exp(m_prev - m_new)                 # (1, H)
        p = jnp.exp(s_blk - m_new)                     # (S_b, H)
        m_ref[...] = m_new
        l_ref[...] = l_prev * corr + p.sum(axis=0, keepdims=True)
        segt = segt_ref[...]                           # (H, D)
        p_lanes = jax.lax.dot_general(                 # (S_b, D)
            p, segt, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        corr_lanes = jax.lax.dot_general(              # (1, D)
            corr, segt, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        v = v_ref[0].astype(jnp.float32)               # (S_b, D)
        acc_ref[...] = (acc_ref[...] * corr_lanes
                        + (p_lanes * v).sum(axis=0, keepdims=True))

    @pl.when(last)
    def _finish():
        l_lanes = jax.lax.dot_general(
            l_ref[...], segt_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        # write row i, preserve the others
        val = (acc_ref[...] / l_lanes).astype(o_ref.dtype)
        o_ref[...] = jnp.where(
            jax.lax.broadcasted_iota(jnp.int32, o_ref.shape, 0) == i,
            val, o_ref[...])


@functools.partial(jax.jit, static_argnames=("n_heads", "head_dim",
                                             "block_s", "interpret"))
def decode_attend(q, kc, vc, pos, busy=None, *, n_heads: int, head_dim: int,
                  block_s: int = DEFAULT_BLOCK_S, interpret: bool = False,
                  work: Optional[DecodeWork] = None):
    """One decode tick's attention over the cache.

    ``q (B, H·hd)`` flat queries, ``kc/vc (B, S, H·hd)`` flat caches,
    ``pos`` a scalar or a ``(B,)`` int32 vector, ``busy (B,) bool`` or None
    (every row): busy row ``b`` attends its cache prefix ``[0, pos[b]]``
    and reads only the blocks that hold it; a row that is not busy reads
    nothing and is 0 (module docstring).  ``work``: :func:`work_list` of
    the same ``pos``, ``busy`` and block, where the caller has built it
    (None: built here).  Returns ``ctx (B, H·hd)``.  Requires the q-head
    count to equal the cache's ``n_heads``; GQA decode goes through
    :func:`decode_attend_gqa`.
    """
    b, s, d = kc.shape
    h = n_heads
    assert d == h * head_dim, (d, h, head_dim)
    bs, grid, scalars, _, kv_map = _work_spec(work, pos, busy, b, s,
                                              block_s)
    scale = 1.0 / (head_dim ** 0.5)
    seg = _seg(d, h)
    vma = _inherit_vma(q, kc, vc)
    whole = lambda *_: (0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars), grid=grid,
        in_specs=[
            pl.BlockSpec((b, d), whole),
            pl.BlockSpec((1, bs, d), kv_map),
            pl.BlockSpec((1, bs, d), kv_map),
            pl.BlockSpec((d, h), whole),
            pl.BlockSpec((h, d), whole),
        ],
        out_specs=pl.BlockSpec((b, d), whole),
        scratch_shapes=[
            pltpu.VMEM((1, h), jnp.float32),
            pltpu.VMEM((1, h), jnp.float32),
            pltpu.VMEM((1, d), jnp.float32),
        ])
    return pl.pallas_call(
        functools.partial(_kernel, s=s, block_s=bs, scale=scale),
        grid_spec=grid_spec,
        out_shape=_sds((b, d), q.dtype, vma=vma),
        name="decode_attn_mha",
        interpret=interpret,
    )(*scalars, q, kc, vc, seg, seg.T)


def _beam_kernel(slot_ref, block_ref, n_ref, pos_ref, q_ref, k_ref, v_ref,
                 seg_ref, segt_ref, acc_o_ref, m_o_ref, l_o_ref,
                 m_ref, l_ref, acc_ref, *, beams, s, block_s, scale):
    """Query groups of one cache row: q rows [i·beams, (i+1)·beams) share
    batch row i's cache; per-row online-softmax state; outputs UNNORMALIZED
    (acc, m, l), normalized outside by the flash combine
    (:func:`merge_attend_parts`).  The walk and the mask (positions beyond
    the cache row's own, from scalar prefetch) are :func:`_kernel`'s."""
    start, i, j, pos_i, run, first, last = _step(
        slot_ref, block_ref, n_ref, pos_ref, s, block_s)
    _resident_zero(start, acc_o_ref, m_o_ref, l_o_ref)

    _open_softmax(first, m_ref, l_ref, acc_ref)

    @pl.when(run)
    def _block():
        kb = k_ref[0].astype(jnp.float32)              # (S_b, D)
        vb = v_ref[0].astype(jnp.float32)
        seg, segt = seg_ref[...], segt_ref[...]
        rows = jax.lax.broadcasted_iota(jnp.int32, q_ref.shape, 0)
        for r in range(beams):
            q = jnp.where(rows == i * beams + r, q_ref[...], 0).astype(
                jnp.float32).sum(axis=0, keepdims=True)           # (1, D)
            s_blk = jax.lax.dot_general(
                kb * q, seg, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32) * scale       # (S_b, H)
            # position-validity from the row's prefetch scalar — zero HBM
            # cost (an f32 mask operand would stream B·g·S·4 bytes per
            # layer per tick)
            idx = j * block_s + jax.lax.broadcasted_iota(
                jnp.int32, s_blk.shape, 0)
            s_blk = jnp.where(idx <= pos_i, s_blk, _NEG)
            m_prev = m_ref[r:r + 1, :]                            # (1, H)
            l_prev = l_ref[r:r + 1, :]
            m_new = jnp.maximum(m_prev, s_blk.max(axis=0, keepdims=True))
            corr = jnp.exp(m_prev - m_new)
            p = jnp.exp(s_blk - m_new)
            m_ref[r:r + 1, :] = m_new
            l_ref[r:r + 1, :] = l_prev * corr + p.sum(axis=0, keepdims=True)
            p_lanes = jax.lax.dot_general(
                p, segt, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            corr_lanes = jax.lax.dot_general(
                corr, segt, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            acc_ref[r:r + 1, :] = (acc_ref[r:r + 1, :] * corr_lanes
                                   + (p_lanes * vb).sum(axis=0, keepdims=True))

    @pl.when(last)
    def _finish():
        orows = jax.lax.broadcasted_iota(jnp.int32, acc_o_ref.shape, 0)
        hrows = jax.lax.broadcasted_iota(jnp.int32, m_o_ref.shape, 0)
        for r in range(beams):
            row = i * beams + r
            acc_o_ref[...] = jnp.where(orows == row, acc_ref[r:r + 1, :],
                                       acc_o_ref[...])
            m_o_ref[...] = jnp.where(hrows == row, m_ref[r:r + 1, :],
                                     m_o_ref[...])
            l_o_ref[...] = jnp.where(hrows == row, l_ref[r:r + 1, :],
                                     l_o_ref[...])


@functools.partial(jax.jit, static_argnames=(
    "beams", "n_heads", "head_dim", "block_s", "interpret"))
def beam_attend_parts(q, kc, vc, pos, busy=None, *,
                      beams: int, n_heads: int, head_dim: int,
                      block_s: int = DEFAULT_BLOCK_S,
                      interpret: bool = False,
                      work: Optional[DecodeWork] = None):
    """Attention of ``beams`` query rows a cache row, unnormalized — what
    narrow-head GQA decode rides on (:func:`decode_attend_gqa`: a KV head's
    query group is ``beams`` query rows of one cache row).

    ``q (B·beams, H·hd)`` flat queries, row ``b·beams + r`` the ``r``-th of
    batch row ``b``; ``kc/vc (B, S, H·hd)`` the cache; ``pos`` a scalar or
    a ``(B,)`` int32 vector, ≥ 0: batch row ``b``'s rows see its prefix
    ``[0, pos[b]]`` and read only the blocks that hold it, and only the
    ``busy`` rows' are read at all (``busy``, ``work`` as
    :func:`decode_attend`: a row that is not busy gives ``(0, 0, 0)``,
    which merges to 0).  Returns ``(acc (B·beams, D) f32 unnormalized, m
    (B·beams, H) f32, l (B·beams, H) f32)``: normalize with
    :func:`merge_attend_parts`.
    """
    bk, d = q.shape
    b, s, _ = kc.shape
    assert bk == b * beams, (bk, b, beams)
    h = n_heads
    assert d == h * head_dim, (d, h, head_dim)
    bs, grid, scalars, _, kv_map = _work_spec(work, pos, busy, b, s,
                                              block_s)
    scale = 1.0 / (head_dim ** 0.5)
    seg = _seg(d, h)
    whole = lambda *_: (0, 0)
    vma = _inherit_vma(q, kc, vc)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars), grid=grid,
        in_specs=[
            pl.BlockSpec((bk, d), whole),
            pl.BlockSpec((1, bs, d), kv_map),
            pl.BlockSpec((1, bs, d), kv_map),
            pl.BlockSpec((d, h), whole),
            pl.BlockSpec((h, d), whole),
        ],
        out_specs=[
            pl.BlockSpec((bk, d), whole),
            pl.BlockSpec((bk, h), whole),
            pl.BlockSpec((bk, h), whole),
        ],
        scratch_shapes=[
            pltpu.VMEM((beams, h), jnp.float32),
            pltpu.VMEM((beams, h), jnp.float32),
            pltpu.VMEM((beams, d), jnp.float32),
        ])
    return pl.pallas_call(
        functools.partial(_beam_kernel, beams=beams, s=s, block_s=bs,
                          scale=scale),
        grid_spec=grid_spec,
        out_shape=[_sds((bk, d), jnp.float32, vma=vma),
                   _sds((bk, h), jnp.float32, vma=vma),
                   _sds((bk, h), jnp.float32, vma=vma)],
        name="decode_attn_beam",
        interpret=interpret,
    )(*scalars, q, kc, vc, seg, seg.T)


def merge_attend_parts(parts, n_heads: int, head_dim: int, dtype):
    """Flash combine of ``(acc, m, l)`` parts → normalized context
    ``(B·beams, H·hd)`` in ``dtype``.

    Masking uses a finite ``-1e30`` sentinel, so a row whose every position
    were masked could not be told from real data; :func:`beam_attend_parts`
    masks by ``pos >= 0``, never a whole row.  The ``l > 0`` guard: a row
    that is not busy arrives as ``(0, 0, 0)`` and gives zeros, not 0/0."""
    d = n_heads * head_dim
    seg_t = _seg(d, n_heads).T

    def lanes(x):  # (N, H) -> (N, D) per-head broadcast
        return x @ seg_t

    m = functools.reduce(jnp.maximum, [p[1] for p in parts])
    l_tot = 0.0
    acc_tot = 0.0
    for acc, m_i, l_i in parts:
        a = jnp.exp(m_i - m)
        l_tot = l_tot + l_i * a
        acc_tot = acc_tot + acc * lanes(a)
    den = lanes(l_tot)
    ctx = acc_tot / jnp.maximum(den, 1e-30)
    return jnp.where(den > 0, ctx, 0.0).astype(dtype)


def zero_idle_rows(out, busy):
    """``out (B, ...)`` with the rows that are not ``busy (B,) bool`` set
    to 0 (None: all busy).  A per-slot result block that no pair mapped was
    never written: such a slot reads 0, not what the buffer held — and the
    einsum paths beside the kernels give the same."""
    if busy is None:
        return out
    return jnp.where(busy.reshape((-1,) + (1,) * (out.ndim - 1)), out, 0)


def _gqa_kernel(slot_ref, block_ref, n_ref, pos_ref, q_ref, k_ref, v_ref,
                o_ref, m_ref, l_ref, acc_ref, *, s, block_s, scale, n_kv,
                rows, head_dim):
    """One slot's blocks in a row of the walk, as :func:`_mla_kernel`, with
    a K/V pair of ``n_kv`` heads: ``q_ref (1, n_kv·rows, hd)`` float32
    holds KV head ``h``'s query heads in rows ``[h·rows, (h+1)·rows)``
    (``rows``: the group rounded up to whole sublane tiles, the pad rows
    zero), ``k_ref / v_ref (1, S_b, n_kv·hd)`` the flat cache block, whose
    columns ``[h·hd, (h+1)·hd)`` are head ``h``'s."""
    _, _, j, pos_i, run, first, last = _step(slot_ref, block_ref, n_ref,
                                             pos_ref, s, block_s)

    _open_softmax(first, m_ref, l_ref, acc_ref)

    @pl.when(run)
    def _block():
        idx = j * block_s + jax.lax.broadcasted_iota(
            jnp.int32, (rows, block_s), 1)
        live = idx <= pos_i
        for h in range(n_kv):
            r = slice(h * rows, (h + 1) * rows)
            c = slice(h * head_dim, (h + 1) * head_dim)
            k = k_ref[0, :, c]                         # (S_b, hd)
            v = v_ref[0, :, c]
            s_blk = jax.lax.dot_general(
                q_ref[0, r, :].astype(k.dtype), k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # (rows, S_b)
            s_blk = jnp.where(live, s_blk, _NEG)
            m_prev = m_ref[r, :1]                      # (rows, 1)
            m_new = jnp.maximum(m_prev, s_blk.max(-1, keepdims=True))
            p = jnp.where(live, jnp.exp(s_blk - m_new), 0.0)
            alpha = jnp.exp(m_prev - m_new)
            l_new = l_ref[r, :1] * alpha + p.sum(-1, keepdims=True)
            acc_ref[r, :] = acc_ref[r, :] * alpha + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)    # (rows, hd)
            m_ref[r, :] = jnp.broadcast_to(m_new, (rows, m_ref.shape[1]))
            l_ref[r, :] = jnp.broadcast_to(l_new, (rows, l_ref.shape[1]))

    @pl.when(last)
    def _finish():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[:, :1], 1e-37)
                    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "n_q_heads", "n_kv_heads", "head_dim", "block_s", "interpret"))
def _decode_attend_gqa_lanes(q, kc, vc, pos, busy, work, *, n_q_heads,
                             n_kv_heads, head_dim, block_s, interpret):
    """:func:`decode_attend_gqa` through :func:`_gqa_kernel`."""
    b, s, d_kv = kc.shape
    g = n_q_heads // n_kv_heads
    rows = -(-g // 8) * 8
    bs, grid, scalars, slot_map, kv_map = _work_spec(work, pos, busy, b, s,
                                                     block_s)
    # head-major (Hkv, g, hd): query head h·g + r is KV head h's r-th
    qg = q.reshape(b, n_kv_heads, g, head_dim).astype(jnp.float32)
    if rows != g:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, rows - g), (0, 0)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars), grid=grid,
        in_specs=[
            pl.BlockSpec((1, n_kv_heads * rows, head_dim), slot_map),
            pl.BlockSpec((1, bs, d_kv), kv_map),
            pl.BlockSpec((1, bs, d_kv), kv_map),
        ],
        out_specs=pl.BlockSpec((1, n_kv_heads * rows, head_dim), slot_map),
        scratch_shapes=[
            pltpu.VMEM((n_kv_heads * rows, 128), jnp.float32),
            pltpu.VMEM((n_kv_heads * rows, 128), jnp.float32),
            pltpu.VMEM((n_kv_heads * rows, head_dim), jnp.float32),
        ])
    out = pl.pallas_call(
        functools.partial(_gqa_kernel, s=s, block_s=bs,
                          scale=1.0 / (head_dim ** 0.5), n_kv=n_kv_heads,
                          rows=rows, head_dim=head_dim),
        grid_spec=grid_spec,
        out_shape=_sds((b, n_kv_heads * rows, head_dim), q.dtype,
                       vma=_inherit_vma(q, kc, vc)),
        name="decode_attn_gqa",
        interpret=interpret,
    )(*scalars, qg.reshape(b, n_kv_heads * rows, head_dim), kc, vc)
    out = out.reshape(b, n_kv_heads, rows, head_dim)[:, :, :g].reshape(
        b, n_q_heads * head_dim)
    return zero_idle_rows(out, busy)


def decode_attend_gqa(q, kc, vc, pos, busy=None, *, n_q_heads: int,
                      n_kv_heads: int, head_dim: int,
                      block_s: int = DEFAULT_BLOCK_S,
                      interpret: bool = False,
                      work: Optional[DecodeWork] = None):
    """GQA decode tick: grouped queries against the shared-KV-head cache.

    Heads of whole lane tiles (``head_dim % 128 == 0``) take the
    one-position-per-cache-row face of :func:`decode_attend` through
    :func:`_gqa_kernel`.  Narrower heads ride the query-group kernel
    (:func:`beam_attend_parts`): the ``g = n_q_heads/n_kv_heads`` query
    groups of batch row ``b`` all attend batch row ``b``'s cache, so they
    are ``beams=g`` query rows of it, masked by the row's position.
    Either way a block of the cache streams ONCE per tick (one grid step a
    listed block; the g groups iterate in-register) — GQA's inference
    payoff is preserved.

    ``q (B, Hq·hd)`` head-major flat; ``kc/vc (B, S, Hkv·hd)``; ``pos``
    a scalar or a ``(B,)`` int32 vector (one position per cache row),
    ``busy`` and ``work`` as :func:`decode_attend`; returns
    ``ctx (B, Hq·hd)``, 0 in the rows that are not busy.  Group
    convention matches ``parallel/decode.py``:
    q-head h uses KV head ``h // g`` (head-major reshape to
    ``(Hkv, g, hd)``).
    """
    b, s, d_kv = kc.shape
    g = n_q_heads // n_kv_heads
    if n_q_heads % n_kv_heads or g < 1:
        raise ValueError(f"bad head ratio {n_q_heads}/{n_kv_heads}")
    if head_dim % 128 == 0:
        return _decode_attend_gqa_lanes(
            q, kc, vc, pos, busy, work, n_q_heads=n_q_heads,
            n_kv_heads=n_kv_heads, head_dim=head_dim, block_s=block_s,
            interpret=interpret)
    # (B, Hkv, g, hd) -> group-major rows (B·g, Hkv·hd), b-major as the
    # kernel's row -> cache row mapping expects
    q_g = q.reshape(b, n_kv_heads, g, head_dim).transpose(0, 2, 1, 3) \
        .reshape(b * g, n_kv_heads * head_dim)
    part = beam_attend_parts(q_g, kc, vc, pos, busy, beams=g,
                             n_heads=n_kv_heads, head_dim=head_dim,
                             block_s=block_s, interpret=interpret, work=work)
    ctx_g = merge_attend_parts([part], n_heads=n_kv_heads,
                               head_dim=head_dim, dtype=q.dtype)
    return ctx_g.reshape(b, g, n_kv_heads, head_dim) \
        .transpose(0, 2, 1, 3).reshape(b, n_q_heads * head_dim)


def _mla_kernel(slot_ref, block_ref, n_ref, pos_ref, q_ref, c_ref, o_ref,
                m_ref, l_ref, acc_ref, *, s, block_s, scale, rank):
    """Absorbed latent attention, one slot's blocks in a row of the walk:
    ``q_ref (1, H, rank + rope)`` holds ``[W_UK^T q_nope | RoPE(q_rope)]``
    per head, ``c_ref (1, S_b, rank + rope)`` the latent rows ``[c_kv |
    RoPE(k_rope)]``; the values are the block's first ``rank`` columns."""
    _, _, j, pos_i, run, first, last = _step(slot_ref, block_ref, n_ref,
                                             pos_ref, s, block_s)

    _open_softmax(first, m_ref, l_ref, acc_ref)

    @pl.when(run)
    def _block():
        c = c_ref[0]                                   # (S_b, R + r)
        s_blk = jax.lax.dot_general(
            q_ref[0], c, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (H, S_b)
        idx = j * block_s + jax.lax.broadcasted_iota(
            jnp.int32, s_blk.shape, 1)
        live = idx <= pos_i
        s_blk = jnp.where(live, s_blk, _NEG)
        m_prev = m_ref[:, :1]                          # (H, 1)
        m_new = jnp.maximum(m_prev, s_blk.max(-1, keepdims=True))
        p = jnp.where(live, jnp.exp(s_blk - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_ref[:, :1] * alpha + p.sum(-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(c.dtype), c[:, :rank], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)        # (H, R)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(last)
    def _finish():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[:, :1], 1e-37)
                    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("rank", "scale", "block_s",
                                             "interpret"))
def decode_attend_mla(q, cache, pos, busy=None, *, rank: int, scale: float,
                      block_s: int = DEFAULT_BLOCK_S,
                      interpret: bool = False,
                      work: Optional[DecodeWork] = None):
    """One decode tick's absorbed latent attention over the cache.

    ``q (B, H, rank + rope)`` per-head absorbed queries ``[W_UK^T q_nope |
    RoPE(q_rope)]``, ``cache (B, S, rank + rope)`` the latent rows
    ``[c_kv | RoPE(k_rope)]`` shared by all ``H`` heads, ``pos`` a scalar
    or a ``(B,)`` int32 vector, ``busy`` and ``work`` as
    :func:`decode_attend`: busy row ``b`` attends ``[0, pos[b]]`` and
    reads only the blocks that hold it, a row that is not busy reads
    nothing and is 0 (module docstring).  ``scale``
    multiplies the scores (the model's softmax scale: the kernel knows no
    head size).  Returns ``o_lat (B, H, rank)``, the softmax-weighted sum
    of ``c_kv``; the caller applies ``W_UV``."""
    b, s, width = cache.shape
    _, h, wq = q.shape
    assert wq == width and rank <= width, (q.shape, cache.shape, rank)
    bs, grid, scalars, slot_map, kv_map = _work_spec(work, pos, busy, b, s,
                                                     block_s)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars), grid=grid,
        in_specs=[
            pl.BlockSpec((1, h, width), slot_map),
            pl.BlockSpec((1, bs, width), kv_map),
        ],
        out_specs=pl.BlockSpec((1, h, rank), slot_map),
        scratch_shapes=[
            pltpu.VMEM((h, 128), jnp.float32),
            pltpu.VMEM((h, 128), jnp.float32),
            pltpu.VMEM((h, rank), jnp.float32),
        ])
    out = pl.pallas_call(
        functools.partial(_mla_kernel, s=s, block_s=bs, scale=scale,
                          rank=rank),
        grid_spec=grid_spec,
        out_shape=_sds((b, h, rank), q.dtype, vma=_inherit_vma(q, cache)),
        name="decode_attn_mla",
        interpret=interpret,
    )(*scalars, q, cache)
    return zero_idle_rows(out, busy)
