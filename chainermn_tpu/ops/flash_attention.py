"""Flash attention as Pallas TPU kernels, forward and backward.

Reference relationship: the reference's only runtime-compiled device code
was CuPy's fused cast/scale CUDA kernels on the allreduce path
(``chainermn/communicators/pure_nccl_communicator.py`` [uv], SURVEY.md
§2.7); attention itself predates it entirely.  This is the TPU-native
analog of "hand-write the hot kernel": the O(S²) score matrix never
touches HBM — Q/K/V stream through VMEM in MXU-sized tiles and the online-
softmax state (m, l, acc) lives in VMEM scratch across the K-block grid
dimension (pallas_guide.md §4/§8 revolving-accumulator pattern).

Forward: one Pallas kernel, grid ``(B·H, S/block_q, S/block_k)``, the last
dimension sequential ("arbitrary") so scratch accumulates across K blocks.
Saves the log-sum-exp alongside the output.

Backward: ONE fused Pallas kernel (round 4; previously a dQ + dKV pair
that recomputed ``qk``/``do·v`` twice and read the operands from HBM
twice).  Grid is K-major with (group, Q) sequential: dk/dv accumulate in
fp32 VMEM scratch (the GQA head-group fold happens in-scratch), while
each cell's dq contribution is written as a per-K-block PARTIAL slab —
input dtype, summed in fp32 by one XLA reduce — because K-major cells
visit a given q block non-consecutively (no scratch residency) and HBM
read-modify-write aliasing would race the block prefetch at diagonal
corners.  Probabilities recompute from the saved LSE (``p = exp(s −
lse)`` is the exact softmax, no renormalisation pass); causal
above-diagonal cells are skipped AND their dead block DMA elided by
index-map clamping.  O(S·block) live memory in VMEM, an O(nk·S·D)
HBM transient for the dq partials.  A
``lax.scan`` XLA fallback (``backward='xla'``) covers Mosaic-hostile
block geometries and serves as the oracle in tests.  On CPU (tests,
debugging) the kernels run in Pallas interpret mode; the math is
identical.

Layout: ``(B, S, H, D)`` — the same convention as ``parallel/``'s ring and
Ulysses attention, which uses this kernel for its local (post-all-to-all)
attention when ``attn_impl='flash'``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax

import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .._compat import shape_dtype_struct as _sds
from .._compat import tpu_compiler_params as _tpu_compiler_params

NEG_INF = -1e30
_LANES = 128  # TPU vector lane count: scratch vectors are (block_q, 128)
_MIN_BLOCK = 8  # fp32 sublane tile; divisor blocks below this are Mosaic-
                # hostile (prime S degrades to 1), so we pad+mask instead


def resolve_attn_impl(attn_impl: str, seq_len: int) -> str:
    """Resolve ``'auto'`` to a concrete attention implementation.

    ``'flash'`` (this module's Pallas kernels) on a TPU backend for
    non-trivial sequences — measured ≥5× faster than the materializing
    path at S=1024 on v5e and O(block) memory at long S; the materializing
    ``'xla'`` path for tiny sequences (grid overhead dominates) and for
    CPU runs (interpret-mode Pallas is a per-cell Python loop — tests
    force it explicitly when they mean to).  Explicit names pass through
    untouched."""
    if attn_impl != "auto":
        return attn_impl
    if jax.default_backend() == "tpu" and seq_len >= 128:
        return "flash"
    return "xla"


def _pick_block(s: int, want: int) -> int:
    """Largest block ≤ want that divides s (static shapes, no padding)."""
    for b in range(min(want, s), 0, -1):
        if s % b == 0:
            return b
    return 1


def _pick_aligned_block(s: int, want: int) -> int:
    """Largest MOSAIC-LEGAL block ≤ ``want`` dividing ``s``: either the
    full dimension (always legal) or a multiple of the 8-row sublane tile.
    Returns 0 when none exists — the caller must pad ``s``.  (A divisor
    like 100 for S=200 passes the old ≥8 test but is neither full-size nor
    8-aligned, which Mosaic rejects at lowering.)"""
    if s <= want:
        return s
    for b in range(min(want, s), _MIN_BLOCK - 1, -1):
        if s % b == 0 and b % _MIN_BLOCK == 0:
            return b
    return 0


def _pick_lane_block(s: int, want: int) -> int:
    """Largest LANE-multiple (128) divisor of ``s`` ≤ ``want`` — the
    backward's Pallas kernels slice (1, 1, S) LSE/delta rows at lane-dim
    offset iq·block_q, which compiled Mosaic requires 128-aligned, so the
    q-block must be a 128-multiple.  Preferring 128-multiple divisors keeps
    shapes like S=640 (→128) and S=1280 (→256) on the Pallas path where the
    plain 8-aligned pick would return 320 and silently fall back to the XLA
    scan (round-4 advisor finding).  Falls back to the 8-aligned pick when
    no 128-multiple divisor exists (the dispatch check then routes to XLA).
    """
    for b in range(min(want, s) // _LANES * _LANES, 0, -_LANES):
        if s % b == 0:
            return b
    return _pick_aligned_block(s, want)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                acc_ref, m_ref, l_ref, *, scale, causal,
                block_q, block_k, num_kblocks, seq_len):
    iq, ik = pl.program_id(1), pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # seq_len < the padded S means a masked tail (prime/odd S padded up to
    # the block size); those K positions must contribute nothing.
    tail = seq_len is not None

    # Causal: K blocks entirely above the diagonal contribute nothing —
    # skip their matmuls (≈2× FLOP saving at long S).  Fully-padded K
    # blocks likewise.
    run = (ik * block_k <= iq * block_q + block_q - 1) if causal else True
    if tail:
        run = jnp.logical_and(run, ik * block_k < seq_len)

    @pl.when(run)
    def _body():
        q = q_ref[0]                                   # (block_q, D)
        k = k_ref[0]                                   # (block_k, D)
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (block_q, block_k)

        mask = None
        if causal or tail:
            q_pos = iq * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = ik * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            mask = (q_pos >= k_pos) if causal else (k_pos == k_pos)
            if tail:
                mask = jnp.logical_and(mask, k_pos < seq_len)
            s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:, :1]                          # (block_q, 1)
        l_prev = l_ref[:, :1]
        m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
        # NEG_INF is finite, so exp(s - m_new) alone would turn fully-masked
        # rows into 1s — multiply by the mask explicitly.
        p = jnp.exp(s - m_new)
        if mask is not None:
            p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)                # (block_q, 1)
        l_new = l_prev * alpha + p.sum(-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    # For causal, the last contributing K block for this Q block is the one
    # covering the diagonal, not num_kblocks-1.
    if causal:
        last_ik = jnp.minimum(
            (iq * block_q + block_q - 1) // block_k, num_kblocks - 1)
    else:
        last_ik = num_kblocks - 1

    @pl.when(ik == last_ik)
    def _finalize():
        l = l_ref[:, :1]
        o_ref[0] = (acc_ref[...] / jnp.maximum(l, 1e-37)).astype(o_ref.dtype)
        # LSE is lane-replicated (block_q, LANES) — Mosaic needs the last
        # two block dims tileable; callers slice [..., 0].
        lse_ref[0] = m_ref[...] + jnp.log(jnp.maximum(l_ref[...], 1e-37))


def _inherit_vma(*xs) -> frozenset:
    """Union of the inputs' varying-mesh-axes sets — pallas_call inside
    shard_map requires out_shapes to declare how outputs vary."""
    vma = set()
    for x in xs:
        aval = getattr(x, "aval", None)
        v = getattr(aval, "vma", None)
        if v:
            vma |= set(v)
    return frozenset(vma)


def _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret, seq_len,
               group: int = 1):
    """``q (B·H, S, D)``, ``k/v (B·H/group, S, D)``: ``group`` consecutive
    q heads share one KV head (GQA/MQA).  The sharing happens in the
    BlockSpec index_map — KV is never materialized at H heads."""
    bh, s, d = q.shape
    bq = _pick_aligned_block(s, block_q)
    bk = _pick_aligned_block(s, block_k)
    assert bq and bk, (s, block_q, block_k)  # wrapper pads unalignable S
    nq, nk = s // bq, s // bk
    vma = _inherit_vma(q, k, v)

    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal,
        block_q=bq, block_k=bk, num_kblocks=nk,
        seq_len=None if seq_len == s else seq_len)

    def kv_index(b, i, j):
        # Causal: K blocks past the diagonal are pl.when-skipped — clamp
        # their index to the diagonal block so Pallas's revisit detection
        # elides the (otherwise dead) K/V DMA for the whole skipped tail.
        if causal:
            j = jnp.minimum(j, (i * bq + bq - 1) // bk)
        return (b // group, j, 0)

    out, lse = pl.pallas_call(
        kernel,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, d), kv_index),
            pl.BlockSpec((1, bk, d), kv_index),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, _LANES), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            _sds((bh, s, d), q.dtype, vma=vma),
            _sds((bh, s, _LANES), jnp.float32, vma=vma),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, _LANES), jnp.float32),
            pltpu.VMEM((bq, _LANES), jnp.float32),
        ],
        compiler_params=_tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="flash_fwd",
        interpret=interpret,
    )(q, k, v)
    return out, lse[..., 0]


def _bwd_blockwise(q, k, v, out, lse, do, causal, scale, block_k, seq_len,
                   dlse=None):
    """Memory-efficient backward: scan over K blocks, recomputing p from
    the saved LSE.  All operands (BH, S, D); returns (dq, dk, dv).

    ``dlse``: cotangent of the LSE output when the caller differentiates
    through it (ring attention's block-merge weights).  Since
    ∂lse_i/∂s_ij = p_ij, it folds into the score cotangent as
    ``ds = p * (dp - delta + dlse)``; v gets no extra term (lse is
    v-independent)."""
    bh, s, d = q.shape
    bk = _pick_block(s, block_k)
    nk = s // bk
    tail = seq_len != s
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)                                  # (BH, S)
    q_pos = jnp.arange(s)

    def step(dq_acc, ik):
        kb = jax.lax.dynamic_slice_in_dim(k, ik * bk, bk, axis=1)
        vb = jax.lax.dynamic_slice_in_dim(v, ik * bk, bk, axis=1)
        sc = jnp.einsum("bqd,bkd->bqk", q, kb,
                        preferred_element_type=jnp.float32) * scale
        p = jnp.exp(sc - lse[..., None])                      # exact softmax
        if causal or tail:
            k_pos = ik * bk + jnp.arange(bk)
            mask = (q_pos[:, None] >= k_pos[None, :] if causal
                    else jnp.ones((s, bk), bool))
            if tail:
                # Padded q rows have lse ≈ NEG_INF, making exp() overflow to
                # inf; padded k columns must contribute nothing.  Mask both.
                mask = (mask & (k_pos[None, :] < seq_len)
                        & (q_pos[:, None] < seq_len))
            p = jnp.where(mask[None], p, 0.0)
        dv_b = jnp.einsum("bqk,bqd->bkd", p.astype(do.dtype), do,
                          preferred_element_type=jnp.float32)
        dp = jnp.einsum("bqd,bkd->bqk", do, vb,
                        preferred_element_type=jnp.float32)
        dsoft = dp - delta[..., None]
        if dlse is not None:
            dsoft = dsoft + dlse[..., None]
        ds = p * dsoft * scale                                # (BH, S, bk)
        dq_acc = dq_acc + jnp.einsum("bqk,bkd->bqd", ds.astype(kb.dtype), kb,
                                     preferred_element_type=jnp.float32)
        dk_b = jnp.einsum("bqk,bqd->bkd", ds.astype(q.dtype), q,
                          preferred_element_type=jnp.float32)
        return dq_acc, (dk_b, dv_b)

    # The accumulator must carry q's varying-axes type (scan demands
    # carry-in/out agree inside shard_map) WITHOUT inheriting q's values —
    # `q * 0` would smear one inf/NaN in q into an all-NaN dq.
    from .collective import zeros_like_vma

    dq, (dks, dvs) = jax.lax.scan(
        step, zeros_like_vma(q, jnp.float32), jnp.arange(nk))
    # (nk, BH, bk, D) → (BH, nk·bk=S, D); blocks were emitted in order.
    dk = dks.transpose(1, 0, 2, 3).reshape(bh, s, d)
    dv = dvs.transpose(1, 0, 2, 3).reshape(bh, s, d)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


def _bwd_fused_kernel(k_ref, v_ref, q_ref, do_ref, lse_ref, delta_ref,
                      dqp_ref, dk_ref, dv_ref, dk_acc, dv_acc, *, scale,
                      causal, block_q, block_k, num_qblocks, group, seq_len):
    """Fused backward: ONE kernel produces dk, dv AND dq.

    Grid ``(B·H_kv, S/block_k, group, S/block_q)`` with the (group, Q)
    dims sequential — one K block's dk/dv accumulate over every q head
    sharing it (the GQA fold happens IN the scratch, in fp32) and every Q
    block, exactly as the old dK/dV kernel did.  The difference: the
    ``ds·k`` product this cell already has in registers ALSO yields this
    (q-block, k-block) cell's dq contribution, so the old separate dQ
    kernel — which re-did the qk and do·v matmuls and re-read q/k/v/do
    from HBM — is gone (2 of 7 backward matmuls and half the backward
    input DMA, measured +21% backward at S=8192, docs/PERF.md round 4).

    dq contributions cannot accumulate in scratch here (the grid is
    K-major; a q block's contributions arrive across non-consecutive
    cells) and HBM read-modify-write via input/output aliasing would race
    Pallas's block prefetch at the diagonal corners, so each K block
    writes its dq PARTIAL to its own ``(B·H, nk, S, D)`` slab slice and
    one XLA sum over nk finishes the job — O(nk·S·D) fp32 transient,
    ~0.7 ms of the ~5 ms the fusion saves at S=8192."""
    jk, g, iq = pl.program_id(1), pl.program_id(2), pl.program_id(3)

    @pl.when(jnp.logical_and(g == 0, iq == 0))
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    tail = seq_len is not None
    run = (iq * block_q + block_q - 1 >= jk * block_k) if causal else True
    if tail:
        run = jnp.logical_and(run, iq * block_q < seq_len)

    @pl.when(run)
    def _body():
        k, v, q, do = k_ref[0], v_ref[0], q_ref[0], do_ref[0]
        lse = lse_ref[0, 0, pl.dslice(iq * block_q, block_q)]
        delta = delta_ref[0, 0, pl.dslice(iq * block_q, block_q)]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (bq, bk)
        p = jnp.exp(s - lse[:, None])
        if causal or tail:
            q_pos = iq * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = jk * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            mask = (q_pos >= k_pos) if causal else (k_pos == k_pos)
            if tail:
                mask = jnp.logical_and(
                    mask, jnp.logical_and(k_pos < seq_len, q_pos < seq_len))
            p = jnp.where(mask, p, 0.0)
        dv_acc[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # (bk, d)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * scale
        dk_acc[...] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dqp_ref[0, 0] = jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(dqp_ref.dtype)  # bf16 partial: fp32 sum outside

    @pl.when(jnp.logical_not(run))
    def _skip():
        # this cell's partial slice is summed unconditionally outside —
        # unwritten blocks would be uninitialized memory, not zeros
        dqp_ref[0, 0] = jnp.zeros_like(dqp_ref[0, 0])

    @pl.when(jnp.logical_and(g == group - 1, iq == num_qblocks - 1))
    def _fin():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _bwd_pallas(q, k, v, out, lse, do, causal, scale, block_q, block_k,
                interpret, seq_len, group, dlse=None):
    """Pallas dq/dk/dv via the ONE fused kernel (see
    :func:`_bwd_fused_kernel`), sharing one XLA-precomputed
    ``delta = rowsum(do·out) − dlse`` (the LSE cotangent folds in exactly:
    ``ds = p·(dp − delta + dlse)``).  Same blockwise-LSE math as
    :func:`_bwd_blockwise`, but the (S, block) score recompute never leaves
    VMEM and the GQA head-group fold happens in the fp32 scratch."""
    bh, s, d = q.shape
    bh_kv = k.shape[0]
    bq = _pick_block(s, block_q)
    bk = _pick_block(s, block_k)
    nq, nk = s // bq, s // bk
    vma = _inherit_vma(q, k, v, do)
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)                                    # (BH, S)
    if dlse is not None:
        delta = delta - dlse
    # (BH, 1, S): full-row trailing dims satisfy Mosaic's block alignment
    # for any block_q; kernels slice their q block dynamically.
    lse = lse.astype(jnp.float32)[:, None, :]
    delta = delta[:, None, :]
    sl = None if seq_len == s else seq_len

    def qdo_index(b, j, g, i):
        # Q blocks strictly above the diagonal (i·bq + bq − 1 < j·bk) are
        # pl.when-skipped — clamp them up to the first contributing block
        # so Pallas's revisit detection elides their dead Q/dO DMA
        if causal:
            i = jnp.maximum(i, (j * bk) // bq)
        return (b * group + g, i, 0)

    dq_part, dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_fused_kernel, scale=scale, causal=causal, block_q=bq,
            block_k=bk, num_qblocks=nq, group=group, seq_len=sl),
        grid=(bh_kv, nk, group, nq),
        in_specs=[
            pl.BlockSpec((1, bk, d), lambda b, j, g, i: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, j, g, i: (b, j, 0)),
            pl.BlockSpec((1, bq, d), qdo_index),
            pl.BlockSpec((1, bq, d), qdo_index),
            pl.BlockSpec((1, 1, s), lambda b, j, g, i: (b * group + g, 0, 0)),
            pl.BlockSpec((1, 1, s), lambda b, j, g, i: (b * group + g, 0, 0)),
        ],
        out_specs=[
            # dq partials: UNclamped index — dead cells write their own
            # zero slice (the sum below reads every slab slice)
            pl.BlockSpec((1, 1, bq, d),
                         lambda b, j, g, i: (b * group + g, j, i, 0)),
            pl.BlockSpec((1, bk, d), lambda b, j, g, i: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, j, g, i: (b, j, 0)),
        ],
        out_shape=[
            # partials in the INPUT dtype: bf16 models halve the slab
            # traffic at the cost of rounding each of the nk per-K-block
            # partials to bf16 BEFORE the fp32 sum (the sum itself adds no
            # further error) — dq error vs the fp32-slab path measured
            # ~0.5% relative, inside bf16 training noise, and pinned by
            # the bf16 gradient parity test; fp32 callers (ring
            # attention's fp32-grade parity) keep a full-precision slab
            _sds((bh, nk, s, d), q.dtype, vma=vma),
            _sds((bh_kv, s, d), k.dtype, vma=vma),
            _sds((bh_kv, s, d), v.dtype, vma=vma),
        ],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        compiler_params=_tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary",
                                 "arbitrary")),
        name="flash_bwd",
        interpret=interpret,
    )(k, v, q, do, lse, delta)
    dq = dq_part.astype(jnp.float32).sum(axis=1).astype(q.dtype)
    return dq, dk, dv


def _expand_kv(x, group):
    """(B·Hkv, S, D) → (B·H, S, D) by repeating each KV head ``group``
    times (backward-only; the forward shares via the index_map)."""
    if group == 1:
        return x
    return jnp.repeat(x, group, axis=0)


def _fold_dkv(dx, group):
    """(B·H, S, D) grads → (B·Hkv, S, D): sum the shared-head group in fp32
    (an MQA group can be 32+ heads; a bf16 tree-sum would shed low-order
    gradient mass) — callers cast back to the KV dtype."""
    if group == 1:
        return dx
    bh, s, d = dx.shape
    return dx.reshape(bh // group, group, s, d).astype(jnp.float32).sum(1)


def _bwd_gqa(q, k, v, out, lse, do, causal, scale, block_k, seq_len, group,
             dlse=None):
    """GQA backward: recompute with KV expanded to the full q-head count,
    then fold the shared-head gradient groups back down.  The expansion is
    backward-only and O(S·D·H) — dominated by the (BH, S, block) score
    recompute the blockwise backward already carries."""
    dq, dk, dv = _bwd_blockwise(
        q, _expand_kv(k, group), _expand_kv(v, group), out, lse, do,
        causal, scale, block_k, seq_len, dlse=dlse)
    return dq, _fold_dkv(dk, group).astype(k.dtype), \
        _fold_dkv(dv, group).astype(v.dtype)


_BWD_BLOCK_Q = 512   # backward tiles: the 5-matmul body needs coarse
_BWD_BLOCK_K = 2048  # blocks to amortise grid overhead (v5e-tuned; the
# S=16384 hunt measured bwd 0.374 MFU at 512x2048 vs 0.315 at the
# forward-optimal 1024x1024 — fwd and bwd optima DIFFER, so the backward
# no longer inherits the forward's blocks; scripts/tune_flash_bwd.py)


def _bwd_dispatch(q, k, v, out, lse, do, causal, scale, block_q, block_k,
                  interpret, seq_len, group, backward, dlse=None,
                  bwd_block_q=None, bwd_block_k=None):
    """Route to the Pallas dq/dk/dv kernels (``'pallas'``), the XLA
    blockwise scan (``'xla'``), or pick automatically (``'auto'``: Pallas
    whenever the block geometry is Mosaic-aligned — which on TPU with the
    default blocks is every realistic shape).  Backward tiles are chosen
    independently of the forward's (``bwd_block_q``/``bwd_block_k``,
    default the v5e-tuned ``_BWD_BLOCK_*``): the two optima measurably
    differ, and an explicit value is honored even when finer than the
    default."""
    s = q.shape[1]
    # Backward blocks are INDEPENDENT of the forward's: the optima differ
    # (S=16384: fwd wants 1024x1024, bwd wants 512x2048 — 19% apart), so
    # callers' forward tuning no longer drags the backward with it.
    # Explicit bwd_block_q/bwd_block_k on flash_attention override.
    # Default q block: prefer 128-multiple divisors (lane-aligned LSE
    # slices, see below).  An EXPLICIT bwd_block_q keeps the plain
    # 8-aligned pick so the caller's value is honored verbatim — and a
    # non-lane explicit block still fails loudly on backward='pallas'
    # instead of being silently swapped for a smaller tile.
    bwd_bq = (_pick_block if interpret else
              _pick_aligned_block if bwd_block_q else _pick_lane_block)(
        s, bwd_block_q or _BWD_BLOCK_Q)
    bwd_bk = (_pick_block if interpret else _pick_aligned_block)(
        s, bwd_block_k or _BWD_BLOCK_K)
    # The kernels slice the (1, 1, S) LSE/delta rows at lane-dim offset
    # iq·block_q — compiled Mosaic wants those slices 128-aligned, so the
    # Pallas path needs a 128-multiple q block.  _pick_lane_block prefers
    # 128-multiple divisors of S, so the real condition is: S has a
    # 128-multiple divisor ≤ the q-block budget (every multiple of 128
    # qualifies; e.g. S=640 → block 128).  Anything else — e.g. S=200 —
    # falls back to the XLA scan.
    ok = interpret or (bwd_bq % _LANES == 0)
    if backward == "auto":
        backward = "pallas" if ok else "xla"
    elif backward == "pallas" and not ok:
        raise ValueError(
            f"pallas backward needs a q block that is a multiple of "
            f"{_LANES} after shrinking to divide S={s} (got {bwd_bq}); "
            f"pad S to a multiple of {_LANES} or use backward='xla'")
    if backward == "pallas":
        return _bwd_pallas(q, k, v, out, lse, do, causal, scale, bwd_bq,
                           bwd_bk, interpret, seq_len, group, dlse=dlse)
    if backward != "xla":
        raise ValueError(
            f"backward must be 'auto', 'pallas' or 'xla', got {backward!r}")
    return _bwd_gqa(q, k, v, out, lse, do, causal, scale, bwd_bk,
                    seq_len, group, dlse=dlse)


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10, 11))
def _flash_bhsd(q, k, v, causal, block_q, block_k, interpret, seq_len, group,
                backward, bwd_block_q=None, bwd_block_k=None):
    scale = 1.0 / (q.shape[-1] ** 0.5)
    out, _ = _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret,
                        seq_len, group)
    return out


def _flash_bhsd_fwd(q, k, v, causal, block_q, block_k, interpret, seq_len,
                    group, backward, bwd_block_q=None, bwd_block_k=None):
    scale = 1.0 / (q.shape[-1] ** 0.5)
    out, lse = _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret,
                          seq_len, group)
    return out, (q, k, v, out, lse)


def _flash_bhsd_bwd(causal, block_q, block_k, interpret, seq_len, group,
                    backward, bwd_block_q, bwd_block_k, res, do):
    q, k, v, out, lse = res
    scale = 1.0 / (q.shape[-1] ** 0.5)
    return _bwd_dispatch(q, k, v, out, lse, do, causal, scale, block_q,
                         block_k, interpret, seq_len, group, backward,
                         bwd_block_q=bwd_block_q, bwd_block_k=bwd_block_k)


_flash_bhsd.defvjp(_flash_bhsd_fwd, _flash_bhsd_bwd)


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10, 11))
def _flash_bhsd_lse(q, k, v, causal, block_q, block_k, interpret, seq_len,
                    group, backward, bwd_block_q=None, bwd_block_k=None):
    """Like :func:`_flash_bhsd` but also returns the LSE as a DIFFERENTIABLE
    output — ring attention merges visiting blocks with LSE-derived weights,
    so gradients must flow through it."""
    scale = 1.0 / (q.shape[-1] ** 0.5)
    return _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret,
                      seq_len, group)


def _flash_bhsd_lse_fwd(q, k, v, causal, block_q, block_k, interpret,
                        seq_len, group, backward,
                        bwd_block_q=None, bwd_block_k=None):
    scale = 1.0 / (q.shape[-1] ** 0.5)
    out, lse = _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret,
                          seq_len, group)
    return (out, lse), (q, k, v, out, lse)


def _flash_bhsd_lse_bwd(causal, block_q, block_k, interpret, seq_len,
                        group, backward, bwd_block_q, bwd_block_k, res, cts):
    q, k, v, out, lse = res
    do, dlse = cts
    scale = 1.0 / (q.shape[-1] ** 0.5)
    return _bwd_dispatch(q, k, v, out, lse, do, causal, scale, block_q,
                         block_k, interpret, seq_len, group, backward,
                         dlse=dlse, bwd_block_q=bwd_block_q,
                         bwd_block_k=bwd_block_k)


_flash_bhsd_lse.defvjp(_flash_bhsd_lse_fwd, _flash_bhsd_lse_bwd)


def flash_attention(q, k, v, causal: bool = False,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: Optional[bool] = None,
                    return_lse: bool = False, backward: str = "auto",
                    bwd_block_q: Optional[int] = None,
                    bwd_block_k: Optional[int] = None):
    """Flash attention over ``(B, S, H, D)`` arrays.

    ``interpret=None`` auto-selects: the compiled Pallas kernel on TPU,
    interpret mode elsewhere (CPU tests — same math, no Mosaic).  When ``S``
    is a multiple of a reasonable block, blocks shrink to the largest
    Mosaic-legal divisor (full-size or 8-row aligned); otherwise
    (prime/small-factor S) ``S`` is padded up to the next lane multiple and
    the tail masked inside the kernel.  Differentiable via the blockwise
    LSE backward; O(S·block) live memory both directions.

    Default blocks (``block_q/block_k=None``) are tuned on TPU v5e:
    128×128 leaves the grid too fine (measured ~5× slower at S=1024 —
    per-cell overhead dominates the two (block_q × d × block_k) MXU
    issues).  512×1024 amortises it at short S; from S ≥ 2048 the
    forward measurably prefers 1024×1024 (S=8192: 6.11 → 4.92 ms,
    docs/PERF.md long-context round 4) and the fp32 score tile (4 MB)
    still fits VMEM, so the q block widens automatically.  Explicit
    values are always honored.

    ``backward`` selects the gradient path: ``'pallas'`` — the ONE fused
    dq/dk/dv kernel (blockwise LSE recompute in VMEM, fp32 dk/dv scratch,
    input-dtype dq partials + fp32 XLA sum, causal cells skipped with
    their DMA elided, GQA group-fold in-scratch);
    ``'xla'`` — the lax.scan blockwise recompute; ``'auto'`` — Pallas
    whenever the block geometry is Mosaic-aligned (any S that is a multiple
    of 128 after padding), else XLA.

    ``bwd_block_q``/``bwd_block_k`` (default None → 512x2048, v5e-tuned)
    tile the BACKWARD independently of the forward: the optima differ
    (S=16384 measured: bwd 512x2048 vs the forward-optimal 1024x1024 is
    ~2-5% end to end; S=4096 fwd+bwd improved 0.30 → 0.47 attn-MFU when
    the backward stopped inheriting the forward's 1024-wide q block).

    ``return_lse=True`` additionally returns the per-query log-sum-exp
    ``(B, H, S)`` as a differentiable output (the block-merge currency of
    ring attention).

    GQA/MQA: ``k``/``v`` may carry FEWER heads than ``q`` (``H_kv`` with
    ``H % H_kv == 0``); each group of ``H/H_kv`` consecutive q heads
    attends the shared KV head.  The sharing is done in the kernel's block
    index map — KV never materializes at ``H`` heads in the forward.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, s, h, d = q.shape
    h_kv = k.shape[2]
    if h % h_kv:
        raise ValueError(
            f"q heads {h} not a multiple of kv heads {h_kv} (GQA contract)")
    if v.shape[2] != h_kv:
        raise ValueError(f"k has {h_kv} heads but v has {v.shape[2]}")
    group = h // h_kv
    if block_q is None:
        block_q = 1024 if s >= 2048 else 512
    if block_k is None:
        block_k = 1024
    block_q = max(block_q, _MIN_BLOCK)
    block_k = max(block_k, _MIN_BLOCK)
    s_pad = s
    if not (_pick_aligned_block(s, block_q)
            and _pick_aligned_block(s, block_k)):
        # No Mosaic-legal block divides S (prime/small-divisor lengths):
        # pad to the next lane multiple — 128 | s_pad guarantees an aligned
        # block ≥ min(block, 128) exists, and keeps the padding overhead
        # O(128) instead of the old round-up to lcm(block_q, block_k).
        s_pad = -(-s // _LANES) * _LANES
        pad = [(0, 0), (0, s_pad - s), (0, 0), (0, 0)]
        q, k, v = (jnp.pad(x, pad) for x in (q, k, v))

    def to_bhsd(x):
        nh = x.shape[2]
        return x.transpose(0, 2, 1, 3).reshape(b * nh, s_pad, x.shape[-1])

    if return_lse:
        out, lse = _flash_bhsd_lse(to_bhsd(q), to_bhsd(k), to_bhsd(v),
                                   causal, block_q, block_k, interpret, s,
                                   group, backward, bwd_block_q, bwd_block_k)
        return (out.reshape(b, h, s_pad, d)[:, :, :s].transpose(0, 2, 1, 3),
                lse.reshape(b, h, s_pad)[:, :, :s])
    out = _flash_bhsd(to_bhsd(q), to_bhsd(k), to_bhsd(v),
                      causal, block_q, block_k, interpret, s, group,
                      backward, bwd_block_q, bwd_block_k)
    return out.reshape(b, h, s_pad, d)[:, :, :s].transpose(0, 2, 1, 3)
