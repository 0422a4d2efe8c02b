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

Backward: ONE fused Pallas kernel.  Grid is K-major with (group, Q)
sequential: dk/dv accumulate in fp32 VMEM scratch (the GQA head-group fold
happens in-scratch), while each cell's dq contribution is written as a
per-K-block PARTIAL slab — input dtype, summed in fp32 by one XLA reduce —
because K-major cells visit a given q block non-consecutively (no scratch
residency) and HBM read-modify-write aliasing would race the block
prefetch at diagonal corners.  Probabilities recompute from the saved LSE
(``p = exp(s − lse)`` is the exact softmax, no renormalisation pass).
O(S·block) live memory in VMEM, an O(nk·S·D) HBM transient for the dq
partials.  A ``lax.scan`` XLA fallback (``backward='xla'``) covers
Mosaic-hostile block geometries and serves as the oracle in tests.  On CPU
(tests, debugging) the kernels run in Pallas interpret mode; the math is
identical.

The causal (and padded-tail) schedule, both kernels: the GRID stays coarse
— a grid step costs about as much as a 128 × 128 score tile at head 64 —
so the unit of skipping and of masking is a SUB-BLOCK inside a grid cell
(``_SUB_BLOCK``; :func:`causal_schedule`).  Grid cells wholly above the
diagonal are skipped and their dead block DMA elided by index-map
clamping, as before.  Inside a cell that runs, each Q sub-block computes
only the K sub-blocks at or below its diagonal, as one product as wide as
those columns, and pays the mask's iota / compare / select only on the
sub-blocks the diagonal (or a padded tail) crosses.  The schedule is
static: every distinct way a cell is computed (its *plan*) is one
straight-line body, all of the cell's Q sub-blocks in one region, and the
cell picks its plan from its program ids.  ``causal=False`` with no tail
has one plan, the undivided cell.  A WINDOW (``window=W``: query ``q`` sees
keys ``0 <= q - k < W``), forward and backward, is one more edge of the
same schedule: grid cells wholly left of the band (in the K-major backward:
Q blocks wholly below it) are skipped and their DMA elided like those above
the diagonal, and inside a cell each Q sub-block starts its one product at
the first K sub-block the band reaches and masks those the band's left edge
crosses, as it masks those the diagonal crosses.  The banded kernels carry
their own names, ``window_flash_fwd`` and ``window_flash_bwd``.

Layout: ``(B, S, H, D)`` — the same convention as ``parallel/``'s ring and
Ulysses attention, which uses this kernel for its local (post-all-to-all)
attention when ``attn_impl='flash'``.
"""

from __future__ import annotations

import functools
import itertools
from typing import Optional

import jax

import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .._compat import shape_dtype_struct as _sds
from .._compat import tpu_compiler_params as _tpu_compiler_params
from ..observability import trace as _trace

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


# Edge of a sub-block inside a grid cell (causal_schedule).  Found on the
# chip at the benchmark cells' shapes (scripts/tune_flash_bwd.py; PERF.md
# Findings PR 30): at B 8, H 16, S 1024, D 64 the forward reads 0.378 ms at
# 128, 0.398 at 256, 0.453 at 512 (the undivided parent 0.737) and the
# backward 0.907 / 0.956 / 1.019 (1.221); the latent prefill (H 128, D 192,
# S 1024) 0.669 / 0.705 / 0.821 (1.213).
_SUB_BLOCK = 128


def _sub_block(block: int) -> int:
    """Edge of the sub-blocks a grid cell's ``block`` rows (or columns) are
    walked in: the largest lane multiple ≤ ``_SUB_BLOCK`` that divides it
    (sub-block slices then start on a 128 boundary, which the backward's
    LSE row slices and packed bf16 tiles need), else the whole block."""
    for b in range(min(_SUB_BLOCK, block) // _LANES * _LANES, 0, -_LANES):
        if block % b == 0:
            return b
    return block


def _k_sub_range(row0, col0, sub_q, sub_k, n_sub_k, causal, seq_len,
                 least=jnp.minimum, most=jnp.maximum):
    """``(n_full, n_run)`` for the Q sub-block of ``sub_q`` rows at ``row0``
    against a cell's ``n_sub_k`` K sub-blocks, the first at column ``col0``:
    sub-blocks ``[0, n_full)`` lie wholly below the diagonal and inside the
    real sequence (no mask), ``[n_full, n_run)`` are crossed by the diagonal
    or hold the padded tail (masked body), the rest are never computed.
    Works on program ids inside a kernel and, with ``least=min,
    most=max``, on plain ints (:func:`causal_schedule`)."""
    n_full = n_run = n_sub_k
    if causal:
        d = row0 - col0
        n_full = least(most(d + 1, 0) // sub_k, n_full)
        n_run = least(most(d + sub_q + sub_k - 1, 0) // sub_k, n_run)
    if seq_len is not None:
        live = most(seq_len - col0, 0)          # real columns from col0 on
        n_full = least(live // sub_k, n_full)
        n_run = least((live + sub_k - 1) // sub_k, n_run)
        # a Q sub-block that holds padded rows is masked throughout (the
        # backward masks them), one of nothing but padded rows is skipped
        n_full = n_full * most(least(seq_len - row0 - sub_q + 1, 1), 0)
        n_run = n_run * most(least(seq_len - row0, 1), 0)
    return n_full, n_run


def _k_band_range(row0, col0, sub_q, sub_k, n_sub_k, causal, seq_len, window,
                  least=jnp.minimum, most=jnp.maximum):
    """:func:`_k_sub_range` under a window, as ``(n_skip, n_edge, n_full,
    n_masked)``: of the cell's K sub-blocks the first ``n_skip`` lie wholly
    left of the band (``q - k >= window`` for every pair) and are never
    computed, the next ``n_edge`` are crossed by the band's left edge
    (masked), the next ``n_full`` need no mask, the next ``n_masked`` are
    crossed by the diagonal or the padded tail (masked), the rest are never
    computed.  All zero where nothing is computed."""
    n_full, n_run = _k_sub_range(row0, col0, sub_q, sub_k, n_sub_k, causal,
                                 seq_len, least, most)
    d = row0 - col0
    skip = least(most(d - window + 1, 0) // sub_k, n_run)
    # from here on no pair is left of the band
    clear = most(d + sub_q - 1 - window + sub_k, 0) // sub_k
    edge_end = least(most(clear, skip), n_run)
    full_end = least(most(n_full, edge_end), n_run)
    some = least(n_run - skip, 1)              # 0: nothing is computed
    return (skip * some, (edge_end - skip) * some,
            (full_end - edge_end) * some, (n_run - full_end) * some)


def causal_schedule(s: int, block_q: int, block_k: int, causal: bool = True,
                    seq_len: Optional[int] = None,
                    window: Optional[int] = None) -> dict:
    """The static schedule of one head's ``s × s`` score matrix under a
    ``block_q × block_k`` grid: the sub-block edges a cell is divided by
    (the whole block where nothing is masked: ``causal=False``, no tail),
    how many (Q sub-block, K sub-block) pairs are computed at all
    (``run``), computed under the mask (``masked``) and in the square
    (``total``), and the ``plans``: every distinct way a grid cell is
    computed, as one ``(n_full, n_masked)`` for each of its Q sub-blocks —
    of the cell's K sub-blocks the first ``n_full`` unmasked, the next
    ``n_masked`` masked, the rest not at all.  The kernels hold one static
    body per plan and pick a cell's from its program ids with the same
    :func:`_k_sub_range`.  ``seq_len < s`` says where a padded tail
    begins.  With ``window`` (causal only) a Q sub-block's entry is
    :func:`_k_band_range`'s ``(n_skip, n_edge, n_full, n_masked)``."""
    if seq_len == s:
        seq_len = None
    if window is not None and not causal:
        raise ValueError("a window is the causal band 0 <= q - k < window")
    divide = causal or seq_len is not None
    sub_q = _sub_block(block_q) if divide else block_q
    sub_k = _sub_block(block_k) if divide else block_k
    n_sub_k = block_k // sub_k
    run = masked = 0
    plans = set()
    for cell_row0 in range(0, s, block_q):
        for col0 in range(0, s, block_k):
            plan = []
            for row0 in range(cell_row0, cell_row0 + block_q, sub_q):
                if window is not None:
                    entry = _k_band_range(
                        row0, col0, sub_q, sub_k, n_sub_k, causal, seq_len,
                        window, least=min, most=max)
                    run += sum(entry[1:])
                    masked += entry[1] + entry[3]
                    plan.append(entry)
                    continue
                n_full, n_run = _k_sub_range(
                    row0, col0, sub_q, sub_k, n_sub_k, causal, seq_len,
                    least=min, most=max)
                run += n_run
                masked += n_run - n_full
                plan.append((n_full, n_run - n_full))
            plans.add(tuple(plan))
    return {"sub_q": sub_q, "sub_k": sub_k, "n_sub_k": n_sub_k, "run": run,
            "masked": masked, "total": (s // sub_q) * (s // sub_k),
            "plans": tuple(sorted(plans)), "window": window}


def _count_score_blocks(schedule: dict, heads: int) -> None:
    """Book one traced kernel call's schedule (all ``heads`` of it) with the
    tracer, as ``comm/<op>`` is booked: at trace time, off when disabled."""
    tr = _trace.get_tracer()
    for key in ("run", "masked", "total"):
        tr.add_counter(f"flash/score_blocks_{key}", heads * schedule[key])


def _run_plan(schedule, cell_row0, cell_col0, causal, seq_len, body) -> None:
    """Give the grid cell at ``(cell_row0, cell_col0)`` the static body of
    its plan, all of it in ONE straight-line region, chosen by comparing
    what :func:`_k_sub_range` finds for each of its Q sub-blocks from the
    program ids with each plan of ``schedule``.

    ``body(first, count, *outcome)`` — ``(n_full, n_masked)``, under a
    window ``(n_skip, n_edge, n_full, n_masked)`` — is a GENERATOR for the
    run of ``count`` Q sub-blocks from ``first`` that share an outcome; it
    yields
    between its phases (scores; softmax; products), and the runs of a cell
    are advanced in lockstep, so the region reads: every run's score
    products, then every run's vector work, then every run's second
    products.  Program order is where the chip's scheduler starts from:
    with four runs a cell this order measured 20 % (forward) and 11 %
    (backward) faster than run after run, the widest run first another 3 %,
    and a loop over sub-blocks — whose iterations cannot overlap at all,
    each serialising two MXU round trips behind its row maximum — up to
    2.5 x SLOWER than the undivided cell (PERF.md Findings PR 30)."""
    def run(plan):
        # neighbours that compute the same columns go as one taller run (a
        # cell wholly below the diagonal is then the undivided cell)
        runs = []
        for outcome, group in itertools.groupby(enumerate(plan),
                                                lambda e: e[1]):
            first = next(group)[0]
            runs.append(body(first, 1 + sum(1 for _ in group), *outcome))
        runs.reverse()      # the widest (under causal: the last) run first
        done = object()
        while runs:
            runs = [r for r in runs if next(r, done) is not done]

    # (a lone plan goes through the same test: always true, and the kernel's
    # work stays inside a region, which interpret mode under shard_map's
    # vma check needs — it types constants only there)
    sub_q, sub_k, plans = (schedule[key] for key in ("sub_q", "sub_k", "plans"))
    window = schedule["window"]
    if window is not None:
        found = [_k_band_range(cell_row0 + i * sub_q, cell_col0, sub_q,
                               sub_k, schedule["n_sub_k"], causal, seq_len,
                               window) for i in range(len(plans[0]))]
        for plan in plans:
            same = [a == b for got, want in zip(found, plan)
                    for a, b in zip(got, want)]
            pl.when(functools.reduce(jnp.logical_and, same))(
                functools.partial(run, plan))
        return
    found = [_k_sub_range(cell_row0 + i * sub_q, cell_col0, sub_q, sub_k,
                          schedule["n_sub_k"], causal, seq_len)
             for i in range(len(plans[0]))]
    for plan in plans:
        same = [jnp.logical_and(n_full == full, n_run == full + n_masked)
                for (n_full, n_run), (full, n_masked) in zip(found, plan)]
        pl.when(functools.reduce(jnp.logical_and, same))(
            functools.partial(run, plan))


def _score_mask(row0, col0, rows, cols, causal, seq_len, mask_q_tail,
                window=None):
    """The keep-mask of the ``rows × cols`` scores at ``(row0, col0)``: the
    causal triangle (under ``window`` the band ``0 <= q - k < window``)
    and/or the real sequence."""
    q_pos = row0 + jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 0)
    k_pos = col0 + jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 1)
    mask = (q_pos >= k_pos) if causal else None
    if window is not None:
        mask = jnp.logical_and(mask, q_pos - k_pos < window)
    if seq_len is not None:
        tail = k_pos < seq_len
        if mask_q_tail:
            tail = jnp.logical_and(tail, q_pos < seq_len)
        mask = tail if mask is None else jnp.logical_and(mask, tail)
    return mask


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *state, scale, causal,
                block_q, block_k, schedule, num_kblocks, seq_len):
    iq, ik = pl.program_id(1), pl.program_id(2)
    sub_q, sub_k = schedule["sub_q"], schedule["sub_k"]
    # One K block holds every row whole: nothing to carry from cell to
    # cell, so no scratch (``state`` is empty), no zeroing of it and no
    # rescaling by exp(m_prev - m_new) — a sixth of the undivided cell's
    # instructions at S = 1024.
    carried = num_kblocks > 1

    def finish(rows, m, l, acc):
        """Write the rows' output and log-sum-exp from their final row
        maximum, row sum (either ``(rows, 1)`` or lane-replicated) and
        unnormalised output."""
        l = jnp.maximum(l, 1e-37)
        o_ref[0, rows, :] = (acc / l[:, :1]).astype(o_ref.dtype)
        # LSE is lane-replicated (rows, LANES) — Mosaic needs the last two
        # block dims tileable; callers slice [..., 0].
        lse_ref[0, rows, :] = jnp.broadcast_to(m + jnp.log(l),
                                               (acc.shape[0], _LANES))

    if carried:
        acc_ref, m_ref, l_ref = state

        @pl.when(ik == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)
            m_ref[...] = jnp.full_like(m_ref, NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)

    def body(first, count, *outcome):
        """Softmax update of the ``count`` Q sub-blocks from ``first`` with
        the cell's first ``n_full`` K sub-blocks unmasked and the next
        ``n_masked`` under the mask (causal triangle; a padded S's tail:
        those K positions must contribute nothing); the K sub-blocks
        beyond, wholly above the diagonal or wholly padding, are never
        computed.  Under a window the columns start behind the ``n_skip``
        sub-blocks left of the band, and the ``n_edge`` that its left edge
        crosses are masked too."""
        n_skip, n_edge, n_full, n_masked = \
            outcome if len(outcome) == 4 else (0, 0) + outcome
        rows = slice(first * sub_q, (first + count) * sub_q)
        height = count * sub_q
        row0 = iq * block_q + first * sub_q
        start, edge = n_skip * sub_k, (n_skip + n_edge) * sub_k
        lo = edge + n_full * sub_k
        hi = lo + n_masked * sub_k
        if hi == start:     # nothing of this cell's: a carried state stands
            if not carried:  # (rows of padding only: no cell writes them)
                finish(rows, jnp.full((height, 1), NEG_INF, jnp.float32),
                       jnp.zeros((height, 1), jnp.float32),
                       jnp.zeros((height, o_ref.shape[-1]), jnp.float32))
            return

        def on_masked(x, fill):
            """``fill`` where the mask hides a score: of ``x``'s columns
            ``[start, hi)`` those before ``edge`` and those from ``lo`` on
            — the only ones it can hide."""
            def columns(a, b, masked):
                part = x if (a, b) == (start, hi) else \
                    x[:, a - start:b - start]
                if not masked:
                    return part
                return jnp.where(_score_mask(
                    row0, ik * block_k + a, height, b - a, causal, seq_len,
                    False, schedule["window"]), part, fill)

            parts = [columns(a, b, masked) for a, b, masked in (
                (start, edge, True), (edge, lo, False), (lo, hi, True))
                if b > a]
            return parts[0] if len(parts) == 1 else jnp.concatenate(parts, 1)

        s = on_masked(jax.lax.dot_general(
            q_ref[0, rows, :], k_ref[0, start:hi, :],
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale,  # (height, hi - start)
            NEG_INF)
        yield
        m = s.max(-1, keepdims=True)                   # (height, 1)
        if carried:
            m_prev = m_ref[rows, :1]
            m = jnp.maximum(m_prev, m)
        # NEG_INF is finite, so exp(s - m) alone would turn fully-masked
        # rows into 1s — multiply by the mask explicitly.
        p = on_masked(jnp.exp(s - m), 0.0)
        l = p.sum(-1, keepdims=True)
        yield
        v = v_ref[0, start:hi, :]
        acc = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        if not carried:
            finish(rows, m, l, acc)
            return
        alpha = jnp.exp(m_prev - m)                    # (height, 1)
        acc_ref[rows, :] = acc_ref[rows, :] * alpha + acc
        m_ref[rows, :] = jnp.broadcast_to(m, (height, _LANES))
        l_ref[rows, :] = jnp.broadcast_to(l_ref[rows, :1] * alpha + l,
                                          (height, _LANES))

    _run_plan(schedule, iq * block_q, ik * block_k, causal, seq_len, body)

    if carried:
        # For causal, the last contributing K block for this Q block is
        # the one covering the diagonal, not num_kblocks-1.
        if causal:
            last_ik = jnp.minimum(
                (iq * block_q + block_q - 1) // block_k, num_kblocks - 1)
        else:
            last_ik = num_kblocks - 1

        @pl.when(ik == last_ik)
        def _finalize():
            finish(slice(None), m_ref[...], l_ref[...], acc_ref[...])


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
               group: int = 1, window: Optional[int] = None):
    """``q (B·H, S, D)``, ``k/v (B·H/group, S, D)``: ``group`` consecutive
    q heads share one KV head (GQA/MQA).  The sharing happens in the
    BlockSpec index_map — KV is never materialized at H heads."""
    bh, s, d = q.shape
    bq = _pick_aligned_block(s, block_q)
    bk = _pick_aligned_block(s, block_k)
    assert bq and bk, (s, block_q, block_k)  # wrapper pads unalignable S
    nq, nk = s // bq, s // bk
    vma = _inherit_vma(q, k, v)
    schedule = causal_schedule(s, bq, bk, causal, seq_len, window)
    _count_score_blocks(schedule, bh)

    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal,
        block_q=bq, block_k=bk, schedule=schedule, num_kblocks=nk,
        seq_len=None if seq_len == s else seq_len)

    def kv_index(b, i, j):
        # Causal: K blocks past the diagonal are pl.when-skipped — clamp
        # their index to the diagonal block so Pallas's revisit detection
        # elides the (otherwise dead) K/V DMA for the whole skipped tail.
        if causal:
            j = jnp.minimum(j, (i * bq + bq - 1) // bk)
        if window is not None:      # and those wholly left of the band
            j = jnp.maximum(j, jnp.maximum(i * bq - window + 1, 0) // bk)
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
        # the online-softmax state, carried from K block to K block
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, _LANES), jnp.float32),
            pltpu.VMEM((bq, _LANES), jnp.float32),
        ] if nk > 1 else [],
        compiler_params=_tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        # the profiler's ops line calls a kernel by this name: the banded
        # forward's is its own, and still holds ``flash_fwd``
        name="flash_fwd" if window is None else "window_flash_fwd",
        interpret=interpret,
    )(q, k, v)
    return out, lse[..., 0]


def _bwd_blockwise(q, k, v, out, lse, do, causal, scale, block_k, seq_len,
                   dlse=None, window=None):
    """Memory-efficient backward: scan over K blocks, recomputing p from
    the saved LSE.  All operands (BH, S, D); returns (dq, dk, dv).
    ``window``: the band ``0 <= q - k < window`` as one more term of the
    mask (every K block is still computed: the fallback, not the schedule).

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
            if window is not None:
                mask = mask & (q_pos[:, None] - k_pos[None, :] < window)
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
                      causal, block_q, block_k, schedule, num_qblocks,
                      group, seq_len):
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
    sub_q, sub_k = schedule["sub_q"], schedule["sub_k"]

    @pl.when(jnp.logical_and(g == 0, iq == 0))
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def body(first, count, *outcome):
        """The ``count`` Q sub-blocks from ``first`` against the cell's
        first ``n_full`` K sub-blocks unmasked and the next ``n_masked``
        under the mask; their dq for this cell is one float32 product over
        both, rounded to the slab's dtype once.  With nothing to compute
        they write zeros: the sum outside reads every slab slice, and an
        unwritten one would be uninitialized memory.  Under a window the
        columns start behind the ``n_skip`` sub-blocks left of the band,
        and the ``n_edge`` that its left edge crosses are masked too."""
        n_skip, n_edge, n_full, n_masked = \
            outcome if len(outcome) == 4 else (0, 0) + outcome
        rows = slice(first * sub_q, (first + count) * sub_q)
        height = count * sub_q
        row0 = iq * block_q + first * sub_q
        if sub_q % _LANES == 0:
            row0 = pl.multiple_of(row0, _LANES)
        start, edge = n_skip * sub_k, (n_skip + n_edge) * sub_k
        lo = edge + n_full * sub_k
        hi = lo + n_masked * sub_k
        if hi == start:
            dqp_ref[0, 0, rows, :] = jnp.zeros_like(dqp_ref[0, 0, rows, :])
            return
        q, do = q_ref[0, rows, :], do_ref[0, rows, :]
        k, v = k_ref[0, start:hi, :], v_ref[0, start:hi, :]
        lse = lse_ref[0, 0, pl.ds(row0, height)]
        delta = delta_ref[0, 0, pl.ds(row0, height)]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (height, hi - start)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        yield
        p = jnp.exp(s - lse[:, None])
        if n_edge or n_masked:
            # only the columns before ``edge`` and from ``lo`` on can be
            # hidden; the hidden spans are written first (program order is
            # where the chip's scheduler starts from: ``_run_plan``)
            def columns(a, b, masked):
                if not masked:
                    return p[:, a - start:b - start]
                return jnp.where(_score_mask(
                    row0, jk * block_k + a, height, b - a, causal, seq_len,
                    True, schedule["window"]), p[:, a - start:b - start], 0.0)

            spans = [span for span in ((start, edge, True), (edge, lo, False),
                                       (lo, hi, True)) if span[1] > span[0]]
            done = {span: columns(*span)
                    for span in sorted(spans, key=lambda span: not span[2])}
            p = done[spans[0]] if len(spans) == 1 else jnp.concatenate(
                [done[span] for span in spans], 1)
        ds = p * (dp - delta[:, None]) * scale
        yield
        dv_acc[start:hi, :] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)              # (hi - start, d)
        dk_acc[start:hi, :] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dqp_ref[0, 0, rows, :] = jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(dqp_ref.dtype)

    _run_plan(schedule, iq * block_q, jk * block_k, causal, seq_len, body)

    @pl.when(jnp.logical_and(g == group - 1, iq == num_qblocks - 1))
    def _fin():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _bwd_pallas(q, k, v, out, lse, do, causal, scale, block_q, block_k,
                interpret, seq_len, group, dlse=None, window=None):
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
    schedule = causal_schedule(s, bq, bk, causal, seq_len, window)
    _count_score_blocks(schedule, bh)

    def qdo_index(b, j, g, i):
        # Q blocks strictly above the diagonal (i·bq + bq − 1 < j·bk) are
        # pl.when-skipped — clamp them up to the first contributing block
        # so Pallas's revisit detection elides their dead Q/dO DMA
        if causal:
            i = jnp.maximum(i, (j * bk) // bq)
        if window is not None:      # and those wholly below the band
            i = jnp.minimum(i, (j * bk + bk + window - 2) // bq)
        return (b * group + g, i, 0)

    dq_part, dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_fused_kernel, scale=scale, causal=causal, block_q=bq,
            block_k=bk, schedule=schedule, num_qblocks=nq, group=group,
            seq_len=sl),
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
        # the banded backward's name is its own, and still holds
        # ``flash_bwd`` (as the forward's)
        name="flash_bwd" if window is None else "window_flash_bwd",
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
             dlse=None, window=None):
    """GQA backward: recompute with KV expanded to the full q-head count,
    then fold the shared-head gradient groups back down.  The expansion is
    backward-only and O(S·D·H) — dominated by the (BH, S, block) score
    recompute the blockwise backward already carries."""
    dq, dk, dv = _bwd_blockwise(
        q, _expand_kv(k, group), _expand_kv(v, group), out, lse, do,
        causal, scale, block_k, seq_len, dlse=dlse, window=window)
    return dq, _fold_dkv(dk, group).astype(k.dtype), \
        _fold_dkv(dv, group).astype(v.dtype)


_BWD_BLOCK_Q = 512   # backward tiles: the 5-matmul body needs coarse
_BWD_BLOCK_K = 2048  # blocks to amortise grid overhead (v5e-tuned; the
# S=16384 hunt measured bwd 0.374 MFU at 512x2048 vs 0.315 at the
# forward-optimal 1024x1024 — fwd and bwd optima DIFFER, so the backward
# no longer inherits the forward's blocks; scripts/tune_flash_bwd.py)


def _bwd_dispatch(q, k, v, out, lse, do, causal, scale, block_q, block_k,
                  interpret, seq_len, group, backward, dlse=None,
                  bwd_block_q=None, bwd_block_k=None, window=None):
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
                           bwd_bk, interpret, seq_len, group, dlse=dlse,
                           window=window)
    if backward != "xla":
        raise ValueError(
            f"backward must be 'auto', 'pallas' or 'xla', got {backward!r}")
    return _bwd_gqa(q, k, v, out, lse, do, causal, scale, bwd_bk,
                    seq_len, group, dlse=dlse, window=window)


_STATIC = tuple(range(3, 13))    # every argument after q, k, v
_STATIC_LSE = _STATIC[:-1]       # the LSE face takes no window


@functools.partial(jax.custom_vjp, nondiff_argnums=_STATIC)
def _flash_bhsd(q, k, v, causal, block_q, block_k, interpret, seq_len, group,
                backward, bwd_block_q=None, bwd_block_k=None, window=None):
    scale = 1.0 / (q.shape[-1] ** 0.5)
    out, _ = _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret,
                        seq_len, group, window)
    return out


def _flash_bhsd_fwd(q, k, v, causal, block_q, block_k, interpret, seq_len,
                    group, backward, bwd_block_q=None, bwd_block_k=None,
                    window=None):
    scale = 1.0 / (q.shape[-1] ** 0.5)
    out, lse = _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret,
                          seq_len, group, window)
    return out, (q, k, v, out, lse)


def _flash_bhsd_bwd(causal, block_q, block_k, interpret, seq_len, group,
                    backward, bwd_block_q, bwd_block_k, window, res, do):
    q, k, v, out, lse = res
    scale = 1.0 / (q.shape[-1] ** 0.5)
    return _bwd_dispatch(q, k, v, out, lse, do, causal, scale, block_q,
                         block_k, interpret, seq_len, group, backward,
                         bwd_block_q=bwd_block_q, bwd_block_k=bwd_block_k,
                         window=window)


_flash_bhsd.defvjp(_flash_bhsd_fwd, _flash_bhsd_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=_STATIC_LSE)
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

# A model calls these once a layer with the same shapes; under jit the
# kernels (forward, and backward through the pjit's own rules) are traced
# and lowered once per shape and not once per call — a kernel body holds
# every plan of its schedule, and 24 layers of them were seconds of set-up.
_flash_bhsd_jit = jax.jit(_flash_bhsd, static_argnums=_STATIC)
_flash_bhsd_lse_jit = jax.jit(_flash_bhsd_lse, static_argnums=_STATIC_LSE)


def flash_attention(q, k, v, causal: bool = False,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: Optional[bool] = None,
                    return_lse: bool = False, backward: str = "auto",
                    bwd_block_q: Optional[int] = None,
                    bwd_block_k: Optional[int] = None,
                    window: Optional[int] = None):
    """Flash attention over ``(B, S, H, D)`` arrays.

    ``interpret=None`` auto-selects: the compiled Pallas kernel on TPU,
    interpret mode elsewhere (CPU tests — same math, no Mosaic).  When ``S``
    is a multiple of a reasonable block, blocks shrink to the largest
    Mosaic-legal divisor (full-size or 8-row aligned); otherwise
    (prime/small-factor S) ``S`` is padded up to the next lane multiple and
    the tail masked inside the kernel.  Differentiable via the blockwise
    LSE backward; O(S·block) live memory both directions.

    Default blocks (``block_q/block_k=None``): 512 × 1024, and 1024 × 1024
    from S ≥ 2048 (the fp32 score tile, 4 MB, still fits VMEM); explicit
    values are always honored.  The grid is kept that coarse because a grid
    step is dear at head 64; what a cell computes is decided per SUB-BLOCK
    of 128 (``_SUB_BLOCK``, :func:`causal_schedule`): with ``causal=True``
    each run of Q rows computes one product over the K sub-blocks at or
    below its diagonal and masks only those the diagonal crosses, so at
    S = 1024 the kernels compute 36 of the 64 sub-block pairs and mask 8,
    where one cell per K block computed and masked all 64.  Where one K
    block holds the whole row (S ≤ 1024 at the defaults) the forward
    carries no online-softmax state at all.  Measured on the v5e under jax
    0.9.0 (scan-chained, ``scripts/tune_flash_bwd.py``; PERF.md Findings
    PR 30), undivided cell → this schedule: B 8, H 16, S 1024, D 64
    causal, forward 0.737 → 0.378 ms, backward 1.221 → 0.907 ms; the same
    shape not causal, forward 0.752 → 0.531 ms (no carried state), backward
    unchanged; H 128, D 192 causal forward, S 1024 1.213 → 0.669 ms,
    S 3072 7.30 → 5.87 ms; B 2, H 16, S 8192, D 64, forward 5.71 → 5.46 ms,
    backward 10.66 → 9.31 ms.

    ``backward`` selects the gradient path: ``'pallas'`` — the ONE fused
    dq/dk/dv kernel (blockwise LSE recompute in VMEM, fp32 dk/dv scratch,
    input-dtype dq partials + fp32 XLA sum, the same sub-block schedule,
    dead cells' DMA elided, GQA group-fold in-scratch);
    ``'xla'`` — the lax.scan blockwise recompute; ``'auto'`` — Pallas
    whenever the block geometry is Mosaic-aligned (any S that is a multiple
    of 128 after padding), else XLA.

    ``bwd_block_q``/``bwd_block_k`` (default None → 512 × 2048) tile the
    BACKWARD independently of the forward: its five-product body wants a
    wider K block than the forward's two.

    ``window=W`` (with ``causal=True``): query ``q`` sees keys ``0 <= q - k
    < W`` — itself and the ``W - 1`` before it.  The band is one more edge
    of the sub-block schedule (module docstring): at S = 3072, W = 512 a row
    of Q sub-blocks computes 5 K sub-blocks, 2 of them masked, where the
    causal schedule computes up to 24.  The kernels are then named
    ``window_flash_fwd`` and ``window_flash_bwd``: the gradient runs the
    same fused dQ/dK/dV kernel on the band's plan (at S = 8192, W = 1024,
    540 of the causal schedule's 2080 sub-block pairs a head; ``W >= S``
    degenerates to the causal kernel's work), and the XLA scan
    (``backward='xla'``) carries the band as one more term of its mask.

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

    if window is not None and (return_lse or not causal or window < 1):
        raise ValueError("window needs causal=True, window >= 1 and "
                         "return_lse=False")
    if return_lse:
        out, lse = _flash_bhsd_lse_jit(
            to_bhsd(q), to_bhsd(k), to_bhsd(v), causal, block_q, block_k,
            interpret, s, group, backward, bwd_block_q, bwd_block_k)
        return (out.reshape(b, h, s_pad, d)[:, :, :s].transpose(0, 2, 1, 3),
                lse.reshape(b, h, s_pad)[:, :, :s])
    out = _flash_bhsd_jit(to_bhsd(q), to_bhsd(k), to_bhsd(v),
                          causal, block_q, block_k, interpret, s, group,
                          backward, bwd_block_q, bwd_block_k, window)
    return out.reshape(b, h, s_pad, d)[:, :, :s].transpose(0, 2, 1, 3)
