"""Fused softmax-cross-entropy over a large vocabulary (Pallas kernels).

The LM loss's last big non-MXU cost: ``logits = h @ table.T`` materializes
a ``(B·S, V)`` fp32 tensor (1 GB at the bench shape) that is written,
re-read for max/exp/sum/pick, and revisited by autodiff.  Same cure as
flash attention — the logits tile never leaves VMEM:

* **Forward** (``_stats_kernel``): grid ``(T/block_t, V/block_v)``, V
  sequential; each step matmuls an ``(block_t, D)×(D, block_v)`` tile on
  the MXU and folds it into online-softmax scratch (running max ``m``,
  rescaled ``sumexp l``, and the target logit picked via a one-hot
  reduction).  Outputs per-row ``(m, l, picked)`` — O(T) memory.
* **Backward** (``_grads_kernel``, ONE kernel: ``fused_ce_grads``):
  recompute each tile's probabilities from the saved LSE (``p = exp(s −
  lse)`` exactly), fold in the one-hot, and feed BOTH sums from that one
  tile: ``dh += ds @ table`` (V blocks ascending) and ``dtable += ds^T @
  h`` (T blocks ascending), both in fp32 — 8 T·V·D a step with the
  forward's 2, where two backward kernels that each formed the tile made
  it 10.  The sums run over different axes, so only one can stay put
  across the whole grid: the rows are walked in SUPER-BLOCKS whose fp32
  ``dh`` stays in VMEM scratch (``_DH_RESIDENT_BYTES``: 8192 rows of d
  1024 whole), a V block's ``dtable`` sums in scratch across a
  super-block's T blocks, and only where the rows outgrow one super-block
  is that sum carried from one to the next in HBM — a float32 ``(V, D)``
  buffer handed in as zeros and aliased to the result, re-read
  ``(nj − 1)·ni + 1`` grid steps after it was written (the revisit
  invariant in ``_grads_kernel``'s docstring: never under three).

Reference relationship: the reference had no LM head at all (SURVEY.md
§2.8); this is the "hand-write the hot kernel" perf identity
(``pure_nccl_communicator.py`` fused CUDA kernels [uv]) applied to the
biggest matmul in the modern stack.

TP composition: the kernels are shard-local.  ``fused_cross_entropy``
serves the single-shard case; the vocab-parallel path in
``parallel.transformer.vocab_parallel_logits_loss(ce_impl='fused')``
combines per-shard ``(m, l, picked)`` with the same pmax/psum legs as its
materializing form, then drives the backward kernel with the GLOBAL lse.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax

import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .._compat import pcast_varying
from .._compat import shape_dtype_struct as _sds
from .._compat import tpu_compiler_params as _tpu_compiler_params

from .flash_attention import _inherit_vma, _pick_aligned_block, _LANES

NEG_INF = -1e30


def _stats_kernel(h_ref, t_ref, tgt_ref, m_ref, l_ref, p_ref,
                  m_acc, l_acc, p_acc, *, block_t, block_v, num_vblocks):
    it, jv = pl.program_id(0), pl.program_id(1)

    @pl.when(jv == 0)
    def _init():
        m_acc[...] = jnp.full_like(m_acc, NEG_INF)
        l_acc[...] = jnp.zeros_like(l_acc)
        p_acc[...] = jnp.zeros_like(p_acc)

    h = h_ref[...]                                     # (block_t, D)
    tab = t_ref[...]                                   # (block_v, D)
    s = jax.lax.dot_general(
        h, tab, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)            # (block_t, block_v)

    tgt = tgt_ref[0, 0, pl.dslice(it * block_t, block_t)]   # (block_t,)
    local = tgt - jv * block_v
    col = jax.lax.broadcasted_iota(jnp.int32, (block_t, block_v), 1)
    onehot = col == local[:, None]
    p_acc[...] += jnp.broadcast_to(
        jnp.sum(jnp.where(onehot, s, 0.0), axis=1, keepdims=True),
        p_acc.shape)

    m_prev = m_acc[:, :1]
    m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
    l_acc[...] = (l_acc[...] * jnp.exp(m_prev - m_new)
                  + jnp.exp(s - m_new).sum(-1, keepdims=True))
    m_acc[...] = jnp.broadcast_to(m_new, m_acc.shape)

    @pl.when(jv == num_vblocks - 1)
    def _fin():
        m_ref[...] = m_acc[...]
        l_ref[...] = l_acc[...]
        p_ref[...] = p_acc[...]


def _grads_kernel(h_ref, t_ref, tgt_ref, lse_ref, dnll_ref, *rest, block_t,
                  block_v, carried):
    """One ``(block_t, block_v)`` logits tile, formed ONCE, feeds both sums.

    Grid ``(a, j, i)`` = (T super-block, V block, T block inside the
    super-block), all sequential.  ``dh_acc`` holds the float32 ``dh`` of a
    whole super-block across its ``(j, i)`` loops (V blocks ascending for
    every row) and leaves through ``dh_ref`` on the last V block; ``dt_acc``
    holds one V block's float32 ``dtable`` across ``i`` (T blocks
    ascending).  Where the rows outgrow one super-block (``carried``) the
    V block's sum is carried from super-block to super-block in HBM, in
    float32: ``dt_ref`` is then the float32 carrier itself, aliased to the
    zeros handed in as ``dt_in_ref``, read back at ``i == 0`` and written
    at the super-block's last ``i``.

    The revisit invariant: block ``j`` of the carrier, written back after
    step ``(a, j, ni - 1)``, is fetched again for step ``(a + 1, j, 0)``,
    ``(nj - 1)·ni + 1`` steps later.  The pipeline fetches a block one step
    ahead and waits for a write one step behind, so the fetch sees the write
    only from ``(nj - 1)·ni >= 2`` on: ``_super_block`` never cuts the rows
    into super-blocks of fewer than three blocks, and with ONE V block
    nothing is carried at all (a block whose index never changes is neither
    written back nor fetched again between steps: ``dt_acc`` just lives on).
    """
    if carried:
        dt_in_ref, dh_ref, dt_ref, dh_acc, dt_acc = rest
    else:
        dh_ref, dt_ref, dh_acc, dt_acc = rest
    a, jv, i = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    nv, ni = pl.num_programs(1), pl.num_programs(2)
    # this step's rows: in the whole (1, 1, T) vectors, in the super-block
    span = pl.dslice((a * ni + i) * block_t, block_t)
    rows = pl.dslice(pl.multiple_of(i * block_t, block_t), block_t)

    @pl.when(jv == 0)
    def _init_dh():
        dh_acc[rows, :] = jnp.zeros((block_t, dh_acc.shape[1]), jnp.float32)

    # with one V block ``dt_acc`` outlives the super-blocks (see above)
    @pl.when((i == 0) & ((a == 0) | (nv > 1)))
    def _init_dt():
        if carried:
            dt_acc[...] = dt_in_ref[...]
        else:
            dt_acc[...] = jnp.zeros_like(dt_acc)

    h = h_ref[...]                                     # (block_t, D)
    tab = t_ref[...]                                   # (block_v, D)
    s = jax.lax.dot_general(
        h, tab, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)            # (block_t, block_v)
    lse = lse_ref[0, 0, span]
    dnll = dnll_ref[0, 0, span]
    p = jnp.exp(s - lse[:, None])
    tgt = tgt_ref[0, 0, span]
    local = tgt - jv * block_v
    col = jax.lax.broadcasted_iota(jnp.int32, (block_t, block_v), 1)
    ds = (p - jnp.where(col == local[:, None], 1.0, 0.0)) * dnll[:, None]
    dh_acc[rows, :] += jax.lax.dot_general(
        ds.astype(tab.dtype), tab, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)            # (block_t, D)
    dt_acc[...] += jax.lax.dot_general(
        ds.astype(h.dtype), h, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)            # (block_v, D)

    @pl.when(jv == nv - 1)
    def _fin_dh():
        dh_ref[...] = dh_acc[rows, :].astype(dh_ref.dtype)

    @pl.when(i == ni - 1)
    def _fin_dt():
        dt_ref[...] = dt_acc[...].astype(dt_ref.dtype)


#: block and scratch bytes a kernel may hold before it asks for more than
#: the compiler's default scoped VMEM (16 MiB; the score tile's temporaries
#: come on top).  At the default blocks the forward holds 2 MiB at d 1024
#: and asks for nothing (11 MiB at d 2304); the backward holds its
#: super-block of float32 ``dh`` beside its blocks and asks wherever the
#: rows are many: 38.5 MiB at 8192 x 1024, 76.5 MiB at 16384 x 2304.
_DEFAULT_VMEM_ROOM = 14 << 20

#: float32 ``dh`` rows the backward keeps in VMEM at once (a super-block).
#: 8192 rows of d 1024 are 32 MiB and stay whole: nothing is carried in HBM.
#: 16384 rows of d 2304 (151 MB) are walked as eight super-blocks of 2048.
_DH_RESIDENT_BYTES = 32 << 20


def _compiler_params(semantics, block_bytes: int):
    """The kernel's compiler parameters: its grid semantics and, only where
    its blocks and scratch outgrow the default, a scoped-VMEM limit with
    room for the score tile's temporaries (a wide model: ``d`` sets every
    block's width)."""
    if block_bytes <= _DEFAULT_VMEM_ROOM:
        return _tpu_compiler_params(dimension_semantics=semantics)
    return _tpu_compiler_params(dimension_semantics=semantics,
                                vmem_limit_bytes=block_bytes + (16 << 20))


def _blocks_for(t, v, block_t, block_v):
    bt = _pick_aligned_block(t, block_t)
    bv = _pick_aligned_block(v, block_v)
    return bt, bv


def _vma_emulation(interpret, *xs) -> bool:
    """Interpreted Pallas cannot trace bodies whose operands carry
    varying-mesh-axes (multi-axis shard_map on CPU); those cases run an
    XLA emulation with identical math instead.  Standalone CPU calls (no
    vma) still exercise the real kernels in interpret mode, and TPU always
    compiles them."""
    return interpret and any(
        getattr(getattr(x, "aval", None), "vma", None) for x in xs)


def _stats_xla(h, table, targets):
    logits = jnp.einsum("td,vd->tv", h, table,
                        preferred_element_type=jnp.float32)
    m = logits.max(-1)
    l = jnp.exp(logits - m[:, None]).sum(-1)
    v = table.shape[0]
    onehot = (targets[:, None] == jnp.arange(v)[None, :])
    p = jnp.sum(jnp.where(onehot, logits, 0.0), axis=-1)
    return m, l, p


def _grads_xla(h, table, targets, lse, dnll):
    logits = jnp.einsum("td,vd->tv", h, table,
                        preferred_element_type=jnp.float32)
    v = table.shape[0]
    onehot = (targets[:, None] == jnp.arange(v)[None, :]).astype(jnp.float32)
    ds = (jnp.exp(logits - lse[:, None]) - onehot) * dnll[:, None]
    dh = jnp.einsum("tv,vd->td", ds.astype(table.dtype), table,
                    preferred_element_type=jnp.float32).astype(h.dtype)
    dtable = jnp.einsum("tv,td->vd", ds.astype(h.dtype), h,
                        preferred_element_type=jnp.float32).astype(table.dtype)
    return dh, dtable


def ce_stats(h, table, targets, block_t: int = 256, block_v: int = 1024,
             interpret: Optional[bool] = None):
    """Per-row softmax statistics without materializing logits.

    ``h (T, D)``, ``table (V, D)``, ``targets (T,) int32`` →
    ``(m, l, picked)`` each ``(T,)`` fp32: running max, sum of
    ``exp(s − m)``, and the target-column logit.  NOT differentiable —
    use :func:`fused_cross_entropy` (or the vocab-parallel wrapper) for
    gradients.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    t, d = h.shape
    v = table.shape[0]
    bt, bv = _blocks_for(t, v, block_t, block_v)
    if not (bt and bv):
        raise ValueError(
            f"T={t}, V={v} admit no Mosaic-aligned blocks ≤ ({block_t}, "
            f"{block_v}); pad T to a multiple of 8")
    if _vma_emulation(interpret, h, table):
        return _stats_xla(h, table, targets)
    vma = _inherit_vma(h, table)
    tgt_row = targets.astype(jnp.int32)[None, None, :]       # (1, 1, T)
    kern = functools.partial(_stats_kernel, block_t=bt, block_v=bv,
                             num_vblocks=v // bv)
    m, l, p = pl.pallas_call(
        kern,
        grid=(t // bt, v // bv),
        in_specs=[
            pl.BlockSpec((bt, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bv, d), lambda i, j: (j, 0)),
            pl.BlockSpec((1, 1, t), lambda i, j: (0, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bt, _LANES), lambda i, j: (i, 0)),
            pl.BlockSpec((bt, _LANES), lambda i, j: (i, 0)),
            pl.BlockSpec((bt, _LANES), lambda i, j: (i, 0)),
        ],
        out_shape=[_sds((t, _LANES), jnp.float32, vma=vma)
                   for _ in range(3)],
        scratch_shapes=[pltpu.VMEM((bt, _LANES), jnp.float32)
                        for _ in range(3)],
        compiler_params=_compiler_params(
            ("parallel", "arbitrary"),
            2 * d * (bt * h.dtype.itemsize + bv * table.dtype.itemsize)),
        name="fused_ce_stats",
        interpret=interpret,
    )(h, table, tgt_row)
    return m[:, 0], l[:, 0], p[:, 0]


def _super_block(nt: int, row_bytes: int) -> int:
    """T blocks a super-block: the most that divide ``nt`` and whose float32
    ``dh`` rows fit ``_DH_RESIDENT_BYTES`` — but never fewer than three
    where the rows are cut at all (``_grads_kernel``'s revisit invariant)."""
    divisors = [k for k in range(1, nt + 1) if nt % k == 0]
    least = next(k for k in divisors if k >= min(3, nt))
    fits = [k for k in divisors if k * row_bytes <= _DH_RESIDENT_BYTES]
    return max(fits[-1] if fits else 1, least)


def ce_grads(h, table, targets, lse, dnll, block_t: int = 256,
             block_v: int = 1024, interpret: Optional[bool] = None):
    """Backward kernel: ``(dh, dtable)`` for per-row NLL cotangent
    ``dnll (T,)`` given the (possibly globally-combined) ``lse (T,)``."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    t, d = h.shape
    v = table.shape[0]
    bt, bv = _blocks_for(t, v, block_t, block_v)
    if not (bt and bv):
        raise ValueError(
            f"T={t}, V={v} admit no Mosaic-aligned blocks ≤ ({block_t}, "
            f"{block_v}); pad T to a multiple of 8")
    if _vma_emulation(interpret, h, table):
        return _grads_xla(h, table, targets, lse, dnll)
    vma = _inherit_vma(h, table)
    tgt_row = targets.astype(jnp.int32)[None, None, :]
    lse_row = lse.astype(jnp.float32)[None, None, :]
    dnll_row = dnll.astype(jnp.float32)[None, None, :]

    nv, nt = v // bv, t // bt
    # the plain interpreter keeps an aliased input apart from its result, so
    # a carry would read back the zeros it was handed: it walks all rows as
    # one super-block (it has no VMEM to outgrow)
    ni = nt if interpret is True else _super_block(nt, bt * d * 4)
    na = nt // ni
    # dtable's sum over the super-blocks is carried in HBM, in float32
    carried = na > 1 and nv > 1
    dt_dtype = jnp.dtype(jnp.float32) if carried else table.dtype
    operands = [h, table, tgt_row, lse_row, dnll_row]
    row = pl.BlockSpec((1, 1, t), lambda a, j, i: (0, 0, 0))
    dt_spec = pl.BlockSpec((bv, d), lambda a, j, i: (j, 0))
    in_specs = [pl.BlockSpec((bt, d), lambda a, j, i: (a * ni + i, 0)),
                dt_spec, row, row, row]
    if carried:
        zeros = jnp.zeros((v, d), jnp.float32)
        for ax in sorted(vma):
            zeros = pcast_varying(zeros, ax)
        operands.append(zeros)
        in_specs.append(dt_spec)
    dh, dtable = pl.pallas_call(
        functools.partial(_grads_kernel, block_t=bt, block_v=bv,
                          carried=carried),
        grid=(na, nv, ni),
        in_specs=in_specs,
        out_specs=[
            # a row block leaves on the last V block; until then the index
            # rests on the super-block's first, which nothing writes back
            pl.BlockSpec((bt, d), lambda a, j, i: (
                a * ni + jnp.where(j == nv - 1, i, 0), 0)),
            dt_spec,
        ],
        out_shape=[_sds((t, d), h.dtype, vma=vma),
                   _sds((v, d), dt_dtype, vma=vma)],
        scratch_shapes=[pltpu.VMEM((ni * bt, d), jnp.float32),
                        pltpu.VMEM((bv, d), jnp.float32)],
        input_output_aliases={5: 1} if carried else {},
        # both scratches, then each block twice (the pipeline's two
        # buffers): h and dh, table, dtable out and, carried, in
        compiler_params=_compiler_params(
            ("arbitrary", "arbitrary", "arbitrary"),
            d * (4 * (ni * bt + bv) + 4 * bt * h.dtype.itemsize
                 + 2 * bv * (table.dtype.itemsize + dt_dtype.itemsize
                             + 4 * carried))),
        name="fused_ce_grads",
        interpret=interpret,
    )(*operands)
    return dh, dtable.astype(table.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def fused_cross_entropy(h, table, targets, block_t: int = 256,
                        block_v: int = 1024,
                        interpret: Optional[bool] = None):
    """Per-row NLL ``(T,)`` of ``softmax(h @ table.T)`` at ``targets`` —
    O(T) memory, logits tiles live only in VMEM, forward and backward.

    ``h (T, D)`` (flatten batch×sequence first), ``table (V, D)``,
    ``targets (T,) int32``.  Differentiable w.r.t. ``h`` and ``table``.
    Single-shard form; the vocab-parallel composition lives in
    ``parallel.transformer.vocab_parallel_logits_loss``.
    """
    m, l, p = ce_stats(h, table, targets, block_t, block_v, interpret)
    return m + jnp.log(l) - p


def _fce_fwd(h, table, targets, block_t, block_v, interpret):
    m, l, p = ce_stats(h, table, targets, block_t, block_v, interpret)
    lse = m + jnp.log(l)
    return lse - p, (h, table, targets, lse)


def _fce_bwd(block_t, block_v, interpret, res, dnll):
    h, table, targets, lse = res
    dh, dtable = ce_grads(h, table, targets, lse, dnll, block_t, block_v,
                          interpret)
    return dh, dtable, None


fused_cross_entropy.defvjp(_fce_fwd, _fce_bwd)
