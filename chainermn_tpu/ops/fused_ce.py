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
* **Backward** (``_dh_kernel`` / ``_dtable_kernel``): recompute each tile's
  probabilities from the saved LSE (``p = exp(s − lse)`` exactly), fold in
  the one-hot, and accumulate ``dh = ds @ table`` (V-sequential) and
  ``dtable = ds^T @ h`` (T-sequential) in fp32 VMEM scratch — the dQ/dKV
  recipe from ``flash_attention.py`` transplanted to the vocab axis.

Reference relationship: the reference had no LM head at all (SURVEY.md
§2.8); this is the "hand-write the hot kernel" perf identity
(``pure_nccl_communicator.py`` fused CUDA kernels [uv]) applied to the
biggest matmul in the modern stack.

TP composition: the kernels are shard-local.  ``fused_cross_entropy``
serves the single-shard case; the vocab-parallel path in
``parallel.transformer.vocab_parallel_logits_loss(ce_impl='fused')``
combines per-shard ``(m, l, picked)`` with the same pmax/psum legs as its
materializing form, then drives the backward kernels with the GLOBAL lse.
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


def _dh_kernel(h_ref, t_ref, tgt_ref, lse_ref, dnll_ref, dh_ref, dh_acc,
               *, block_t, block_v, num_vblocks):
    it, jv = pl.program_id(0), pl.program_id(1)

    @pl.when(jv == 0)
    def _init():
        dh_acc[...] = jnp.zeros_like(dh_acc)

    h = h_ref[...]
    tab = t_ref[...]
    s = jax.lax.dot_general(
        h, tab, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    lse = lse_ref[0, 0, pl.dslice(it * block_t, block_t)]
    dnll = dnll_ref[0, 0, pl.dslice(it * block_t, block_t)]
    p = jnp.exp(s - lse[:, None])
    tgt = tgt_ref[0, 0, pl.dslice(it * block_t, block_t)]
    local = tgt - jv * block_v
    col = jax.lax.broadcasted_iota(jnp.int32, (block_t, block_v), 1)
    ds = (p - jnp.where(col == local[:, None], 1.0, 0.0)) * dnll[:, None]
    dh_acc[...] += jax.lax.dot_general(
        ds.astype(tab.dtype), tab, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(jv == num_vblocks - 1)
    def _fin():
        dh_ref[...] = dh_acc[...].astype(dh_ref.dtype)


def _dtable_kernel(t_ref, h_ref, tgt_ref, lse_ref, dnll_ref, dt_ref, dt_acc,
                   *, block_t, block_v, num_tblocks):
    jv, it = pl.program_id(0), pl.program_id(1)

    @pl.when(it == 0)
    def _init():
        dt_acc[...] = jnp.zeros_like(dt_acc)

    h = h_ref[...]
    tab = t_ref[...]
    s = jax.lax.dot_general(
        h, tab, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)            # (block_t, block_v)
    lse = lse_ref[0, 0, pl.dslice(it * block_t, block_t)]
    dnll = dnll_ref[0, 0, pl.dslice(it * block_t, block_t)]
    p = jnp.exp(s - lse[:, None])
    tgt = tgt_ref[0, 0, pl.dslice(it * block_t, block_t)]
    local = tgt - jv * block_v
    col = jax.lax.broadcasted_iota(jnp.int32, (block_t, block_v), 1)
    ds = (p - jnp.where(col == local[:, None], 1.0, 0.0)) * dnll[:, None]
    dt_acc[...] += jax.lax.dot_general(
        ds.astype(h.dtype), h, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)            # (block_v, D)

    @pl.when(it == num_tblocks - 1)
    def _fin():
        dt_ref[...] = dt_acc[...].astype(dt_ref.dtype)


#: block and scratch bytes a kernel may hold before it asks for more than
#: the compiler's default scoped VMEM (16 MiB; the score tile's temporaries
#: come on top).  At d 1024 and the default blocks the largest kernel holds
#: 13 MiB and asks for nothing; at d 2304 the backward kernels hold 20-31.
_DEFAULT_VMEM_ROOM = 14 << 20


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


def ce_grads(h, table, targets, lse, dnll, block_t: int = 256,
             block_v: int = 1024, interpret: Optional[bool] = None):
    """Backward kernels: ``(dh, dtable)`` for per-row NLL cotangent
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

    dh = pl.pallas_call(
        functools.partial(_dh_kernel, block_t=bt, block_v=bv,
                          num_vblocks=v // bv),
        grid=(t // bt, v // bv),
        in_specs=[
            pl.BlockSpec((bt, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bv, d), lambda i, j: (j, 0)),
            pl.BlockSpec((1, 1, t), lambda i, j: (0, 0, 0)),
            pl.BlockSpec((1, 1, t), lambda i, j: (0, 0, 0)),
            pl.BlockSpec((1, 1, t), lambda i, j: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((bt, d), lambda i, j: (i, 0)),
        out_shape=_sds((t, d), h.dtype, vma=vma),
        scratch_shapes=[pltpu.VMEM((bt, d), jnp.float32)],
        compiler_params=_compiler_params(
            ("parallel", "arbitrary"),
            d * (bt * (4 * h.dtype.itemsize + 4)
                 + 2 * bv * table.dtype.itemsize)),
        name="fused_ce_dh",
        interpret=interpret,
    )(h, table, tgt_row, lse_row, dnll_row)

    dtable = pl.pallas_call(
        functools.partial(_dtable_kernel, block_t=bt, block_v=bv,
                          num_tblocks=t // bt),
        grid=(v // bv, t // bt),
        in_specs=[
            pl.BlockSpec((bv, d), lambda j, i: (j, 0)),
            pl.BlockSpec((bt, d), lambda j, i: (i, 0)),
            pl.BlockSpec((1, 1, t), lambda j, i: (0, 0, 0)),
            pl.BlockSpec((1, 1, t), lambda j, i: (0, 0, 0)),
            pl.BlockSpec((1, 1, t), lambda j, i: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((bv, d), lambda j, i: (j, 0)),
        out_shape=_sds((v, d), table.dtype, vma=vma),
        scratch_shapes=[pltpu.VMEM((bv, d), jnp.float32)],
        compiler_params=_compiler_params(
            ("parallel", "arbitrary"),
            d * (bv * (4 * table.dtype.itemsize + 4)
                 + 2 * bt * h.dtype.itemsize)),
        name="fused_ce_dtable",
        interpret=interpret,
    )(table, h, tgt_row, lse_row, dnll_row)
    return dh, dtable


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
