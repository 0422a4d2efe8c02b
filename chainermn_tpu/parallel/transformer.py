"""Tensor-parallel Transformer LM: Megatron-style sharding over one axis.

Reference relationship: the reference shipped the raw differentiable
collectives that make intra-layer model parallelism *expressible*
(SURVEY.md §2.8 "TP: expressible manually via functions.allgather/alltoall;
no library support") but no transformer and no TP library.  This module is
that missing layer, built TPU-first:

* **Attention**: QKV projections are column-parallel (heads sharded over
  the model axis — each chip owns ``H/P`` heads and attends them with the
  in-tree flash kernel or plain XLA attention), the output projection is
  row-parallel.  ONE psum of cross-chip traffic per attention block.
* **MLP**: column→gelu→row (:func:`tensor_parallel.tp_mlp`), one psum.
* **Embedding / LM head**: vocab-parallel (each chip owns a vocab shard);
  the logits stay vocab-sharded and the cross-entropy computes from the
  sharded logits with two scalar-sized psums (max and log-sum-exp legs) —
  the full ``(B, S, V)`` logits never materialize on one chip.
* **LayerNorms, residuals**: replicated compute (cheap, bandwidth-bound).

Compose with data parallelism over a ``('data', 'model')`` mesh via
``parallel.hybrid.make_hybrid_shard_map_step`` — the loss below is per-token
mean over the LOCAL batch shard, exactly what that builder pmeans.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .._compat import pcast_varying, typeof as _typeof
from . import blocks as _blocks
from .tensor_parallel import column_parallel_dense, row_parallel_dense, tp_mlp


def _layer_norm(x, scale, bias, eps=1e-5):
    x32 = x.astype(jnp.float32)
    mu = x32.mean(-1, keepdims=True)
    var = ((x32 - mu) ** 2).mean(-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale + bias).astype(x.dtype)


def apply_rope(x, positions, *, base: float = 10000.0):
    """Rotary position embedding over ``(B, S, H, head_dim)``.

    Beyond-reference (learned absolute positions were already beyond the
    2017 reference; RoPE is the long-context-era standard — relative
    attention decay, extrapolation-friendly): rotate each head-dim pair by
    ``position · base^(-2i/d)``.  ``positions (S,)`` are GLOBAL token
    positions, so sequence-parallel shards pass ``my_shard_offset +
    arange(S_local)`` and the ring stays exact.  A 2-D ``positions
    (B, S)`` rotates each batch row at its OWN positions — the serving
    tick's contract, where every slot sits at a different sequence
    length.  ``head_dim`` must be even.
    """
    half = x.shape[-1] // 2
    if x.shape[-1] % 2:
        raise ValueError(f"RoPE needs an even head_dim, got {x.shape[-1]}")
    freqs = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    if positions.ndim == 2:                                  # per-row (B, S)
        ang = positions.astype(jnp.float32)[..., None] * freqs  # (B, S, half)
        cos = jnp.cos(ang)[:, :, None, :]
        sin = jnp.sin(ang)[:, :, None, :]
    else:
        ang = positions.astype(jnp.float32)[:, None] * freqs[None]  # (S, half)
        cos = jnp.cos(ang)[None, :, None, :]
        sin = jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1).astype(x.dtype)


def _project_qkv(h, a, head_dim: int, axis_name: str, bias: bool = True):
    """Shared QKV projection for both attention param layouts: returns
    local ``q (B, S, Hl, hd)`` and ``k, v (B, S, Hkv_l, hd)``.  ``bias=
    False`` (``LMArch.attn_bias``): the model's attention has no biases
    and ``a`` carries none.

    Works for TP-sharded weights (column shards produce local heads) and
    replicated weights (SP blocks — full heads) alike, since
    ``column_parallel_dense`` is a local matmul.  Single home for the
    fused-``wqkv`` vs GQA-``wq``/``wkv`` branch used by ``tp_attention``,
    ``sp_block`` and the KV-cache decoder.
    """
    b, s, _ = h.shape
    bias_of = (lambda name: a[name]) if bias else (lambda name: None)
    if "wq" in a:
        q = column_parallel_dense(h, a["wq"], bias_of("bq"),
                                  axis_name=axis_name)
        q = q.reshape(b, s, -1, head_dim)
        kv = column_parallel_dense(h, a["wkv"], bias_of("bkv"),
                                   axis_name=axis_name)
        if kv.shape[-1] % (2 * head_dim):
            raise ValueError(
                f"local wkv shard width {kv.shape[-1]} is not a whole "
                f"number of KV heads (2*head_dim={2 * head_dim}) — "
                f"n_kv_heads must be divisible by the model-axis size")
        kv = kv.reshape(b, s, -1, 2, head_dim)
        return q, kv[..., 0, :], kv[..., 1, :]
    qkv = column_parallel_dense(h, a["wqkv"], bias_of("bqkv"),
                                axis_name=axis_name)
    qkv = qkv.reshape(b, s, -1, 3, head_dim)
    return qkv[..., 0, :], qkv[..., 1, :], qkv[..., 2, :]


def tp_attention(x, params, *, head_dim: int, axis_name: str,
                 causal: bool = True, attn_impl: str = "auto",
                 positions=None, bias: bool = True, arch=None,
                 layer: int = 0):
    """Multi-head self-attention with heads sharded over ``axis_name``.

    ``x``: replicated-local ``(B, S, D)``; ``params``: local shards
    ``wqkv (D, 3·D/P)`` laid out HEAD-MAJOR (columns grouped per head as
    ``[q_h | k_h | v_h]`` so a contiguous column shard is whole heads —
    see :func:`init_tp_transformer_lm`), ``bqkv (3·D/P,)``,
    ``wo (D/P, D)``, replicated ``bo (D,)`` (``bias=False``: none of the
    three biases).  One psum (in the row-parallel output projection) per
    call.

    ``arch`` / ``layer`` (``blocks.LMArch``; None: none of either) give the
    layer its rotation (``arch.rotary[layer]``: theta, fraction, YaRN —
    the same ``blocks.turn_qk`` the serving prefill calls) and its window
    (``arch.window(layer) = W``: query ``q`` sees keys ``0 <= q - k < W``,
    the banded flash kernels forward and backward, or the band in the
    materializing path's mask).  The projections lie under the scope
    ``proj``, rotation and attention under ``core`` (a windowed layer's
    inside ``block/attn/window``, as the serving prefill's) — inside
    :func:`tp_block`'s ``block/attn``: the leaves ``block/attn/proj`` and
    ``block/attn/core`` of docs/OBSERVABILITY.md.
    """
    from ..ops.flash_attention import resolve_attn_impl

    arch = _blocks.resolve(arch)
    b, s, d = x.shape
    attn_impl = resolve_attn_impl(attn_impl, s)
    with jax.named_scope("proj"):
        q, k, v = _project_qkv(x, params, head_dim, axis_name, bias)
    h_local = q.shape[2]

    with jax.named_scope("core"), _blocks.window_scope(arch, layer):
        # the layer's own rotation, else plain RoPE where the model has no
        # position table (positions are global token indices), else none
        at = jnp.arange(s) if positions is None and arch.rotary is not None \
            else positions
        q, k = _blocks.turn_qk(arch, layer, q, k, at, positions is not None)
        ctx = _attend_local_heads(q, k, v, causal=causal,
                                  attn_impl=attn_impl, head_dim=head_dim,
                                  window=arch.window(layer))
    with jax.named_scope("proj"):
        ctx = ctx.reshape(b, s, h_local * head_dim)         # (B, S, D/P)
        return row_parallel_dense(ctx, params["wo"],
                                  params["bo"] if bias else None,
                                  axis_name=axis_name)


def _attend_local_heads(q, k, v, *, causal, attn_impl, head_dim,
                        window=None):
    """Attention over this chip's heads, full sequence: ``q (B, S, Hl, hd)``,
    GQA-aware (``k``/``v`` may carry fewer heads).  ``window``: the band
    ``0 <= q - k < window`` (causal only).  Shared by the
    replicated-activation (:func:`tp_attention`) and Megatron-SP
    (:func:`tp_attention_sp`) paths."""
    if window and not causal:
        raise ValueError("a window is the causal band 0 <= q - k < window")
    if attn_impl == "flash":
        from ..ops.flash_attention import flash_attention
        return flash_attention(q, k, v, causal=causal, window=window or None)
    h_local, s = q.shape[2], q.shape[1]
    if k.shape[2] != h_local:  # GQA on the materializing path
        g = h_local // k.shape[2]
        k = jnp.repeat(k, g, axis=2)
        v = jnp.repeat(v, g, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32)
    scores = scores / (head_dim ** 0.5)
    if causal:
        dist = jnp.arange(s)[:, None] - jnp.arange(s)[None, :]
        mask = dist >= 0
        if window:
            mask = mask & (dist < window)
        scores = jnp.where(mask[None, None], scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32).astype(q.dtype)


def tp_block(x, params, *, head_dim: int, axis_name: str, causal: bool = True,
             attn_impl: str = "auto", positions=None, arch=None,
             layer: int = 0):
    """Pre-norm transformer block: norm→attn→residual, norm→FFN→residual.

    ``arch`` (``blocks.LMArch``; None = the GPT-2-style default: LayerNorm,
    MHA/GQA, ``gelu`` MLP) names the block's vocabulary — the SAME
    description ``parallel/decode.py`` reads, so a model is described once
    for training, prefill and the decode tick.  ``layer`` picks the
    layer's kinds (attention: MHA | MLA | gated delta rule; dense MLP |
    experts), its window and its rotation from it."""
    return _tp_block_routed(x, params, head_dim=head_dim,
                            axis_name=axis_name, causal=causal,
                            attn_impl=attn_impl, positions=positions,
                            arch=arch, layer=layer)[0]


def _tp_block_routed(x, params, *, head_dim: int, axis_name: str,
                     causal: bool = True, attn_impl: str = "auto",
                     positions=None, arch=None, layer: int = 0):
    """:func:`tp_block` as ``(x, routing)``: ``routing`` is what
    ``blocks.ffn`` gives beside the result — None for a dense layer, the
    routing-count vector and the chosen experts for an expert layer.  The
    attention half is the layer's kind's ``blocks.LAYER_KINDS[kind].train``
    (the ``_*_forward`` below); a kind that has none is refused by name."""
    arch = _blocks.resolve(arch)
    forward = _blocks.layer_kind(arch, layer).train
    if forward is None:
        raise NotImplementedError(
            f"tp_block: layer {layer} is described with attention kind "
            f"{arch.attn_kind(layer)!r}, which has no training forward "
            f"(serving runs it: parallel/decode.py)")
    x = forward(arch, x, params, layer, head_dim=head_dim,
                axis_name=axis_name, causal=causal, attn_impl=attn_impl,
                positions=positions)
    with jax.named_scope("block/mlp"):
        h = _blocks.norm(arch, x, params, "ln2")
        y, routing = _blocks.ffn(arch, layer, h, params, axis_name)
        return x + y, routing


def _mha_forward(arch, x, params, layer: int, **kw):
    """The MHA/GQA layer's half of the training block."""
    if arch.attn_gate:
        # no silent ungated substitute: the loss path has no output gate
        # yet (a window and a per-layer rotation it runs)
        raise NotImplementedError(
            f"tp_block: layer {layer} is described with window="
            f"{arch.window(layer)}, attn_gate={arch.attn_gate}, rotary="
            f"{arch.rotary is not None}; the training block runs no "
            f"output gate (serving does: parallel/decode.py)")
    # the attention half's own leaves inside ``block/attn``: ``proj``
    # (norm, projections, residual) and ``core`` (``tp_attention``)
    with jax.named_scope("block/attn"):
        with jax.named_scope("proj"):
            h = _blocks.norm(arch, x, params, "ln1")
        y = tp_attention(h, params["attn"], bias=arch.attn_bias, arch=arch,
                         layer=layer, **kw)
        with jax.named_scope("proj"):
            return x + y


def _mla_forward(arch, x, params, layer: int, *, attn_impl: str, positions,
                 **_):
    """The latent-attention layer's half of the training block: the
    prefill form over the whole sequence."""
    from ..ops.flash_attention import resolve_attn_impl

    with jax.named_scope("block/attn"):
        h = _blocks.norm(arch, x, params, "ln1")
        s = x.shape[1]
        q_nope, q_rope, c_kv, k_rope = _blocks.mla_project(
            arch.mla, h, params["attn"],
            jnp.arange(s) if positions is None else positions,
            arch.norm_eps)
        ctx = _blocks.mla_attend_prefill(
            arch.mla, q_nope, q_rope, c_kv, k_rope, params["attn"],
            resolve_attn_impl(attn_impl, s))
        return x + jnp.matmul(ctx, params["attn"]["wo"],
                              preferred_element_type=jnp.float32
                              ).astype(x.dtype)


def _kda_forward(arch, x, params, layer: int, **_):
    """The gated delta-rule layer's half of the training block: from a zero
    state, in the chunked form (plain XLA: matmuls, a triangular solve and
    a scan, all of which differentiate)."""
    from .kda import kda_layer

    with jax.named_scope("block/kda"):
        h = _blocks.norm(arch, x, params, "ln1")
        kept = zip(arch.kda.state_shapes, (jnp.float32, x.dtype))
        state, window = (jnp.zeros((x.shape[0],) + s, d) for s, d in kept)
        return x + kda_layer(arch.kda, h, params["attn"], state, window,
                             None, arch.norm_eps)[0]


def tp_attention_sp(x, params, *, head_dim: int, axis_name: str,
                    causal: bool = True, attn_impl: str = "auto",
                    positions=None):
    """Megatron-SP attention: ``x (B, S/P, D)`` SEQUENCE-sharded.

    The entry sequence all-gather fuses into the QKV projection
    (:func:`tensor_parallel.gather_seq_matmul` — ring hops overlap the
    matmul chunks) and the exit is a fused matmul+reduce-scatter back to
    sequence shards, replacing :func:`tp_attention`'s psum.  Heads stay
    TP-sharded; attention itself sees the full sequence.  ``positions``
    must be the GLOBAL ``arange(S)`` (attention runs post-gather).
    """
    from ..ops.flash_attention import resolve_attn_impl

    from .tensor_parallel import gather_seq_matmul, matmul_scatter_seq

    b, s_loc, d = x.shape
    s = s_loc * jax.lax.axis_size(axis_name)
    attn_impl = resolve_attn_impl(attn_impl, s)
    if "wq" in params:
        q = gather_seq_matmul(x, params["wq"], params["bq"],
                              axis_name=axis_name).reshape(b, s, -1, head_dim)
        kv = gather_seq_matmul(x, params["wkv"], params["bkv"],
                               axis_name=axis_name)
        kv = kv.reshape(b, s, -1, 2, head_dim)
        k, v = kv[..., 0, :], kv[..., 1, :]
    else:
        qkv = gather_seq_matmul(x, params["wqkv"], params["bqkv"],
                                axis_name=axis_name)
        qkv = qkv.reshape(b, s, -1, 3, head_dim)
        q, k, v = qkv[..., 0, :], qkv[..., 1, :], qkv[..., 2, :]
    if positions is not None:
        q = apply_rope(q, positions)
        k = apply_rope(k, positions)
    ctx = _attend_local_heads(q, k, v, causal=causal, attn_impl=attn_impl,
                              head_dim=head_dim)
    ctx = ctx.reshape(b, s, -1)                              # (B, S, D/P)
    return matmul_scatter_seq(ctx, params["wo"], params["bo"],
                              axis_name=axis_name)


def tp_block_sp(x, params, *, head_dim: int, axis_name: str,
                causal: bool = True, attn_impl: str = "auto",
                positions=None):
    """Megatron-SP transformer block over SEQUENCE-sharded ``(B, S/P, D)``.

    Same params/layout as :func:`tp_block`; LayerNorms and residuals are
    per-position so they run on the local shard (1/P the replicated
    compute), and all four cross-chip collectives (attention/MLP entry
    gathers, exit reduce-scatters) ride the overlapped
    ``collective_matmul`` rings.  Numerically equal to :func:`tp_block`
    on the gathered sequence up to reassociation (tests pin it).
    """
    from .tensor_parallel import tp_mlp_sp

    with jax.named_scope("block/attn"):
        h = _layer_norm(x, params["ln1_scale"], params["ln1_bias"])
        x = x + tp_attention_sp(h, params["attn"], head_dim=head_dim,
                                axis_name=axis_name, causal=causal,
                                attn_impl=attn_impl, positions=positions)
    with jax.named_scope("block/mlp"):
        h = _layer_norm(x, params["ln2_scale"], params["ln2_bias"])
        return x + tp_mlp_sp(h, params["mlp"], axis_name=axis_name)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _fused_vp_nll(h2, table, local_t, axis_name, explicit_psum):
    """Per-row NLL via the fused-CE kernels with VOCAB-SHARDED tables:
    shard-local online stats, pmax/psum combine, global-LSE backward.
    ``h2 (T, D)``, ``table (V/P, D)``, ``local_t (T,)`` already shifted to
    this shard's range (out-of-range ids match nothing — exactly the
    one-hot masking the kernels implement).

    ``explicit_psum``: True when vma tracking is OFF (``check_vma=False``
    contexts) — the backward then hand-psums dh over ``axis_name``; with
    tracking on, the caller's pcast promotions route every cross-shard
    gradient reduction through their transposes instead."""
    return _fused_vp_nll_fwd(h2, table, local_t, axis_name, explicit_psum)[0]


def _fused_vp_nll_fwd(h2, table, local_t, axis_name, explicit_psum):
    from ..ops.fused_ce import ce_stats

    m, l, p = ce_stats(h2, table, local_t)
    gm = jax.lax.pmax(m, axis_name)
    gl = jax.lax.psum(l * jnp.exp(m - gm), axis_name)
    lse = gm + jnp.log(gl)
    picked = jax.lax.psum(p, axis_name)  # owner shard contributes; rest 0
    return lse - picked, (h2, table, local_t, lse)


def _fused_vp_nll_bwd(axis_name, explicit_psum, res, dnll):
    from ..ops.fused_ce import ce_grads

    h2, table, local_t, lse = res
    dh, dtable = ce_grads(h2, table, local_t, lse, dnll)
    if explicit_psum:
        dh = jax.lax.psum(dh.astype(jnp.float32), axis_name).astype(h2.dtype)
    return dh, dtable, None


_fused_vp_nll.defvjp(_fused_vp_nll_fwd, _fused_vp_nll_bwd)

# Auto threshold: switch to the fused kernels when the materialized local
# logits would exceed this many bytes.  Deliberately conservative vs the
# measured standalone crossover (on v5e the XLA path still ran, ~40%
# faster, at 8.6 GB of logits and failed at 34 GB — docs/PERF.md): a
# FULL train step also holds params/activations/optimizer state, so
# 'auto' must flip while the logits still leave that headroom; prefer a
# few ms of CE time over an OOM at compile.  Force ce_impl='xla' to keep
# the materializing path near the boundary.
_FUSED_CE_AUTO_BYTES = 8 << 30


def vocab_parallel_logits_loss(h, table, targets, *, axis_name: str,
                               ce_impl: str = "auto"):
    """Cross-entropy from VOCAB-SHARDED logits — ``(B, S, V)`` never
    materializes unsharded.

    ``h (B, S, D)`` replicated-local; ``table (V/P, D)`` the local vocab
    shard of the (tied) embedding; ``targets (B, S)`` global token ids.
    Three cheap collectives: pmax (stable shift), psum of the local
    exp-sum, psum of the target-logit one-hot pick.

    ``ce_impl``: ``'xla'`` materializes the local ``(B, S, V/P)`` fp32
    logits (fastest when they fit — XLA runs this chain at ~0.8 MFU);
    ``'fused'`` runs the Pallas online-softmax kernels
    (``ops.fused_ce``) — logits tiles never leave VMEM, O(B·S) memory,
    the only path that COMPILES at huge ``T×V`` (docs/PERF.md records
    the 34 GB-logits case); ``'auto'`` picks fused on TPU once the local
    logits buffer would cross ~8 GB (below that XLA is measurably
    faster), xla otherwise.
    """
    vocab_per = table.shape[0]
    start = jax.lax.axis_index(axis_name) * vocab_per
    b, s, d = h.shape
    if ce_impl == "auto":
        big = b * s * vocab_per * 4 > _FUSED_CE_AUTO_BYTES
        on_tpu = jax.default_backend() == "tpu"
        aligned = (b * s) % 8 == 0 and vocab_per % 8 == 0
        ce_impl = "fused" if (big and on_tpu and aligned) else "xla"
    if ce_impl == "fused":
        h2 = h.reshape(b * s, d)
        # The custom_vjp replaces AD's transpose, so every cross-shard
        # gradient reduction must come from varying-axis promotions
        # OUTSIDE it: promote BOTH operands to the union of their varying
        # axes (h gains the model axis, table gains the data axis under
        # DP×TP) — each promotion's transpose then psums the matching
        # cotangent (dh over model, dtable over data) exactly where the
        # bypassed machinery would have.  When vma tracking is off
        # (check_vma=False contexts) there is nothing to promote; the
        # backward hand-psums dh over the model axis instead.
        hv = set(getattr(_typeof(h2), "vma", frozenset()))
        tv = set(getattr(_typeof(table), "vma", frozenset()))
        vma_active = bool(hv or tv)
        if vma_active:
            union = hv | tv | {axis_name}
            for ax in sorted(union - hv):
                h2 = pcast_varying(h2, ax)
            for ax in sorted(union - tv):
                table = pcast_varying(table, ax)
        local_t = (targets - start).reshape(-1)
        nll = _fused_vp_nll(h2, table, local_t, axis_name, not vma_active)
        return jnp.mean(nll)
    if ce_impl != "xla":
        raise ValueError(
            f"ce_impl must be 'auto', 'xla' or 'fused', got {ce_impl!r}")
    logits = jnp.einsum("bsd,vd->bsv", h, table,
                        preferred_element_type=jnp.float32)  # (B, S, V/P)

    # The max shift is numerics-only: its gradient contribution cancels
    # analytically (d/dx of m + log Σ exp(x−m) ignores m), and pmax has no
    # differentiation rule — so cut it out of the tangent graph entirely.
    m = jax.lax.pmax(jax.lax.stop_gradient(logits).max(-1), axis_name)  # (B, S)
    sumexp = jax.lax.psum(
        jnp.exp(logits - m[..., None]).sum(-1), axis_name)   # (B, S)
    local_t = targets - start
    in_range = (local_t >= 0) & (local_t < vocab_per)
    picked = jnp.take_along_axis(
        logits, jnp.clip(local_t, 0, vocab_per - 1)[..., None], axis=-1)[..., 0]
    target_logit = jax.lax.psum(jnp.where(in_range, picked, 0.0), axis_name)
    return jnp.mean(m + jnp.log(sumexp) - target_logit)


def tp_transformer_lm_loss(params, batch, *, head_dim: int, axis_name: str,
                           causal: bool = True, attn_impl: str = "auto",
                           ce_impl: str = "auto", arch=None,
                           remat: bool = False, aux: bool = False):
    """Per-token mean NLL of a decoder-only LM over the LOCAL batch shard.

    ``batch``: ``(tokens (B, S+1) int32,)`` — inputs are ``[:, :-1]``,
    targets ``[:, 1:]``.  Feed to ``make_hybrid_shard_map_step`` for DP×TP
    (``functools.partial`` the static args first).  ``ce_impl`` selects
    the loss path (see :func:`vocab_parallel_logits_loss`).  ``arch``: the
    model's ``blocks.LMArch`` (None = the GPT-2-style default).

    ``remat=True`` recomputes each block in the backward pass
    (``jax.checkpoint`` around a block: what a block saves is its input
    alone, and its intermediates — an expert layer's gathered rows and
    three grouped products among them — live for one layer at a time).

    ``aux=True`` (a model with expert layers) returns ``(loss, {'counts',
    'routes'})``: the layers' int32 routing-count vectors
    (``moe.COUNT_FIELDS`` then one entry a held expert) summed over the
    expert layers, and the chosen experts ``(B, S, expert layers, top_k)``
    — with ``make_hybrid_shard_map_step(has_aux=True, aux_specs={'counts':
    P(), 'routes': P(data_axis)})`` the step hands the counts out summed
    over the data axis and the routes a sample.
    """
    from .tensor_parallel import vocab_parallel_embedding

    arch = _blocks.resolve(arch)
    tokens = batch[0]
    inputs, targets = tokens[:, :-1], tokens[:, 1:]

    positions = None
    with jax.named_scope("embed"):
        x = vocab_parallel_embedding(inputs, params["embed"],
                                     axis_name=axis_name)
        x = _blocks.scale_embedding(arch, x, params["embed"].shape[1])
        if "pos_embed" in params:
            x = x + params["pos_embed"][: x.shape[1]][None]
        else:  # RoPE model (init with pos_impl='rope'): rotate in attention
            positions = jnp.arange(x.shape[1])
    routing = []
    for i, blk in enumerate(params["blocks"]):
        block = partial(_tp_block_routed, head_dim=head_dim,
                        axis_name=axis_name, causal=causal,
                        attn_impl=attn_impl, arch=arch, layer=i)
        if remat:
            block = jax.checkpoint(block)
        x, routed = block(x, blk, positions=positions)
        if routed is not None:
            routing.append(routed)
    with jax.named_scope("head_ce"):
        x = _blocks.norm(arch, x, params, "lnf")
        loss = vocab_parallel_logits_loss(
            x, _blocks.head_table(arch, params), targets,
            axis_name=axis_name, ce_impl=ce_impl)
    if not aux:
        return loss
    if not routing:
        raise ValueError("aux: the model has no expert layer to count")
    with jax.named_scope("block/moe/route"):    # the layers' counts summed
        return loss, {
            "counts": sum((c for c, _ in routing[1:]), routing[0][0]),
            "routes": jnp.stack([idx for _, idx in routing], axis=2)}


def sp_block(x, params, *, head_dim: int, axis_name: str, causal: bool = True,
             attn_impl: str = "auto", sp_impl: str = "ring", positions=None):
    """Transformer block with the SEQUENCE sharded over ``axis_name``.

    The long-context configuration (first-class per the rebuild brief;
    absent from the 2017 reference — SURVEY.md §5): ``x`` is the local
    sequence shard ``(B, S/P, D)`` with params REPLICATED; attention runs
    over ``sp_impl`` — ``'ring'`` (ppermute K/V rotation, O(S/P) keys per
    chip, any head count) or ``'ulysses'`` (two all-to-alls swapping the
    sharded axis to heads; needs ``n_heads % P == 0``).  Everything else
    (LN, MLP) is embarrassingly parallel over sequence positions.  Uses the
    same (unsharded) block-param layout as :func:`init_tp_transformer_lm` —
    the head-major wqkv makes the local reshape identical to
    :func:`tp_attention`'s.
    """
    from .ring_attention import ring_attention
    from .ulysses import ulysses_attention

    b, s_local, d = x.shape
    n_heads = d // head_dim
    a = params["attn"]
    h = _layer_norm(x, params["ln1_scale"], params["ln1_bias"])
    # Params are replicated here, so the shared projection yields FULL
    # heads (GQA: fewer KV heads ride the ring / all-to-all).
    q, k, v = _project_qkv(h, a, head_dim, axis_name)
    if positions is not None:
        # RoPE with GLOBAL positions: each shard rotates by its own offsets
        # before K/V ride the ring, so relative phases stay exact.
        q = apply_rope(q, positions)
        k = apply_rope(k, positions)
    if sp_impl == "ring":
        ctx = ring_attention(q, k, v, axis_name=axis_name, causal=causal,
                             attn_impl=attn_impl)
    elif sp_impl == "ulysses":
        ctx = ulysses_attention(q, k, v, axis_name=axis_name, causal=causal,
                                attn_impl=attn_impl)
    else:
        raise ValueError(f"sp_impl must be 'ring' or 'ulysses', got {sp_impl!r}")
    ctx = ctx.reshape(b, s_local, d)
    attn_out = jnp.matmul(ctx, a["wo"],
                          preferred_element_type=jnp.float32).astype(x.dtype)
    x = x + attn_out + a["bo"]
    h = _layer_norm(x, params["ln2_scale"], params["ln2_bias"])
    mlp = params["mlp"]
    y = jax.nn.gelu(jnp.matmul(h, mlp["wi"],
                    preferred_element_type=jnp.float32).astype(x.dtype)
                    + mlp["bi"])
    y = jnp.matmul(y, mlp["wo"],
                   preferred_element_type=jnp.float32).astype(x.dtype)
    return x + y + mlp["bo"]


def sp_transformer_lm_loss(params, batch, *, head_dim: int, axis_name: str,
                           causal: bool = True, attn_impl: str = "auto",
                           sp_impl: str = "ring"):
    """Per-token mean NLL with the SEQUENCE sharded over ``axis_name``.

    ``batch``: ``(inputs (B, S/P), targets (B, S/P))`` — the caller shards
    a ``(B, S)`` token array over its sequence axis (``P(None, axis)``) and
    shifts globally BEFORE sharding, so each chip's targets line up with
    its inputs.  Params replicated; the ring carries the only cross-chip
    traffic.  Gradient sync composes exactly like data parallelism: pmean
    the loss over the axis and let autodiff insert the cotangent psum.
    """
    inputs, targets = batch
    my = jax.lax.axis_index(axis_name)
    s_local = inputs.shape[1]
    s_global = jax.lax.axis_size(axis_name) * s_local
    pos = my * s_local + jnp.arange(s_local)
    x = jnp.take(params["embed"], inputs, axis=0)
    x = x * (params["embed"].shape[1] ** 0.5)
    positions = None
    if "pos_embed" in params:
        max_len = params["pos_embed"].shape[0]
        if s_global > max_len:
            # jnp.take would silently CLAMP out-of-range positions to the
            # last pos_embed row — degenerate positional info, no error.
            raise ValueError(
                f"global sequence {s_global} exceeds pos_embed max_len "
                f"{max_len}; re-init the model with max_len >= {s_global}")
        x = x + jnp.take(params["pos_embed"], pos, axis=0)[None]
    else:  # RoPE: no length cap, rotation happens inside attention
        positions = pos
    for blk in params["blocks"]:
        x = sp_block(x, blk, head_dim=head_dim, axis_name=axis_name,
                     causal=causal, attn_impl=attn_impl, sp_impl=sp_impl,
                     positions=positions)
    x = _layer_norm(x, params["lnf_scale"], params["lnf_bias"])
    logits = jnp.einsum("bsd,vd->bsv", x, params["embed"],
                        preferred_element_type=jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(nll)


# ---- init + specs (GLOBAL params; shard with transformer_lm_specs) ----

def init_tp_transformer_lm(rng, vocab: int, d_model: int, n_heads: int,
                           n_layers: int, d_hidden: Optional[int] = None,
                           max_len: int = 512, dtype=jnp.float32,
                           n_kv_heads: Optional[int] = None,
                           pos_impl: str = "learned") -> Dict[str, Any]:
    """GLOBAL (unsharded) parameter pytree for the TP transformer LM.

    ``n_kv_heads`` (GQA/MQA): when set below ``n_heads``, attention carries
    separate ``wq`` and fused ``wkv`` projections (both head-major) instead
    of the fused ``wqkv``; the KV cache and projection shrink by
    ``n_heads / n_kv_heads``.  Under TP, ``n_kv_heads`` must stay divisible
    by the model-axis size.

    ``pos_impl``: ``'learned'`` (absolute ``pos_embed`` table, capped at
    ``max_len``) or ``'rope'`` (rotary, :func:`apply_rope` — no table, no
    length cap; the loss builders detect the absent ``pos_embed`` key).
    """
    if pos_impl not in ("learned", "rope"):
        raise ValueError(f"pos_impl must be 'learned' or 'rope', got {pos_impl!r}")
    if d_model % n_heads:
        raise ValueError(f"d_model {d_model} not divisible by n_heads {n_heads}")
    if n_kv_heads is not None and n_heads % n_kv_heads:
        raise ValueError(
            f"n_heads {n_heads} not a multiple of n_kv_heads {n_kv_heads}")
    gqa = n_kv_heads is not None and n_kv_heads != n_heads
    d_hidden = d_hidden or 4 * d_model
    head_dim = d_model // n_heads
    keys = jax.random.split(rng, 2 + 4 * n_layers)
    scale = lambda fan_in: (2.0 / fan_in) ** 0.5

    def dense(key, n_in, n_out):
        return (jax.random.normal(key, (n_in, n_out)) * scale(n_in)).astype(dtype)

    blocks = []
    for i in range(n_layers):
        k1, k2, k3, k4 = keys[2 + 4 * i: 6 + 4 * i]
        if gqa:
            kq, kk, kv_ = jax.random.split(k1, 3)
            d_kv = n_kv_heads * head_dim
            # kv-head-major: columns are [head0: k|v, head1: k|v, …] so a
            # contiguous column shard over the model axis is whole KV heads.
            wk = dense(kk, d_model, d_kv).reshape(d_model, n_kv_heads, head_dim)
            wv = dense(kv_, d_model, d_kv).reshape(d_model, n_kv_heads, head_dim)
            attn = {
                "wq": dense(kq, d_model, d_model),
                "bq": jnp.zeros((d_model,), dtype),
                "wkv": jnp.stack([wk, wv], axis=2).reshape(d_model, 2 * d_kv),
                "bkv": jnp.zeros((2 * d_kv,), dtype),
                "wo": dense(k2, d_model, d_model),
                "bo": jnp.zeros((d_model,), dtype),
            }
        else:
            # Head-major qkv layout: columns are [head0: q|k|v, head1:
            # q|k|v, …] so a contiguous column shard is whole heads.
            wq, wk, wv = (dense(kk, d_model, d_model).reshape(
                d_model, n_heads, head_dim) for kk in jax.random.split(k1, 3))
            attn = {
                "wqkv": jnp.stack([wq, wk, wv], axis=2).reshape(
                    d_model, 3 * d_model),
                "bqkv": jnp.zeros((3 * d_model,), dtype),
                "wo": dense(k2, d_model, d_model),
                "bo": jnp.zeros((d_model,), dtype),
            }
        blocks.append({
            "ln1_scale": jnp.ones((d_model,), dtype),
            "ln1_bias": jnp.zeros((d_model,), dtype),
            "ln2_scale": jnp.ones((d_model,), dtype),
            "ln2_bias": jnp.zeros((d_model,), dtype),
            "attn": attn,
            "mlp": {
                "wi": dense(k3, d_model, d_hidden),
                "bi": jnp.zeros((d_hidden,), dtype),
                "wo": dense(k4, d_hidden, d_model),
                "bo": jnp.zeros((d_model,), dtype),
            },
        })
    out = {
        "embed": (jax.random.normal(keys[0], (vocab, d_model))
                  * scale(d_model)).astype(dtype),
        "blocks": blocks,
        "lnf_scale": jnp.ones((d_model,), dtype),
        "lnf_bias": jnp.zeros((d_model,), dtype),
    }
    if pos_impl == "learned":
        out["pos_embed"] = (jax.random.normal(keys[1], (max_len, d_model))
                            * 0.02).astype(dtype)
    return out


def transformer_lm_specs(params, axis_name: str = "model"):
    """PartitionSpecs matching :func:`init_tp_transformer_lm`'s pytree.

    QKV / MLP-in are column-sharded, attention-out / MLP-out row-sharded,
    the tied embedding vocab-sharded, norms/positions replicated.  ``wqkv``
    column-sharding is head-granular automatically because heads are the
    fastest-varying dim of its 3·D output.
    """
    ax = axis_name

    def block_specs(blk):
        if "wq" in blk["attn"]:  # GQA: separate q / fused kv projections
            attn = {"wq": P(None, ax), "bq": P(ax),
                    "wkv": P(None, ax), "bkv": P(ax),
                    "wo": P(ax, None), "bo": P()}
        else:
            attn = {"wqkv": P(None, ax), "bqkv": P(ax),
                    "wo": P(ax, None), "bo": P()}
        return {
            "ln1_scale": P(), "ln1_bias": P(),
            "ln2_scale": P(), "ln2_bias": P(),
            "attn": attn,
            "mlp": {"wi": P(None, ax), "bi": P(ax),
                    "wo": P(ax, None), "bo": P()},
        }

    out = {
        "embed": P(ax, None),
        "blocks": [block_specs(b) for b in params["blocks"]],
        "lnf_scale": P(),
        "lnf_bias": P(),
    }
    if "pos_embed" in params:
        out["pos_embed"] = P()
    return out
