"""Autoregressive decoding with a KV cache for the TP transformer LM.

Beyond-reference (the reference's only generation was seq2seq greedy
translate): incremental decoding the TPU way —

* ONE jitted program: prefill (full-prompt forward that also writes the
  per-layer KV cache) + a ``lax.scan`` over the new tokens (static trip
  count, static cache shapes — no dynamic shapes anywhere);
* the cache holds the **KV heads** (GQA models cache ``n_kv_heads``, the
  whole point of GQA at inference);
* tensor parallelism composes: projections are column-parallel so each
  chip caches only its local heads, the output projection's psum is the
  only per-token cross-chip traffic, and the vocab-parallel logits are
  argmax'd via a (max, index) pmax/psum pair — the full ``(B, V)`` logits
  never materialize on one chip;
* positions come from the model's ``pos_impl`` (learned table or RoPE —
  RoPE rotates each new token at its absolute position).

Layout matches :func:`transformer.init_tp_transformer_lm`; works for both
fused-``wqkv`` and GQA (``wq``/``wkv``) attention params.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .._compat import pcast_varying
from . import blocks as _blocks
from .tensor_parallel import row_parallel_dense
from .transformer import _layer_norm, _project_qkv


def _decoder_core(params, head_dim: int, axis_name: str, arch=None,
                  live=None):
    """Shared incremental-decoding machinery:
    ``(embed, attn_block, block_with, rope)``.

    ``attn_block`` derives its batch from ``x`` so the same core serves the
    greedy path (batch B) and beam search (batch B·K); ``block_with`` is
    the underlying scaffolding with a pluggable attend stage (the lazy
    beam swaps in its ancestry-masked attention there).

    ``arch`` (a ``blocks.LMArch``; None = the GPT-2-style default) names
    the block's vocabulary — norm, MLP, attention and layer kinds, head,
    embedding scale — read here once and shared with the training loss
    (``parallel/transformer.py``).  A layer's cache is a TUPLE of buffers,
    whatever its attention declares (``blocks.cache_layout``): ``(k, v)``
    for MHA/GQA — rows a token, or under a window a RING of the window's
    rows — one latent buffer for MLA, ``(state, window)`` for a
    gated delta-rule or a selective state-space layer — the kind is the LAYER's
    (``arch.attn_kind(layer)``).  ``attn_block.moe_routing``
    collects the expert layers' ``(counts, idx)`` in trace order; ``live
    (N, S_q) bool`` names the rows that carry a token (None: all) — the
    expert layers send the others to no expert, a state layer (delta rule,
    selective scan) leaves their state as it is, and the tick's attention (``S_q == 1``)
    reads their cache not at all: such a row's context is 0.

    Every equation of a block lies under one ``jax.named_scope`` of the
    vocabulary a traced program's device time is split by
    (docs/OBSERVABILITY.md, "Device time by scope"): the attention half is
    ``block/{attn,mla,kda,mamba}/proj``, ``.../core`` and ``cache_write``,
    the FFN half ``block/mlp`` — ``with`` blocks only, never a function layer.
    """
    arch = _blocks.resolve(arch)
    d_model = params["embed"].shape[1]
    rope = "pos_embed" not in params
    moe_routing = []

    def tick_work(work, pos, n, rows):
        """The flash-decode kernels' work list over an ``(n, rows, ·)``
        cache — the busy slots' live blocks — from the tick's own memo
        ``work`` (one list a cache shape, built where the first layer of
        that shape attends, handed to every later one); None without a
        memo: the kernel builds its own."""
        from ..ops.decode_attention import work_list

        if work is None:
            return None
        if rows not in work:
            with jax.named_scope("tick/work_list"):
                work[rows] = work_list(pos, busy_rows(), n, rows)
        return work[rows]

    def busy_rows():
        """``(N,) bool`` of a tick's rows that carry a token (None: all)."""
        return None if live is None else live[:, 0]

    def tick_slots(work, n):
        """The tick's busy list over ``n`` slots from its memo ``work``
        (built where the first layer wants it, handed to every later one:
        the row writers and the state kernels walk the same list); None
        without a memo: the kernel builds its own."""
        from ..ops.kv_cache import busy_slots

        if work is None:
            return None
        if "slots" not in work:
            # a layer that takes no kernel leaves the list unread, and the
            # compiler drops it
            work["slots"] = busy_slots(busy_rows(), n)
        return work["slots"]

    def write_new_rows(bufs, rows, write_at, work):
        """The layer's new ``rows`` (a tuple of ``(N, S_q, W_i)``) into its
        cache buffers ``bufs`` at ``write_at`` — every caller's one door,
        under its ``cache_write`` scope.  The tick (one row a slot, each at
        its own position ``write_at (N,)``, clamped inside the buffer)
        goes through ``ops/kv_cache.py::write_rows``: the busy slots' rows
        alone, in place, over the tick's busy list (``work``'s memo: built
        where the first layer writes, handed to every later one).  A
        scalar position keeps the closed batch's writers: ``cache_append``
        for a K/V pair, a ``dynamic_update_slice`` for one buffer."""
        from ..ops.kv_cache import cache_append, write_rows

        if getattr(write_at, "ndim", 0) == 1:
            if rows[0].shape[1] != 1:       # a chunk behind a cache
                return write_rows(bufs, rows, write_at)
            return write_rows(bufs, rows, write_at, busy_rows(),
                              slots=tick_slots(work, bufs[0].shape[0]))
        if len(bufs) == 2:
            return cache_append(*bufs, *rows, write_at, axis=1)
        return tuple(jax.lax.dynamic_update_slice(
            c, r.astype(c.dtype), (0, write_at, 0))
            for c, r in zip(bufs, rows))

    def embed(tokens, positions):
        from .tensor_parallel import vocab_parallel_embedding

        # The table is VOCAB-SHARDED over the model axis — a plain take
        # would index local rows with global ids.
        x = vocab_parallel_embedding(tokens, params["embed"],
                                     axis_name=axis_name)
        x = _blocks.scale_embedding(arch, x, d_model)
        if not rope:
            pe = jnp.take(params["pos_embed"], positions, axis=0)
            # (S,) positions broadcast over the batch; (N, S) positions
            # (the serving tick: every slot at its own length) index
            # per row.
            x = x + (pe if positions.ndim == 2 else pe[None])
        return x

    def second_half(x, blk, layer):
        """residual stream after attention → norm → the layer's FFN (dense
        MLP or experts, by ``arch``) → residual."""
        with jax.named_scope("block/mlp"):
            h = _blocks.norm(arch, x, blk, "ln2")
            y, routing = _blocks.ffn(arch, layer, h, blk, axis_name, live)
            if routing is not None:
                moe_routing.append(routing)
            return x + y

    def block_with(x, blk, positions, attend, layer: int = 0):
        """Shared block scaffolding: ln1 → qkv projection (+rope) →
        pluggable ``attend(q, k, v) -> (ctx, extras)`` → wo row-parallel →
        residual → ln2 → the layer's FFN.  ONE copy of the model structure
        serves the physical-cache path and the lazy-beam path; only the
        score/context stage differs."""
        n, s_q = x.shape[0], x.shape[1]
        a = blk["attn"]
        with jax.named_scope("block/attn/proj"):
            h = _blocks.norm(arch, x, blk, "ln1")
            q, k, v = _project_qkv(h, a, head_dim, axis_name, arch.attn_bias)
            # the layer's own rotation (theta, the rotated fraction, YaRN),
            # else the model's plain one
            q, k = _blocks.turn_qk(arch, layer, q, k, positions, rope)
        # the attend stage's own cache append nests as .../core/cache_write
        with jax.named_scope("block/attn/core"):
            ctx, extras = attend(q, k, v)
        if arch.attn_gate:
            # per-head sigmoid gate from the attention's own input, on the
            # context, before the output projection
            with jax.named_scope("block/attn/gate"):
                gate = jax.nn.sigmoid(jnp.matmul(
                    h, a["wg"], preferred_element_type=jnp.float32))
                ctx = (ctx.reshape(n, s_q, -1, head_dim).astype(jnp.float32)
                       * gate[..., None]).astype(x.dtype)
        with jax.named_scope("block/attn/proj"):
            ctx = ctx.reshape(n, s_q, -1)
            x = x + row_parallel_dense(
                ctx, a["wo"], a["bo"] if arch.attn_bias else None,
                axis_name=axis_name)
        return (second_half(x, blk, layer),) + extras

    def mla_block(x, blk, cache, positions, write_at, q_valid, layer, work):
        """The MLA layer: the token's latent row is written to ``cache``
        (one buffer), a prefill attends in the prefill form through the
        flash kernel, everything else in the absorbed form over the
        latent rows — on a TPU the one-token tick through the
        flash-decode kernel, one position per cache row."""
        from ..ops.decode_attention import _pick_block_s
        from ..ops.flash_attention import resolve_attn_impl

        cfg = arch.mla
        n, s_q = x.shape[0], x.shape[1]
        with jax.named_scope("block/mla"):
            with jax.named_scope("proj"):
                h = _blocks.norm(arch, x, blk, "ln1")
                q_nope, q_rope, c_kv, k_rope = _blocks.mla_project(
                    cfg, h, blk["attn"], positions, arch.norm_eps)
                rows = _blocks.mla_latent_rows(cfg, c_kv, k_rope)
            with jax.named_scope("cache_write"):
                (cache,) = write_new_rows((cache,), (rows,), write_at, work)
            if s_q > 1 and isinstance(write_at, int) and write_at == 0 \
                    and isinstance(q_valid, int) and q_valid == 0:
                ctx = _blocks.mla_attend_prefill(
                    cfg, q_nope, q_rope, c_kv, k_rope, blk["attn"],
                    resolve_attn_impl("auto", s_q))
            else:
                with jax.named_scope("core"):
                    valid = (jnp.asarray(q_valid, jnp.int32).reshape(-1, 1)
                             + jnp.arange(s_q, dtype=jnp.int32)[None] + 1)
                    valid = jnp.broadcast_to(valid, (n, s_q))
                    use_kernel = (s_q == 1
                                  and jax.default_backend() == "tpu"
                                  and _pick_block_s(cache.shape[1]) > 0)
                    busy = busy_rows() if s_q == 1 else None
                    lists = tick_work(work, valid[:, 0] - 1, n,
                                      cache.shape[1]) if use_kernel else None
                ctx = _blocks.mla_attend_absorbed(
                    cfg, q_nope, q_rope, cache, valid, blk["attn"],
                    use_kernel, busy, lists)
            with jax.named_scope("proj"):
                x = x + jnp.matmul(
                    ctx, blk["attn"]["wo"],
                    preferred_element_type=jnp.float32).astype(x.dtype)
        return second_half(x, blk, layer), cache

    def kda_block(x, blk, state, window, layer, work):
        """The gated delta-rule layer: no rows, a state a sequence.  One
        token a row is the tick (``ops/conv_step``, then ``ops/kda_step``
        over the tick's busy list: the live rows' window and state move
        on in place, the others' are not touched); more are the chunked
        form from the state given, which after a padded prompt stands at
        the last live position, not at the last row."""
        from .kda import kda_layer

        with jax.named_scope("block/kda"):
            with jax.named_scope("proj"):
                h = _blocks.norm(arch, x, blk, "ln1")
            with jax.named_scope("conv"):   # the busy list, where first
                slots = tick_slots(work, x.shape[0]) \
                    if x.shape[1] == 1 else None
            y, state, window = kda_layer(
                arch.kda, h, blk["attn"], state, window, live,
                arch.norm_eps, slots)
            with jax.named_scope("proj"):
                x = x + y
        return second_half(x, blk, layer), state, window

    def mamba_block(x, blk, state, window, layer, work):
        """The selective state-space layer: no rows, a state a sequence, as
        ``kda_block``.  One token a row is the tick (``ops/ssm_step`` over
        the tick's busy list), more are the selective scan
        (``ops/selective_scan``) from the state given."""
        from .mamba import mamba_layer

        with jax.named_scope("block/mamba"):
            with jax.named_scope("proj"):
                h = _blocks.norm(arch, x, blk, "ln1")
            with jax.named_scope("core"):   # the busy list, where first
                slots = tick_slots(work, x.shape[0]) \
                    if x.shape[1] == 1 else None
            y, state, window = mamba_layer(
                arch.mamba, h, blk["attn"], state, window, live,
                arch.norm_eps, slots)
            with jax.named_scope("proj"):
                x = x + y
        return second_half(x, blk, layer), state, window

    def attn_block(x, blk, k_cache, v_cache, positions, write_at, q_valid,
                   layer: int = 0, work=None):
        """x (N,S,D) → block output; caches written at ``write_at + i`` for
        the i-th input position; query i attends cache [:q_valid + i + 1).

        An MLA layer (the layer's ``arch.attn_kind``) keeps ONE buffer:
        pass it as ``k_cache`` and None as ``v_cache``; the result is ``(x,
        cache)``.  A delta-rule or selective-scan layer takes ``(state,
        window)`` there and no position: the state says where it stands.

        ``write_at``/``q_valid`` may be RANK-1 vectors of length N (the
        serving tick): row ``b`` then writes at ``write_at[b]`` and
        attends its own prefix ``[:q_valid[b] + i + 1)`` — the ragged
        iteration-level batch.  On a TPU the one-token tick takes the
        flash-decode kernel either way (it walks the live blocks of the
        rows that carry a token, each up to the row's own position;
        ``work``: a tick's memo of its work lists, ``tick_work``); the
        einsum below serves other backends, ``s_q > 1`` chunked fills
        and totals with no 8-aligned block.

        Cache layout is FLAT — ``(B, total, H_kv·head_dim)`` — so every
        cache load streams dense 128-lane rows; per-head structure is
        recovered by view reshapes (einsum fallback) or the segmented
        matmuls inside the flash-decode kernel.  The 4-D layouts measured
        0.7-0.9 µs/position against a ~0.3 µs bandwidth floor in the
        compiled decode loop because XLA lowered the q-length-1 dots to
        VPU multiply+reduce fusions over half-empty 64-lane vregs
        (scripts/profile_decode.py + the round-5 HLO dump).
        """
        kind = arch.attn_kind(layer)
        if kind == "mla":
            return mla_block(x, blk, k_cache, positions, write_at, q_valid,
                             layer, work)
        if kind == "kda":
            return kda_block(x, blk, k_cache, v_cache, layer, work)
        if kind == "mamba":
            return mamba_block(x, blk, k_cache, v_cache, layer, work)
        n = x.shape[0]
        per_row = getattr(write_at, "ndim", 0) == 1
        window = arch.window(layer)

        def attend(q, k, v):
            if not window:
                return attend_rows(q, k, v)
            with jax.named_scope("block/attn/window"):
                return attend_rows(q, k, v)

        def attend_rows(q, k, v):
            s_q = q.shape[1]
            hl, hkv = q.shape[2], k.shape[2]
            flat = lambda t: t.reshape(n, s_q, hkv * head_dim)
            prefill = s_q > 1 and isinstance(write_at, int) \
                and write_at == 0 and isinstance(q_valid, int) \
                and q_valid == 0
            if window and not prefill:
                # A layer that sees the last ``window`` tokens keeps a
                # RING, position p at row ``p % window``.  The tick writes
                # there and attends the ring as it would a rows buffer —
                # at most ``window`` rows, ``pos + 1`` before the first
                # wrap (a position beyond the buffer masks nothing), in
                # whatever order: each key was rotated at its own position
                # before it was cached.
                if s_q != 1:
                    raise NotImplementedError(
                        f"layer {layer} keeps a ring of {window} rows: it "
                        f"takes a whole prompt or one token a row, not a "
                        f"chunk of {s_q} behind a cache")
                at = write_at % window
            else:
                at = write_at
            # one-row decode appends go through the Pallas in-place
            # writers (ops/kv_cache.py) — the tick's per-slot positions
            # over the busy slots alone, K and V in one call; the closed
            # batch's scalar position over every row: the XLA dus costs a
            # full extra pass over the cache per tick, its vmap a loop
            # over every slot; prefill's slab write (s_q > 1) is a dus
            with jax.named_scope("cache_write"):
                if window and prefill:
                    # the ring of the prompt's REAL rows (``live``): a
                    # padded row would land on a real one's place
                    s_real = (jnp.full((n,), s_q, jnp.int32) if live is None
                              else live.sum(-1).astype(jnp.int32))
                    kc, vc = (_blocks.ring_rows(flat(t), s_real, window
                                                ).astype(c.dtype)
                              for t, c in ((k, k_cache), (v, v_cache)))
                else:
                    kc, vc = write_new_rows((k_cache, v_cache),
                                            (flat(k), flat(v)), at, work)
            if prefill:
                # PREFILL: pure causal self-attention over the prompt —
                # the flash kernels, not the naive einsum, which would
                # materialize an (n, h, s_q, total) fp32 score tensor
                # (268 MB/layer at the bench config; the HLO cost model
                # ranked its softmax reductions above every decode op,
                # and its cost GREW with the cache length, polluting the
                # measured per-token decode rate).  Under a window, the
                # band ``0 <= q - k < window`` of it.
                from ..ops.flash_attention import flash_attention
                ctx = flash_attention(q, k, v, causal=True, window=window)
                return ctx.astype(x.dtype), (kc, vc)
            from ..ops.decode_attention import (_pick_block_s,
                                                 decode_attend,
                                                 decode_attend_gqa,
                                                 zero_idle_rows)
            if s_q == 1 and jax.default_backend() == "tpu" \
                    and _pick_block_s(kc.shape[1]) > 0:
                # DECODE on TPU: one flash-decode Pallas pass — the
                # cache of the rows that carry a token read once at full
                # lane density (ops/decode_attention), each row up to its
                # own ``write_at`` (scalar: the closed batch; vector: the
                # serving tick's slots).  GQA has the same face
                # (``decode_attend_gqa``: its own kernel at heads of whole
                # lane tiles, else the beam kernel).  Odd totals with no
                # 8-aligned S-block (e.g. a max_new=1 probe's 513) stay on
                # the einsum fallback below.
                lists = tick_work(work, write_at, n, kc.shape[1])
                if hl == hkv:
                    ctx = decode_attend(
                        q.reshape(n, hl * head_dim), kc, vc, write_at,
                        busy_rows(), n_heads=hkv, head_dim=head_dim,
                        work=lists)
                else:
                    ctx = decode_attend_gqa(
                        q.reshape(n, hl * head_dim), kc, vc, write_at,
                        busy_rows(), n_q_heads=hl, n_kv_heads=hkv,
                        head_dim=head_dim, work=lists)
                return ctx.reshape(n, 1, hl, head_dim), (kc, vc)
            # Fallback (non-TPU backends, chunked fills, unaligned
            # totals): grouped einsum attention against head-view
            # reshapes of the flat cache.
            # Per-query valid lengths make one formula serve chunked
            # fills (causal) and decode (full prefix): query i sees
            # q_valid + i + 1 entries.
            total = kc.shape[1]
            kc4 = kc.reshape(n, total, hkv, head_dim)
            vc4 = vc.reshape(n, total, hkv, head_dim)
            if per_row:
                # (n, 1, 1, s_q, 1): each row's own valid prefix
                valid = (q_valid[:, None] + jnp.arange(s_q)[None] + 1
                         )[:, None, None, :, None]
            else:
                valid = (q_valid + jnp.arange(s_q) + 1
                         )[None, None, None, :, None]
            # Grouped attention against the UN-expanded cache (GQA's
            # inference payoff): q heads regrouped onto their KV head — no
            # per-tick n_heads-sized cache copy.
            g = hl // hkv
            q5 = q.reshape(n, s_q, hkv, g, head_dim)
            s = jnp.einsum("bqhgd,bkhd->bhgqk", q5, kc4,
                           preferred_element_type=jnp.float32) \
                / (head_dim ** 0.5)
            mask = (jnp.arange(total)[None, None, None, None, :]
                    < valid)
            s = jnp.where(mask, s, -1e30)
            p = jax.nn.softmax(s, axis=-1)
            ctx = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(vc4.dtype), vc4,
                             preferred_element_type=jnp.float32
                             ).astype(x.dtype)
            if s_q == 1:    # the kernels' contract: an idle row reads 0
                ctx = zero_idle_rows(ctx, busy_rows())
            return ctx, (kc, vc)

        return block_with(x, blk, positions, attend, layer)

    attn_block.moe_routing = moe_routing
    attn_block.arch = arch
    return embed, attn_block, block_with, rope


def _run_layer(attn_block, x, blk, bufs, positions, write_at, q_valid,
               layer: int, work=None):
    """One block over the layer's cache tuple ``bufs`` — ``(k, v)`` or one
    latent buffer — returning ``(x, new cache tuple)``.  ``work``: a tick's
    memo of work lists (``attn_block``)."""
    x, *new = attn_block(x, blk, bufs[0], bufs[1] if len(bufs) > 1 else None,
                         positions, write_at, q_valid, layer, work)
    return x, tuple(new)


def _routing(attn_block):
    """The expert layers' routing of one traced forward: ``(counts,
    routes)`` — the int32 count vectors summed over layers, and the
    chosen experts ``(N, S_q, expert layers, top_k)``; None for a model
    without experts."""
    if not attn_block.moe_routing:
        return None
    counts = [c for c, _ in attn_block.moe_routing]
    return (sum(counts[1:], counts[0]),
            jnp.stack([idx for _, idx in attn_block.moe_routing], axis=2))


def _check_length(params, total: int, rope: bool) -> None:
    if not rope and total > params["pos_embed"].shape[0]:
        raise ValueError(
            f"prompt + max_new_tokens = {total} exceeds the learned "
            f"pos_embed max_len {params['pos_embed'].shape[0]}; shorten the "
            f"generation or init the model with pos_impl='rope'")


def _kv_heads(params, head_dim: int) -> int:
    """KV heads of the model's MHA/GQA layers (the first one's), 0 where
    it has none: an MLA layer keeps one shared latent row, a delta-rule or
    selective-scan layer (``conv``) a state — no per-head K/V."""
    for blk in params["blocks"]:
        a = blk["attn"]
        if "wkv" in a:
            return a["wkv"].shape[1] // (2 * head_dim)
        if "wqkv" in a and "conv" not in a:
            return a["wqkv"].shape[1] // (3 * head_dim)
    return 0


def _prefill(params, embed, attn_block, prompt, total: int, head_dim: int):
    """Run the full prompt through the stack, returning ``(h_final,
    caches)`` with per-layer caches of length ``total`` (prompt written,
    tail zeros): per layer the tuple of flat ``(B, total, columns)``
    buffers its attention declares (``blocks.cache_layout``; ``(k, v)`` of
    ``H_kv·head_dim`` columns for MHA/GQA — see ``attn_block``), the ``(B,
    window, columns)`` ring of a windowed layer, or the ``(B,) + shape``
    state it declares, after the prompt's live rows."""
    arch = attn_block.arch
    b, s_p = prompt.shape
    layout = _blocks.cache_layout(arch, len(params["blocks"]),
                                  _kv_heads(params, head_dim) * head_dim, "")
    with jax.named_scope("prefill/embed"):
        positions = jnp.arange(s_p)
        x = embed(prompt, positions)
    caches = []
    for i, (blk, bufs) in enumerate(zip(params["blocks"], layout)):
        with jax.named_scope("cache_write"):
            zeros = [jnp.zeros(_blocks.buffer_shape(buf, b, total),
                               (buf[1] if _blocks.is_state(buf) else None)
                               or x.dtype) for buf in bufs]
        x, new = _run_layer(attn_block, x, blk, zeros, positions, 0, 0, i)
        if arch.window(i) or arch.attn_kind(i) == "mamba":
            # a ring is a gather of the layer's k and v that nothing wants
            # before the pool is written at the program's end: left alone,
            # the compiler defers every such gather and keeps each sliding
            # layer's (S, columns) k and v alive until then (0.9 GB more
            # temporaries at 40 layers and S = 3072: my ahead-of-time
            # compile, PR 33).  A selective-scan layer's window is such a
            # slice of its (S, E) ``u`` (0.27 GB more at 26 layers and S =
            # 1024: PR 40)
            with jax.named_scope("cache_write"):
                x, new = jax.lax.optimization_barrier((x, new))
        caches.append(new)
    with jax.named_scope("prefill/head"):
        return _blocks.norm(arch, x, params, "lnf"), caches


def _greedy_token(table, h_last, axis_name: str):
    """Vocab-parallel greedy next token from ``h_last (N, D)`` against the
    VOCAB-SHARDED embedding ``table (V/P, D)``: per-shard (max, argmax)
    then a global (pmax, pmin-over-winners) pair — the full ``(N, V)``
    logits never materialize on one chip.  An exact-fp tie across shards
    resolves to the LOWEST winning index (argmax convention).  Shared by
    :func:`lm_generate` (``temperature=0``) and the serving engine's
    per-tick step, so batched-slot decode is token-exact against the
    closed-batch generator."""
    from ..ops import collective as _col

    vocab_per = table.shape[0]
    start = jax.lax.axis_index(axis_name) * vocab_per
    logits = jnp.einsum("bd,vd->bv", h_last, table,
                        preferred_element_type=jnp.float32)
    local_best = logits.max(-1)
    local_idx = start + logits.argmax(-1)
    # accounted face: the serving tick's argmax pair must be ledger-
    # visible for the shard-flow static↔dynamic reconciliation
    gbest = _col.pmax(local_best, axis_name)
    winner = (local_best == gbest)
    return _col.pmin(
        jnp.where(winner, local_idx, jnp.int32(2 ** 30)), axis_name)


def _next_token(table, h_last, axis_name, keys, temps, step_pos):
    """Per-row greedy-OR-sampled next token from ``h_last (N, D)`` —
    the serving tick's selection step (ISSUE 9 sampling plumbing).

    ``keys (N, 2) uint32`` is each row's REQUEST rng key, ``temps (N,)``
    its temperature (``<= 0`` → greedy), ``step_pos (N,) int32`` the
    position being generated.  Rows with ``temps > 0`` draw the exact
    Gumbel trick of :func:`lm_generate`'s sampled path — same key
    folding ``fold_in(fold_in(rng, step_pos), axis_index)``, same
    ``(1, V/P)`` uniform draw per row — so a request sampled through
    the shared serving pool is TOKEN-EXACT vs ``lm_generate(rng=...)``
    alone at the same key (the tests/test_serving_disagg.py oracle).
    Rows with ``temps <= 0`` reproduce :func:`_greedy_token` bit-for-
    bit (the selection happens BEFORE the shared pmax/pmin pair, which
    is rowwise).  ONE (pmax, pmin) pair either way: the full ``(N, V)``
    logits never materialize on one chip."""
    from ..ops import collective as _col

    vocab_per = table.shape[0]
    start = jax.lax.axis_index(axis_name) * vocab_per
    logits = jnp.einsum("bd,vd->bv", h_last, table,
                        preferred_element_type=jnp.float32)
    g_best = logits.max(-1)
    g_idx = start + logits.argmax(-1)

    def row_gumbel(key, sp):
        # mirror lm_generate's logits_next exactly: step-pos salt, then
        # axis salt, then a (1, V/P) uniform (the B=1 oracle's shape —
        # threefry bits depend on the flat draw count, asserted by the
        # token-exactness test)
        k = jax.random.fold_in(jax.random.fold_in(key, sp),
                               jax.lax.axis_index(axis_name))
        return -jnp.log(-jnp.log(
            jax.random.uniform(k, (1, vocab_per), minval=1e-20)))[0]

    sample = temps > 0.0

    def sampled_branch():
        gumbel = jax.vmap(row_gumbel)(keys, step_pos)
        safe_t = jnp.where(sample, temps, 1.0)
        scored = logits / safe_t[:, None] + gumbel
        s_best = scored.max(-1)
        s_idx = start + scored.argmax(-1)
        return (jnp.where(sample, s_best, g_best),
                jnp.where(sample, s_idx, g_idx))

    # an all-greedy batch (the serving default) skips the N×(V/P)
    # threefry draw entirely — cond, not where, so the hot decode tick
    # pays for sampling only when some row actually samples; no
    # collectives inside either branch (the shared pmax/pmin pair
    # below runs unconditionally, so every rank takes the same path
    # through the accounted face)
    local_best, local_idx = jax.lax.cond(
        jnp.any(sample), sampled_branch, lambda: (g_best, g_idx))
    # accounted face, like _greedy_token: the serving tick's argmax pair
    # stays ledger-visible for the shard-flow reconciliation
    gbest = _col.pmax(local_best, axis_name)
    winner = (local_best == gbest)
    return _col.pmin(
        jnp.where(winner, local_idx, jnp.int32(2 ** 30)), axis_name)


def lm_prefill(params, prompt, total: int, *, head_dim: int, axis_name: str,
               arch=None, live=None, with_routing: bool = False):
    """Iteration-level PREFILL step: run the full ``prompt (B, S_p)``
    through the stack, returning ``(h, caches)`` — ``h (B, S_p, D)`` is
    the post-final-layer-norm hidden state (greedy-select the first
    generated token from ``h[:, s_real - 1]``), and ``caches`` is the
    per-layer list of cache tuples (``(k, v)`` flat ``(B, total,
    H_kv·head_dim)`` pairs for MHA/GQA, one latent buffer for MLA) with
    the prompt written at rows ``[0, S_p)``.

    Call INSIDE ``shard_map`` with the model axis bound.  This is the
    "prefill(prompt) → slot" half of the serving engine's per-tick API
    (``chainermn_tpu/serving/engine.py``): the caches slot straight into
    a pool row, and generation continues via :func:`lm_decode_tick` —
    no closed ``lax.scan`` batch required.  ``arch``: the model's
    ``blocks.LMArch`` (None = the GPT-2-style default).  ``live (B, S_p)
    bool``: the positions that carry a token (None: all; a padded prompt's
    padding goes to no expert).  ``with_routing=True`` appends the expert
    layers' ``(counts, routes)`` — the summed int32 routing counts and the
    chosen experts ``(B, S_p, expert layers, top_k)`` — to the result
    (None without experts).
    """
    embed, attn_block, _, rope = _decoder_core(params, head_dim, axis_name,
                                               arch, live)
    _check_length(params, total, rope)
    out = _prefill(params, embed, attn_block, prompt, total, head_dim)
    with jax.named_scope("prefill/head"):
        return out + (_routing(attn_block),) if with_routing else out


def lm_decode_tick(params, tokens, caches, pos, *, head_dim: int,
                   axis_name: str, arch=None, live=None,
                   with_routing: bool = False):
    """ONE iteration-level decode tick: consume ``tokens (N,)`` (the last
    emitted token per row), write each row's cache entry at ``pos`` and
    attend its own cache prefix ``[0, pos]``, returning ``(h_last (N, D),
    new_caches)`` — feed ``h_last`` to :func:`_greedy_token` (or a
    sampler) for the next token.

    ``pos`` is a scalar (all rows at the same position — the closed
    ``lm_generate`` batch) or an ``(N,)`` int32 vector (every row at its
    OWN position — the serving engine's slot pool, where sequences are
    inserted and evicted between ticks).  Call INSIDE ``shard_map`` with
    the model axis bound.  ``arch`` / ``with_routing`` as
    :func:`lm_prefill` (routes ``(N, 1, expert layers, top_k)``); ``live
    (N,) bool``: the rows that carry a token (None: all; the serving
    tick's other slots go to no expert, keep their state, and have none
    of their cache read: on a TPU the flash-decode kernels walk one list
    of the live rows' blocks, built once a cache shape and shared by the
    layers).
    """
    per_row = getattr(pos, "ndim", 0) == 1
    with jax.named_scope("tick/embed"):
        embed, attn_block, _, _ = _decoder_core(
            params, head_dim, axis_name, arch,
            None if live is None else live[:, None])
        arch = attn_block.arch
        positions = pos[:, None] if per_row else pos[None]
        x = embed(tokens[:, None], positions)
    new_caches = []
    work = {}       # rows of a cache -> its work list, for every layer
    for i, (blk, bufs) in enumerate(zip(params["blocks"], caches)):
        # one whole layer, attention half and FFN half: the halves and
        # their stages are told apart by the block's own scopes below it
        # (``block/attn/proj``, ``.../core/cache_write``, ``block/mlp``, ...)
        with jax.named_scope("tick/layer"):
            x, new = _run_layer(attn_block, x, blk, bufs, positions, pos,
                                pos, i, work)
        new_caches.append(new)
    with jax.named_scope("tick/head"):
        h = _blocks.norm(arch, x, params, "lnf")
        out = (h[:, -1], new_caches)
        return out + (_routing(attn_block),) if with_routing else out


def _make_face(mesh: Optional[Mesh], axis_name: str, inner, has_rng: bool,
               requires_rng: bool = False):
    """Shared jit face for the generators: resolve the mesh, cache one
    compiled shard_map program per param STRUCTURE, device_put per spec."""
    from .._compat import shard_map
    from .transformer import transformer_lm_specs

    if mesh is None:
        from ..topology import make_mesh
        mesh = make_mesh(axis_name=axis_name)

    cache = {}

    def apply(params, prompt, rng=None):
        specs = transformer_lm_specs(params, axis_name)
        key = jax.tree_util.tree_structure(specs)
        if key not in cache:
            in_specs = (specs, P(), P()) if has_rng else (specs, P())
            cache[key] = jax.jit(shard_map(
                inner, mesh=mesh, in_specs=in_specs, out_specs=P()))
        sharded = jax.tree_util.tree_map(
            lambda x, sp: jax.device_put(x, NamedSharding(mesh, sp)),
            params, specs)
        if has_rng:
            if rng is None:
                if requires_rng:
                    raise ValueError(
                        "temperature > 0 samples tokens and needs an "
                        "explicit rng: pass jax.random.PRNGKey(...) as the "
                        "third argument (the old silent PRNGKey(0) fallback "
                        "made every default-rng call draw IDENTICAL token "
                        "sequences)")
                # unused at temperature == 0: greedy decode never consumes
                # it, a constant is exactly right (keeps the jit signature)
                rng = jax.random.PRNGKey(0)  # spmd-lint: disable=prng-constant-key
            return cache[key](sharded, prompt, rng)
        return cache[key](sharded, prompt)

    return apply


def lm_generate(params, prompt, rng: Optional[jax.Array] = None, *,
                head_dim: int, axis_name: str,
                max_new_tokens: int, temperature: float = 0.0):
    """Generate ``max_new_tokens`` greedily (or sampled when
    ``temperature > 0``) from ``prompt (B, S_p) int32``.

    Call INSIDE ``shard_map`` with the model axis bound (use
    :func:`make_lm_generator` for the jit face).  Returns ``(B,
    max_new_tokens) int32``.

    RNG CONTRACT: ``temperature > 0`` requires an explicit ``rng`` —
    sampling with a process-constant default key would draw the SAME
    Gumbel noise on every call, so every "random" generation from the
    same prompt would emit identical tokens.  The jit face
    (:func:`make_lm_generator`) enforces this with a ``ValueError``;
    ``temperature == 0`` ignores ``rng`` entirely.
    """
    b, s_p = prompt.shape
    total = s_p + max_new_tokens

    def logits_next(h_last, step_pos):
        """Vocab-parallel next-token choice from ``h_last (B, D)``;
        ``step_pos`` (the position being generated) salts the sampling key
        so every step draws FRESH Gumbel noise."""
        table = params["embed"]
        if temperature <= 0.0:
            return _greedy_token(table, h_last, axis_name)
        vocab_per = table.shape[0]
        start = jax.lax.axis_index(axis_name) * vocab_per
        logits = jnp.einsum("bd,vd->bv", h_last, table,
                            preferred_element_type=jnp.float32)
        # Gumbel trick on the SHARDED logits: per-shard argmax of
        # (logit/T + gumbel) then a global (value, index) max — exact
        # categorical sampling without materializing (B, V) anywhere.
        key = jax.random.fold_in(
            jax.random.fold_in(rng, step_pos),
            jax.lax.axis_index(axis_name))
        gumbel = -jnp.log(-jnp.log(
            jax.random.uniform(key, logits.shape, minval=1e-20)))
        scored = logits / temperature + gumbel
        local_best = scored.max(-1)
        local_idx = start + scored.argmax(-1)
        gbest = jax.lax.pmax(local_best, axis_name)
        # Global argmax; an exact-fp tie across shards resolves to the
        # LOWEST winning index (argmax convention), via pmin over winners.
        winner = (local_best == gbest)
        return jax.lax.pmin(
            jnp.where(winner, local_idx, jnp.int32(2 ** 30)), axis_name)

    # ---- prefill: full prompt through the stack, caches written ----
    h, caches = lm_prefill(params, prompt, total, head_dim=head_dim,
                           axis_name=axis_name)
    first = logits_next(h[:, -1], jnp.int32(s_p))

    # ---- decode: one iteration-level tick per scan step (the SAME
    # per-tick step the serving engine drives between insert/evict) ----
    def tick(carry, i):
        token, caches = carry
        pos = s_p + i - 1  # tick i consumes the (i-1)-th generated token
        h_last, new_caches = lm_decode_tick(
            params, token, caches, pos, head_dim=head_dim,
            axis_name=axis_name)
        nxt = logits_next(h_last, s_p + i)
        return (nxt, new_caches), token

    (last, _), toks = jax.lax.scan(
        tick, (first, caches), jnp.arange(1, max_new_tokens))
    # toks carries tokens 0..max_new-2 (each tick emits its INPUT token);
    # append the final one.
    out = jnp.concatenate([toks.T, last[:, None]], axis=1)
    return out.astype(jnp.int32)


def lm_generate_beam(params, prompt, *, head_dim: int, axis_name: str,
                     max_new_tokens: int, beam_size: int,
                     lazy_reorder: bool = True, attend_impl: str = "auto"):
    """Beam search with the KV cache: the highest-cumulative-log-prob
    continuation of each prompt among ``beam_size`` beams.

    Fixed-length beams (the toy LMs here have no EOS semantics); exact
    under the cumulative-log-prob objective because each beam contributes
    its top-``beam_size`` tokens and the global top-``beam_size`` of
    ``K·K`` candidates can never need a token outside a beam's own top-K.
    TP-composed: per-shard top-K of the vocab-sharded log-probs, one small
    all_gather of ``K`` candidates per shard, replicated merge.  Returns
    ``(B, max_new_tokens) int32`` — the best beam.

    ``lazy_reorder=True`` (default) kills the per-tick cache-reorder
    bandwidth tax that made beam-4 cost 9× greedy per token (round-3
    BENCH): instead of physically gathering the (B·K, total, h, d) caches
    by parent each step (read+write of the whole cache, on top of the
    read attention itself needs), the caches are never moved —

    * prompt K/V is computed once at batch B and SHARED by all beams
      (read once per tick, not K times, and not stored K times);
    * each beam SLOT owns an append-only generated-token cache; a tiny
      ``(B, K, max_new)`` int32 ancestry table says which slot held this
      beam's token at each past position, and only the table is
      reordered by parent (kilobytes, not the gigabyte cache);
    * attention scores are computed against ALL K slots and the ancestry
      mask selects the one true writer per position — K× more score
      FLOPs on a (head_dim)-deep dot, nothing on the bandwidth that
      actually bounds decode.  Softmax runs over the joint
      prompt+generated axis, so the result is numerically the standard
      beam attention.

    ``lazy_reorder=False`` keeps the physical-gather path (the parity
    oracle for tests).
    """
    b, s_p = prompt.shape
    k = beam_size
    total = s_p + max_new_tokens
    embed, attn_block, block_with, rope = _decoder_core(
        params, head_dim, axis_name)
    _check_length(params, total, rope)
    blocks = params["blocks"]

    def shard_logprobs(h_last):
        """(N, D) → local log-probs (N, V/P) + this shard's vocab offset.
        Normalized GLOBALLY (pmax/psum logsumexp across shards)."""
        table = params["embed"]
        logits = jnp.einsum("bd,vd->bv", h_last, table,
                            preferred_element_type=jnp.float32)
        m = jax.lax.pmax(logits.max(-1), axis_name)              # (N,)
        z = jax.lax.psum(jnp.exp(logits - m[:, None]).sum(-1), axis_name)
        logz = m + jnp.log(z)
        start = jax.lax.axis_index(axis_name) * table.shape[0]
        return logits - logz[:, None], start

    def global_topk(h_last):
        """(N, D) → (values (N, K), token_ids (N, K)) — global top-K over
        the sharded vocab; invariant outputs (pmax over value-identical
        gathers fixes the VMA type at zero numeric cost)."""
        logp, start = shard_logprobs(h_last)
        v_loc, i_loc = jax.lax.top_k(logp, k)                    # (N, K)
        i_loc = i_loc + start
        gv = jax.lax.all_gather(v_loc, axis_name, axis=1, tiled=True)
        gi = jax.lax.all_gather(i_loc, axis_name, axis=1, tiled=True)
        gv = jax.lax.pmax(gv, axis_name)   # identical values; type → invariant
        gi = jax.lax.pmax(gi, axis_name)
        v, pos = jax.lax.top_k(gv, k)                            # (N, K)
        ids = jnp.take_along_axis(gi, pos, axis=1)
        return v, ids

    if attend_impl not in ("auto", "kernel", "einsum"):
        raise ValueError(f"attend_impl must be auto|kernel|einsum, "
                         f"got {attend_impl!r}")
    if lazy_reorder:
        return _beam_lazy(params, prompt, embed, attn_block, block_with,
                          global_topk, head_dim=head_dim,
                          axis_name=axis_name,
                          max_new_tokens=max_new_tokens, beam_size=k,
                          attend_impl=attend_impl)

    # ---- prefill once at batch B, then tile caches to B·K ----
    h, caches = _prefill(params, embed, attn_block, prompt, total, head_dim)
    caches = [(jnp.repeat(kc, k, axis=0), jnp.repeat(vc, k, axis=0))
              for kc, vc in caches]
    v0k, i0k = global_topk(h[:, -1])                             # (B, K)
    scores = v0k                                                 # (B, K)
    tokens = i0k.astype(jnp.int32)                               # live beams
    toks_buf = jnp.zeros((b, k, max_new_tokens), jnp.int32)
    toks_buf = toks_buf.at[:, :, 0].set(tokens)

    def tick(carry, i):
        tokens, scores, toks_buf, caches = carry
        pos = s_p + i - 1
        x = embed(tokens.reshape(b * k)[:, None], pos[None])     # (B·K, 1, D)
        new_caches = []
        for blk, (kc, vc) in zip(blocks, caches):
            x, kc, vc = attn_block(x, blk, kc, vc, pos[None], pos, pos)
            new_caches.append((kc, vc))
        h = _layer_norm(x, params["lnf_scale"], params["lnf_bias"])
        tokens, scores, toks_buf, parent = _merge_candidates(
            global_topk, h, scores, toks_buf, i, b, k)
        # Reindex the full caches by the winning parents (the bandwidth
        # tax the lazy path avoids).
        reind = []
        for kc, vc in new_caches:
            shp = kc.shape  # (B·K, total, hkv·hd) flat
            kc = jnp.take_along_axis(
                kc.reshape((b, k) + shp[1:]),
                parent[:, :, None, None], axis=1).reshape(shp)
            vc = jnp.take_along_axis(
                vc.reshape((b, k) + shp[1:]),
                parent[:, :, None, None], axis=1).reshape(shp)
            reind.append((kc, vc))
        return (tokens, scores, toks_buf, reind), None

    if max_new_tokens > 1:
        (tokens, scores, toks_buf, _), _ = jax.lax.scan(
            tick, (tokens, scores, toks_buf, caches),
            jnp.arange(1, max_new_tokens))
    # top_k keeps beams score-sorted, so beam 0 is the winner by invariant.
    return toks_buf[:, 0].astype(jnp.int32)


def _merge_candidates(global_topk, h, scores, toks_buf, i, b, k):
    """Shared beam bookkeeping for BOTH cache strategies: global top-K of
    the K·K candidate continuations, then reorder the token history by the
    winning parents.  Returns ``(tokens, scores, toks_buf, parent)`` —
    the caller decides what ELSE the parents reindex (physical caches vs
    the ancestry table)."""
    v_k, i_k = global_topk(h[:, -1])                             # (B·K, K)
    cand = scores[:, :, None] + v_k.reshape(b, k, k)             # (B, K, K)
    flat = cand.reshape(b, k * k)
    scores, pos_flat = jax.lax.top_k(flat, k)                    # (B, K)
    parent = pos_flat // k                                       # (B, K)
    tokens = jnp.take_along_axis(
        i_k.reshape(b, k, k).reshape(b, k * k), pos_flat, axis=1
    ).astype(jnp.int32)
    toks_buf = jnp.take_along_axis(toks_buf, parent[:, :, None], axis=1)
    toks_buf = toks_buf.at[:, :, i].set(tokens)
    return tokens, scores, toks_buf, parent


def _beam_lazy(params, prompt, embed, attn_block, block_with, global_topk, *,
               head_dim: int, axis_name: str, max_new_tokens: int,
               beam_size: int, attend_impl: str = "auto"):
    """Ancestry-indexed beam decode body (see ``lm_generate_beam``
    docstring): shared prompt cache + per-slot append-only generated
    caches + a reordered index table instead of reordered caches."""
    b, s_p = prompt.shape
    k = beam_size
    blocks = params["blocks"]
    n_kv = _kv_heads(params, head_dim)

    # prefill at batch B; caches sized to the PROMPT only (they are never
    # extended — generated tokens live in the per-slot caches)
    h, pcaches = _prefill(params, embed, attn_block, prompt, s_p, head_dim)
    v0k, i0k = global_topk(h[:, -1])                             # (B, K)
    scores = v0k
    tokens = i0k.astype(jnp.int32)
    toks_buf = jnp.zeros((b, k, max_new_tokens), jnp.int32)
    toks_buf = toks_buf.at[:, :, 0].set(tokens)
    def varying_zeros(shape, dtype):
        # the scan writes device-VARYING K/V (they come from sharded
        # params) into these buffers, so the initial carry must already
        # carry the varying-manual-axes type
        z = jnp.zeros(shape, dtype)
        return pcast_varying(z, axis_name)

    # TIME-MAJOR flat generated caches: row t·k + slot.  Valid rows are a
    # contiguous PREFIX [0, i·k) — and a leading-prefix slice into a
    # Pallas operand is measured copy-free on v5e — so the staged scan
    # below shrinks the streamed segment to the live prefix per stage
    # instead of always reading all k·max_new rows (docs/PERF.md).
    gen = [(varying_zeros((b, max_new_tokens * k, n_kv * head_dim), pk.dtype),
            varying_zeros((b, max_new_tokens * k, n_kv * head_dim), pv.dtype))
           for pk, pv in pcaches]
    anc = jnp.zeros((b, k, max_new_tokens), jnp.int32)
    gen_pos = jnp.arange(max_new_tokens)
    slot_ids = jnp.arange(k)

    def lazy_attn(x, blk, pk, pv, gk, gv, amask_tl, pos, i, t_hi):
        """One block for the (B·K, 1, D) tick input, via the SHARED
        ``block_with`` scaffolding — only the attend stage differs from
        the physical path.

        ``amask_tl (B, K, max_new, K_slots) bool`` — TIME-MAJOR
        (b, beam s, position t, slot l) to match the generated-cache row
        order t·k + l: ancestry ∧ validity — True where slot ``l``'s
        generated row at position ``t`` belongs to beam ``s``'s history.
        Exactly one slot is True per valid t.  ``t_hi`` (static, per
        scan stage) bounds the live prefix window that is read."""

        def attend(q, kk, vv):
            # append this tick's K/V — ALL k slots' rows [(i-1)k, ik) in
            # ONE Pallas range scatter (ops/kv_cache.py, rows=k).
            # Layouts: the shared PROMPT cache is FLAT (b, s_p, hkv·hd);
            # the generated caches are TIME-MAJOR flat
            # (b, max_new·k, hkv·hd), row t·k + slot, read through the
            # static live-prefix window [:t_hi·k] (copy-free slice).
            from ..ops.decode_attention import (_pick_block_s,
                                                beam_attend_parts,
                                                merge_attend_parts)
            from ..ops.kv_cache import cache_append
            gk2, gv2 = cache_append(
                gk, gv, kk.reshape(b, k, n_kv * head_dim),
                vv.reshape(b, k, n_kv * head_dim), (i - 1) * k, axis=1,
                pos_aligned=True)  # (i-1)·k is k-aligned by construction
            hl = q.shape[2]
            g = hl // n_kv
            scale = head_dim ** 0.5
            gk_w = gk2[:, :t_hi * k]
            gv_w = gv2[:, :t_hi * k]
            kernel_ok = (g == 1 and _pick_block_s(s_p) > 0
                         and _pick_block_s(k * t_hi) > 0)
            # ``attend_impl='einsum'`` forces the fallback (the on-chip
            # parity oracle for the kernel path); 'kernel' forces the
            # Pallas path (interpret off-TPU — note interpret-Pallas
            # under shard_map trips VMA checks, so off-chip coverage of
            # the flatten/mask convention lives in tests/test_decode.py
            # :: test_beam_kernel_slot_flattening_convention instead).
            if kernel_ok and (attend_impl == "kernel"
                              or (attend_impl == "auto"
                                  and jax.default_backend() == "tpu")):
                # flash-decode beam path: one Pallas pass per segment
                # (shared prompt, ancestry-masked slots), merged with the
                # standard (m, l, acc) flash combine — the einsum path
                # below pays the same VPU half-lane tax greedy decode did.
                interp = jax.default_backend() != "tpu"
                qf = q.reshape(b * k, hl * head_dim)
                part_p = beam_attend_parts(
                    qf, pk, pv, beams=k, n_heads=n_kv, head_dim=head_dim,
                    interpret=interp)
                part_g = beam_attend_parts(
                    qf, gk_w, gv_w,
                    amask_tl[:, :, :t_hi, :].reshape(b, k, t_hi * k)
                    .astype(jnp.int8),
                    beams=k, n_heads=n_kv, head_dim=head_dim,
                    interpret=interp)
                ctx = merge_attend_parts(
                    [part_p, part_g], n_heads=n_kv, head_dim=head_dim,
                    dtype=x.dtype)
                return ctx.reshape(b * k, 1, hl, head_dim), (gk2, gv2)
            q6 = q.reshape(b, k, n_kv, g, head_dim)
            # prompt scores: shared cache, read ONCE for all K beams
            # (flat caches viewed per-head for the einsum fallback)
            pk4 = pk.reshape(b, s_p, n_kv, head_dim)
            pv4 = pv.reshape(b, s_p, n_kv, head_dim)
            gk5 = gk_w.reshape(b, t_hi, k, n_kv, head_dim)
            gv5 = gv_w.reshape(b, t_hi, k, n_kv, head_dim)
            sp = jnp.einsum("bshgd,bthd->bshgt", q6, pk4,
                            preferred_element_type=jnp.float32) / scale
            # generated scores against ALL slots; the ancestry mask
            # selects the one true writer per position
            sg = jnp.einsum("bshgd,btlhd->bshgtl", q6, gk5,
                            preferred_element_type=jnp.float32) / scale
            sg = jnp.where(amask_tl[:, :, None, None, :t_hi, :], sg, -1e30)
            joint = jnp.concatenate(
                [sp, sg.reshape(b, k, n_kv, g, t_hi * k)], axis=-1)
            p = jax.nn.softmax(joint, axis=-1)
            p_p = p[..., :s_p].astype(pv.dtype)
            p_g = p[..., s_p:].reshape(sg.shape).astype(gv2.dtype)
            ctx = (jnp.einsum("bshgt,bthd->bshgd", p_p, pv4,
                              preferred_element_type=jnp.float32)
                   + jnp.einsum("bshgtl,btlhd->bshgd", p_g, gv5,
                                preferred_element_type=jnp.float32))
            return ctx.astype(x.dtype).reshape(b * k, 1, hl, head_dim), \
                (gk2, gv2)

        return block_with(x, blk, pos[None], attend)

    def make_tick(t_hi):
        def tick(carry, i):
            tokens, scores, toks_buf, anc, gen = carry
            pos = s_p + i - 1
            # position i-1 was written by each slot itself
            anc = jax.lax.dynamic_update_slice_in_dim(
                anc, jnp.broadcast_to(slot_ids[None, :, None], (b, k, 1)),
                i - 1, axis=2)
            # ancestry ∧ validity (only positions < i exist), in
            # (b, s, t, l) order to match the time-major row = t·k + l
            amask_tl = ((anc[:, :, None, :] == slot_ids[None, None, :, None])
                        & (gen_pos[None, None, None, :] < i)
                        ).transpose(0, 1, 3, 2)
            x = embed(tokens.reshape(b * k)[:, None], pos[None])
            new_gen = []
            for blk, (pk, pv), (gk, gv) in zip(blocks, pcaches, gen):
                x, gk, gv = lazy_attn(x, blk, pk, pv, gk, gv, amask_tl,
                                      pos, i, t_hi)
                new_gen.append((gk, gv))
            h = _layer_norm(x, params["lnf_scale"], params["lnf_bias"])
            tokens, scores, toks_buf, parent = _merge_candidates(
                global_topk, h, scores, toks_buf, i, b, k)
            # the parents reorder only the ancestry table (kilobytes) —
            # never the caches; that is the whole point of the lazy path
            anc = jnp.take_along_axis(anc, parent[:, :, None], axis=1)
            return (tokens, scores, toks_buf, anc, new_gen), None
        return tick

    if max_new_tokens > 1:
        # STAGED scans: stage ticks [lo, hi) read only the live-prefix
        # window [:hi·k] of the generated caches (always-full reads were
        # ~half dead; the prefix slice is copy-free).  The chunk
        # heuristic below yields max_new/128 stages for 128-multiples
        # (e.g. 4 stages at 512 → ~5/8 of full-segment traffic), exactly
        # 2 stages for other even counts ≥ 8 (~3/4 of the traffic), and
        # a single full-window scan otherwise.  One tick body compiles
        # per stage, so finer chunking trades compile time for traffic.
        if max_new_tokens % 128 == 0:
            chunk = 128
        elif max_new_tokens % 2 == 0 and max_new_tokens >= 8:
            chunk = max_new_tokens // 2
        else:
            chunk = max_new_tokens
        carry = (tokens, scores, toks_buf, anc, gen)
        lo = 1
        for hi in range(chunk, max_new_tokens + 1, chunk):
            carry, _ = jax.lax.scan(make_tick(hi), carry,
                                    jnp.arange(lo, hi))
            lo = hi
        (tokens, scores, toks_buf, anc, gen) = carry
    return toks_buf[:, 0].astype(jnp.int32)


def make_lm_beam_generator(mesh: Optional[Mesh] = None,
                           axis_name: str = "model", *, head_dim: int,
                           max_new_tokens: int, beam_size: int,
                           lazy_reorder: bool = True,
                           attend_impl: str = "auto"):
    """Eager/jit face of :func:`lm_generate_beam`: ``fn(params, prompt) ->
    (B, max_new) tokens`` over TP-sharded global params."""
    return _make_face(
        mesh, axis_name,
        partial(lm_generate_beam, head_dim=head_dim, axis_name=axis_name,
                max_new_tokens=max_new_tokens, beam_size=beam_size,
                lazy_reorder=lazy_reorder, attend_impl=attend_impl),
        has_rng=False)


def make_lm_generator(mesh: Optional[Mesh] = None, axis_name: str = "model",
                      *, head_dim: int, max_new_tokens: int,
                      temperature: float = 0.0):
    """Eager/jit face: ``fn(params, prompt[, rng]) -> (B, max_new) tokens``
    over TP-sharded global params (``transformer_lm_specs`` layout).

    RNG CONTRACT: with ``temperature > 0`` the ``rng`` argument is
    REQUIRED (``ValueError`` otherwise) — a silent default key would make
    every call sample the identical token sequence.  At ``temperature ==
    0`` (greedy) ``rng`` is ignored and may be omitted."""
    return _make_face(
        mesh, axis_name,
        partial(lm_generate, head_dim=head_dim, axis_name=axis_name,
                max_new_tokens=max_new_tokens, temperature=temperature),
        has_rng=True, requires_rng=temperature > 0.0)
