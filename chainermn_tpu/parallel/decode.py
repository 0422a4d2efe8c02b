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

Parameter layouts, a model's description and what each layer keeps:
``parallel/blocks.py``.  A layer's attention half is its KIND's serving
block — ``_mha_block``, ``_mla_block``, ``_kda_block``, ``_mamba_block``
below — reached through the one table ``blocks.LAYER_KINDS``
(``blocks.layer_kind``), which refuses a kind it lacks by name.  Each takes
the forward's :class:`_Core`, the layer's WHOLE buffer tuple (what
``blocks.cache_layout`` declares for it) and the forward's :class:`_Work`,
and returns ``(x, the buffers after it)``.
"""

from __future__ import annotations

from functools import partial
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import blocks as _blocks
from .tensor_parallel import row_parallel_dense
from .transformer import _project_qkv


class _Work(NamedTuple):
    """Where ONE forward stands, the same for every layer of it."""
    #: the input tokens' positions (the table's rows, the rotation's
    #: angles): ``(S_q,)`` shared or ``(N, S_q)`` a row
    positions: Any
    #: the rows before the first input token: the i-th is written at ``at +
    #: i`` and attends ``[0, at + i]``.  The int 0 with ``S_q > 1``: a whole
    #: prompt; a scalar: the closed batch's tick; ``(N,)``: the serving tick
    at: Any
    #: a tick's memo of its work lists, built where the first layer wants
    #: one and handed to every later one; None: each kernel builds its own
    lists: Optional[dict] = None

    def whole_prompt(self, s_q: int) -> bool:
        return s_q > 1 and isinstance(self.at, int) and self.at == 0


class _Core:
    """What the blocks of one traced forward share.  ``arch`` (a
    ``blocks.LMArch``; None = the GPT-2-style default) names the block's
    vocabulary — norm, MLP, attention and layer kinds, head, embedding
    scale — read here once and shared with the training loss
    (``parallel/transformer.py``).  ``live (N, S_q) bool`` names the rows
    that carry a token (None: all) — the expert layers send the others to no
    expert, a state layer (delta rule, selective scan) leaves their state as
    it is, and the tick's attention (``S_q == 1``) reads their cache not at
    all: such a row's context is 0.

    Every equation of a block lies under one ``jax.named_scope`` of the
    vocabulary a traced program's device time is split by
    (docs/OBSERVABILITY.md, "Device time by scope"): the attention half is
    ``block/{attn,mla,kda,mamba}/proj``, ``.../core`` and ``cache_write``, the
    FFN half ``block/mlp`` — ``with`` blocks only, never a function layer.
    """

    def __init__(self, params, head_dim: int, axis_name: str, arch=None,
                 live=None):
        self.params, self.arch = params, _blocks.resolve(arch)
        self.head_dim, self.axis_name, self.live = head_dim, axis_name, live
        self.rope = "pos_embed" not in params   # positions are rotations
        self.moe_routing = []   # the expert layers' (counts, idx), as traced

    def busy_rows(self):
        """``(N,) bool`` of a tick's rows that carry a token (None: all)."""
        return None if self.live is None else self.live[:, 0]

    def tick_work(self, work: _Work, pos, n, rows):
        """The flash-decode kernels' work list over an ``(n, rows, ·)``
        cache — the busy slots' live blocks — from the tick's own memo
        (one list a cache shape, built where the first layer of that shape
        attends, handed to every later one); None without a memo: the
        kernel builds its own."""
        from ..ops.decode_attention import work_list

        if work.lists is None:
            return None
        if rows not in work.lists:
            with jax.named_scope("tick/work_list"):
                work.lists[rows] = work_list(pos, self.busy_rows(), n, rows)
        return work.lists[rows]

    def tick_slots(self, work: _Work, n):
        """The tick's busy list over ``n`` slots from its memo (built where
        the first layer wants it, handed to every later one: the row
        writers and the state kernels walk the same list); None without a
        memo: the kernel builds its own."""
        from ..ops.kv_cache import busy_slots

        if work.lists is None:
            return None
        if "slots" not in work.lists:
            # a layer that takes no kernel leaves the list unread, and the
            # compiler drops it
            work.lists["slots"] = busy_slots(self.busy_rows(), n)
        return work.lists["slots"]

    def write_new_rows(self, bufs, rows, write_at, work: _Work):
        """The layer's new ``rows`` (a tuple of ``(N, S_q, W_i)``) into its
        cache buffers ``bufs`` at ``write_at`` — every caller's one door,
        under its ``cache_write`` scope.  The tick (one row a slot, each at
        its own position ``write_at (N,)``, clamped inside the buffer)
        goes through ``ops/kv_cache.py::write_rows``: the busy slots' rows
        alone, in place, over the tick's busy list.  A scalar position keeps
        the closed batch's writers: ``cache_append`` for a K/V pair, a
        ``dynamic_update_slice`` for one buffer."""
        from ..ops.kv_cache import cache_append, write_rows

        if getattr(write_at, "ndim", 0) == 1:
            if rows[0].shape[1] != 1:       # a chunk behind a cache
                return write_rows(bufs, rows, write_at)
            return write_rows(bufs, rows, write_at, self.busy_rows(),
                              slots=self.tick_slots(work, bufs[0].shape[0]))
        if len(bufs) == 2:
            return cache_append(*bufs, *rows, write_at, axis=1)
        return tuple(jax.lax.dynamic_update_slice(
            c, r.astype(c.dtype), (0, write_at, 0))
            for c, r in zip(bufs, rows))

    def embed(self, tokens, positions):
        from .tensor_parallel import vocab_parallel_embedding

        params = self.params
        # The table is VOCAB-SHARDED over the model axis — a plain take
        # would index local rows with global ids.
        x = vocab_parallel_embedding(tokens, params["embed"],
                                     axis_name=self.axis_name)
        x = _blocks.scale_embedding(self.arch, x, params["embed"].shape[1])
        if not self.rope:
            pe = jnp.take(params["pos_embed"], positions, axis=0)
            # (S,) positions broadcast over the batch; (N, S) positions (the
            # serving tick: every slot at its own length) index per row.
            x = x + (pe if positions.ndim == 2 else pe[None])
        return x

    def second_half(self, x, blk, layer):
        """residual stream after attention → norm → the layer's FFN (dense
        MLP or experts, by ``arch``) → residual."""
        with jax.named_scope("block/mlp"):
            h = _blocks.norm(self.arch, x, blk, "ln2")
            y, routing = _blocks.ffn(self.arch, layer, h, blk,
                                     self.axis_name, self.live)
            if routing is not None:
                self.moe_routing.append(routing)
            return x + y

    def routing(self):
        """The expert layers' routing of this forward: ``(counts, routes)``
        — the int32 count vectors summed over layers, and the chosen
        experts ``(N, S_q, expert layers, top_k)``; None for a model
        without experts."""
        if not self.moe_routing:
            return None
        counts = [c for c, _ in self.moe_routing]
        return (sum(counts[1:], counts[0]),
                jnp.stack([idx for _, idx in self.moe_routing], axis=2))


def _mha_attend(core: _Core, q, k, v, bufs, layer: int, work: _Work, dtype):
    """The MHA/GQA layer's ``core`` stage: the new rows into ``bufs = (k
    cache, v cache)`` — FLAT ``(B, total, H_kv·head_dim)``, so every cache
    load streams dense 128-lane rows (ops/decode_attention.py says why) —
    and the context of ``q (N, S_q, H, hd)``: ``(ctx, (k cache, v cache))``.

    ``work.at`` may be a RANK-1 vector of length N (the serving tick): row
    ``b`` then writes at ``at[b]`` and attends its own prefix ``[0, at[b] +
    i]`` — the ragged iteration-level batch.  On a TPU the one-token tick
    takes the flash-decode kernel either way (it walks the live blocks of
    the rows that carry a token, each up to the row's own position;
    ``work.lists``: the tick's memo of its work lists); the einsum below
    serves other backends, ``s_q > 1`` chunked fills and totals with no
    8-aligned block.
    """
    head_dim, live = core.head_dim, core.live
    k_cache, v_cache = bufs
    write_at = work.at
    window = core.arch.window(layer)
    n, s_q, hl = q.shape[:3]
    hkv = k.shape[2]
    flat = lambda t: t.reshape(n, s_q, hkv * head_dim)
    prefill = work.whole_prompt(s_q)
    if window and not prefill:
        # A layer that sees the last ``window`` tokens keeps a RING, position
        # p at row ``p % window``.  The tick writes there and attends the
        # ring as it would a rows buffer — at most ``window`` rows, ``pos +
        # 1`` before the first wrap (a position beyond the buffer masks
        # nothing), in whatever order: each key was rotated at its own
        # position before it was cached.
        if s_q != 1:
            raise NotImplementedError(
                f"layer {layer} keeps a ring of {window} rows: it "
                f"takes a whole prompt or one token a row, not a "
                f"chunk of {s_q} behind a cache")
        at = write_at % window
    else:
        at = write_at
    # one-row decode appends go through the Pallas in-place writers
    # (ops/kv_cache.py) — the tick's per-slot positions over the busy slots
    # alone, K and V in one call; the closed batch's scalar position over
    # every row: the XLA dus costs a full extra pass over the cache per
    # tick, its vmap a loop over every slot; prefill's slab write (s_q > 1)
    # is a dus
    with jax.named_scope("cache_write"):
        if window and prefill:
            # the ring of the prompt's REAL rows (``live``): a padded row
            # would land on a real one's place
            s_real = (jnp.full((n,), s_q, jnp.int32) if live is None
                      else live.sum(-1).astype(jnp.int32))
            kc, vc = (_blocks.ring_rows(flat(t), s_real, window
                                        ).astype(c.dtype)
                      for t, c in ((k, k_cache), (v, v_cache)))
        else:
            kc, vc = core.write_new_rows((k_cache, v_cache),
                                         (flat(k), flat(v)), at, work)
    if prefill:
        # PREFILL: pure causal self-attention over the prompt — the flash
        # kernels, not the naive einsum, which would materialize an (n, h,
        # s_q, total) fp32 score tensor (268 MB/layer at the bench config).
        # Under a window, the band ``0 <= q - k < window`` of it.
        from ..ops.flash_attention import flash_attention
        ctx = flash_attention(q, k, v, causal=True, window=window)
        return ctx.astype(dtype), (kc, vc)
    from ..ops.decode_attention import (_pick_block_s, decode_attend,
                                        decode_attend_gqa, zero_idle_rows)
    if s_q == 1 and jax.default_backend() == "tpu" \
            and _pick_block_s(kc.shape[1]) > 0:
        # DECODE on TPU: one flash-decode Pallas pass — the cache of the
        # rows that carry a token read once at full lane density
        # (ops/decode_attention), each row up to its own ``write_at``
        # (scalar: the closed batch; vector: the serving tick's slots);
        # GQA through its own kernel at heads of whole lane tiles, else the
        # query-group kernel.  Totals with no 8-aligned S-block (a
        # max_new=1 probe's 513) stay on the einsum fallback below.
        lists = core.tick_work(work, write_at, n, kc.shape[1])
        qf = q.reshape(n, hl * head_dim)
        if hl == hkv:
            ctx = decode_attend(qf, kc, vc, write_at, core.busy_rows(),
                                n_heads=hkv, head_dim=head_dim, work=lists)
        else:
            ctx = decode_attend_gqa(qf, kc, vc, write_at, core.busy_rows(),
                                    n_q_heads=hl, n_kv_heads=hkv,
                                    head_dim=head_dim, work=lists)
        return ctx.reshape(n, 1, hl, head_dim), (kc, vc)
    # Fallback (non-TPU backends, chunked fills, unaligned totals): grouped
    # einsum attention against head-view reshapes of the flat cache.
    # Per-query valid lengths make one formula serve chunked fills (causal)
    # and decode (full prefix): query i sees write_at + i + 1 entries.
    total = kc.shape[1]
    kc4 = kc.reshape(n, total, hkv, head_dim)
    vc4 = vc.reshape(n, total, hkv, head_dim)
    if getattr(write_at, "ndim", 0) == 1:
        # (n, 1, 1, s_q, 1): each row's own valid prefix
        valid = (write_at[:, None] + jnp.arange(s_q)[None] + 1
                 )[:, None, None, :, None]
    else:
        valid = (write_at + jnp.arange(s_q) + 1)[None, None, None, :, None]
    # Grouped attention against the UN-expanded cache (GQA's inference
    # payoff): q heads regrouped onto their KV head — no per-tick
    # n_heads-sized cache copy.
    q5 = q.reshape(n, s_q, hkv, hl // hkv, head_dim)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", q5, kc4,
                   preferred_element_type=jnp.float32) / (head_dim ** 0.5)
    mask = jnp.arange(total)[None, None, None, None, :] < valid
    p = jax.nn.softmax(jnp.where(mask, s, -1e30), axis=-1)
    ctx = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(vc4.dtype), vc4,
                     preferred_element_type=jnp.float32).astype(dtype)
    if s_q == 1:    # the kernels' contract: an idle row reads 0
        ctx = zero_idle_rows(ctx, core.busy_rows())
    return ctx, (kc, vc)


def _mha_block(core: _Core, x, blk, bufs, layer: int, work: _Work):
    """The MHA/GQA layer over its ``(k, v)`` pair — rows a token, or under
    a window a ring: ln1 → qkv projection (+ the layer's rotation) →
    :func:`_mha_attend` → the output gate where the model has one → wo
    row-parallel → residual → the layer's FFN."""
    arch, head_dim = core.arch, core.head_dim
    n, s_q = x.shape[0], x.shape[1]
    a = blk["attn"]
    with jax.named_scope("block/attn/proj"):
        h = _blocks.norm(arch, x, blk, "ln1")
        q, k, v = _project_qkv(h, a, head_dim, core.axis_name,
                               arch.attn_bias)
        # the layer's own rotation (theta, the rotated fraction, YaRN),
        # else the model's plain one
        q, k = _blocks.turn_qk(arch, layer, q, k, work.positions, core.rope)
    # the attend stage's own cache append nests as .../core/cache_write
    with jax.named_scope("block/attn/core"), \
            _blocks.window_scope(arch, layer):
        ctx, bufs = _mha_attend(core, q, k, v, bufs, layer, work, x.dtype)
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
            axis_name=core.axis_name)
    return core.second_half(x, blk, layer), bufs


def _mla_block(core: _Core, x, blk, bufs, layer: int, work: _Work):
    """The MLA layer: the token's latent row is written to its ONE buffer,
    a prefill attends in the prefill form through the flash kernel,
    everything else in the absorbed form over the latent rows — on a TPU
    the one-token tick through the flash-decode kernel, one position per
    cache row."""
    from ..ops.decode_attention import _pick_block_s
    from ..ops.flash_attention import resolve_attn_impl

    arch, cfg = core.arch, core.arch.mla
    (cache,) = bufs
    n, s_q = x.shape[0], x.shape[1]
    with jax.named_scope("block/mla"):
        with jax.named_scope("proj"):
            h = _blocks.norm(arch, x, blk, "ln1")
            q_nope, q_rope, c_kv, k_rope = _blocks.mla_project(
                cfg, h, blk["attn"], work.positions, arch.norm_eps)
            rows = _blocks.mla_latent_rows(cfg, c_kv, k_rope)
        with jax.named_scope("cache_write"):
            (cache,) = core.write_new_rows((cache,), (rows,), work.at, work)
        if work.whole_prompt(s_q):
            ctx = _blocks.mla_attend_prefill(
                cfg, q_nope, q_rope, c_kv, k_rope, blk["attn"],
                resolve_attn_impl("auto", s_q))
        else:
            with jax.named_scope("core"):
                valid = (jnp.asarray(work.at, jnp.int32).reshape(-1, 1)
                         + jnp.arange(s_q, dtype=jnp.int32)[None] + 1)
                valid = jnp.broadcast_to(valid, (n, s_q))
                use_kernel = (s_q == 1
                              and jax.default_backend() == "tpu"
                              and _pick_block_s(cache.shape[1]) > 0)
                busy = core.busy_rows() if s_q == 1 else None
                lists = core.tick_work(work, valid[:, 0] - 1, n,
                                       cache.shape[1]) if use_kernel else None
            ctx = _blocks.mla_attend_absorbed(
                cfg, q_nope, q_rope, cache, valid, blk["attn"],
                use_kernel, busy, lists)
        with jax.named_scope("proj"):
            x = x + jnp.matmul(
                ctx, blk["attn"]["wo"],
                preferred_element_type=jnp.float32).astype(x.dtype)
    return core.second_half(x, blk, layer), (cache,)


def _kda_block(core: _Core, x, blk, bufs, layer: int, work: _Work):
    """The gated delta-rule layer: no rows and no position, ``(state,
    window)`` a sequence — the state says where it stands.  One token a row
    is the tick (``ops/conv_step``, then ``ops/kda_step`` over the tick's
    busy list: the live rows' window and state move on in place, the others'
    are not touched); more are the chunked form from the state given, which
    after a padded prompt stands at the last live position, not the last."""
    from .kda import kda_layer

    arch, (state, window) = core.arch, bufs
    with jax.named_scope("block/kda"):
        with jax.named_scope("proj"):
            h = _blocks.norm(arch, x, blk, "ln1")
        with jax.named_scope("conv"):   # the busy list, where first
            slots = core.tick_slots(work, x.shape[0]) \
                if x.shape[1] == 1 else None
        y, state, window = kda_layer(
            arch.kda, h, blk["attn"], state, window, core.live,
            arch.norm_eps, slots)
        with jax.named_scope("proj"):
            x = x + y
    return core.second_half(x, blk, layer), (state, window)


def _mamba_block(core: _Core, x, blk, bufs, layer: int, work: _Work):
    """The selective state-space layer: ``(state, window)`` a sequence, as
    :func:`_kda_block`.  One token a row is the tick (``ops/ssm_step`` over
    the tick's busy list), more are the selective scan
    (``ops/selective_scan``) from the state given."""
    from .mamba import mamba_layer

    arch, (state, window) = core.arch, bufs
    with jax.named_scope("block/mamba"):
        with jax.named_scope("proj"):
            h = _blocks.norm(arch, x, blk, "ln1")
        with jax.named_scope("core"):   # the busy list, where first
            slots = core.tick_slots(work, x.shape[0]) \
                if x.shape[1] == 1 else None
        y, state, window = mamba_layer(
            arch.mamba, h, blk["attn"], state, window, core.live,
            arch.norm_eps, slots)
        with jax.named_scope("proj"):
            x = x + y
    return core.second_half(x, blk, layer), (state, window)


def _kv_heads(params, head_dim: int, arch=None) -> int:
    """K/V heads of the model's first layer whose kind keeps K/V a head
    (``LayerKind.kv_heads``: MHA/GQA), 0 where none does: an MLA layer keeps
    one shared latent row, a delta-rule or selective-scan layer a state."""
    arch = _blocks.resolve(arch)
    for i, blk in enumerate(params["blocks"]):
        heads = _blocks.layer_kind(arch, i).kv_heads
        if heads is not None:
            return heads(blk["attn"], head_dim)
    return 0


def _greedy_token(table, h_last, axis_name: str):
    """Vocab-parallel greedy next token from ``h_last (N, D)`` against the
    VOCAB-SHARDED embedding ``table (V/P, D)``: per-shard (max, argmax)
    then a global (pmax, pmin-over-winners) pair — the full ``(N, V)``
    logits never materialize on one chip.  An exact-fp tie across shards
    resolves to the LOWEST winning index (argmax convention).
    :func:`lm_generate`'s at ``temperature=0``; the serving tick's
    :func:`_next_token` reproduces it bit for bit."""
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
    per-layer list of cache tuples, each what the layer's attention declares
    (``blocks.cache_layout``): flat ``(B, total, columns)`` rows with the
    prompt written at ``[0, S_p)`` and the tail zeros (``(k, v)`` of
    ``H_kv·head_dim`` columns for MHA/GQA, one latent buffer for MLA), the
    ``(B, window, columns)`` ring of a windowed layer, or the ``(B,) +
    shape`` state it declares, after the prompt's live rows.

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
    core = _Core(params, head_dim, axis_name, arch, live)
    arch = core.arch
    if not core.rope and total > params["pos_embed"].shape[0]:
        raise ValueError(
            f"prompt + max_new_tokens = {total} exceeds the learned "
            f"pos_embed max_len {params['pos_embed'].shape[0]}; shorten the "
            f"generation or init the model with pos_impl='rope'")
    b, s_p = prompt.shape
    layout = _blocks.cache_layout(
        arch, len(params["blocks"]),
        _kv_heads(params, head_dim, arch) * head_dim, "")
    with jax.named_scope("prefill/embed"):
        positions = jnp.arange(s_p)
        x = core.embed(prompt, positions)
    work = _Work(positions, 0)
    caches = []
    for i, (blk, bufs) in enumerate(zip(params["blocks"], layout)):
        with jax.named_scope("cache_write"):
            zeros = tuple(jnp.zeros(
                _blocks.buffer_shape(buf, b, total),
                (buf[1] if _blocks.is_state(buf) else None) or x.dtype)
                for buf in bufs)
        kind = _blocks.layer_kind(arch, i)
        x, new = kind.serve(core, x, blk, zeros, i, work)
        if arch.window(i) or kind.deferred_gather:
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
        out = (_blocks.norm(arch, x, params, "lnf"), caches)
        return out + (core.routing(),) if with_routing else out


def lm_decode_tick(params, tokens, caches, pos, *, head_dim: int,
                   axis_name: str, arch=None, live=None,
                   with_routing: bool = False):
    """ONE iteration-level decode tick: consume ``tokens (N,)`` (the last
    emitted token per row), write each row's cache entry at ``pos`` and
    attend its own cache prefix ``[0, pos]``, returning ``(h_last (N, D),
    new_caches)`` — feed ``h_last`` to :func:`_next_token` for the next.

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
    with jax.named_scope("tick/embed"):
        core = _Core(params, head_dim, axis_name, arch,
                     None if live is None else live[:, None])
        positions = pos[:, None] if getattr(pos, "ndim", 0) == 1 \
            else pos[None]
        x = core.embed(tokens[:, None], positions)
    new_caches = []
    work = _Work(positions, pos, {})
    for i, (blk, bufs) in enumerate(zip(params["blocks"], caches)):
        # one whole layer, attention half and FFN half: the halves and
        # their stages are told apart by the block's own scopes below it
        # (``block/attn/proj``, ``.../core/cache_write``, ``block/mlp``, ...)
        with jax.named_scope("tick/layer"):
            x, new = _blocks.layer_kind(core.arch, i).serve(
                core, x, blk, bufs, i, work)
        new_caches.append(new)
    with jax.named_scope("tick/head"):
        h = _blocks.norm(core.arch, x, params, "lnf")
        out = (h[:, -1], new_caches)
        return out + (core.routing(),) if with_routing else out


def lm_generate(params, prompt, rng: Optional[jax.Array] = None, *,
                head_dim: int, axis_name: str,
                max_new_tokens: int, temperature: float = 0.0):
    """Generate ``max_new_tokens`` greedily (or sampled when
    ``temperature > 0``) from ``prompt (B, S_p) int32``.

    Call INSIDE ``shard_map`` with the model axis bound (use
    :func:`make_lm_generator` for the jit face).  Returns ``(B,
    max_new_tokens) int32``.

    ``temperature > 0`` requires an explicit ``rng`` (the jit face's RNG
    CONTRACT); ``temperature == 0`` ignores it entirely.
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


def make_lm_generator(mesh: Optional[Mesh] = None, axis_name: str = "model",
                      *, head_dim: int, max_new_tokens: int,
                      temperature: float = 0.0):
    """Eager/jit face: ``fn(params, prompt[, rng]) -> (B, max_new) tokens``
    over TP-sharded global params (``transformer_lm_specs`` layout): one
    compiled shard_map program per param STRUCTURE, device_put per spec.

    RNG CONTRACT: with ``temperature > 0`` the ``rng`` argument is
    REQUIRED (``ValueError`` otherwise) — a silent default key would make
    every call sample the identical token sequence.  At ``temperature ==
    0`` (greedy) ``rng`` is ignored and may be omitted."""
    from .._compat import shard_map
    from .transformer import transformer_lm_specs

    if mesh is None:
        from ..topology import make_mesh
        mesh = make_mesh(axis_name=axis_name)
    inner = partial(lm_generate, head_dim=head_dim, axis_name=axis_name,
                    max_new_tokens=max_new_tokens, temperature=temperature)
    cache = {}

    def apply(params, prompt, rng=None):
        specs = transformer_lm_specs(params, axis_name)
        key = jax.tree_util.tree_structure(specs)
        if key not in cache:
            cache[key] = jax.jit(shard_map(
                inner, mesh=mesh, in_specs=(specs, P(), P()), out_specs=P()))
        sharded = jax.tree_util.tree_map(
            lambda x, sp: jax.device_put(x, NamedSharding(mesh, sp)),
            params, specs)
        if rng is None:
            if temperature > 0.0:
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

    return apply
