"""Compiled per-tick decode programs over the slot pool.

The closed-batch generator (``parallel/decode.py::lm_generate``) fuses
prefill + a ``lax.scan`` over new tokens into ONE program — great for
offline batches, useless for serving: nothing can join or leave until
the whole scan retires.  This engine splits the same numerics into two
programs driven from the host, one tick at a time:

* **prefill_into_slot** — full-prompt forward (``lm_prefill``), greedy
  first token from the LAST REAL prompt position, and a
  ``dynamic_update_slice`` of the prompt's K/V slab into the target
  slot's rows of the pool, which the program takes DONATED: the slab
  lands in place.  Compiled once per padded prompt length.
  ``prefill_bucket > 1`` right-pads prompts to bucket multiples to
  bound the number of compiles under mixed lengths: causal attention
  never lets a real token see a pad, and pad rows in the cache sit
  above ``pos`` where the per-row mask — and the occupant's own later
  writes — keep them unreachable.  The default is 1 (no padding):
  padding is mathematically exact but changes the attention reduction's
  length, which can reassociate float sums and flip a machine-eps
  argmax tie, and the engine's contract is TOKEN-exactness against
  ``lm_generate``.
* **tick** — one token for EVERY slot (``lm_decode_tick`` with the
  per-row position vector + ``_greedy_token``), caches appended in
  place per row (the pool donated: no buffer is copied to be written).
  Compiled ONCE for the pool's lifetime: admission and
  eviction change only the host-side position/token vectors, never the
  program.  On a TPU its attention is the flash-decode kernel
  ``lm_generate`` decodes through (``ops/decode_attention.py``), given
  the position vector and the busy mask: each BUSY slot's cache is read
  once, up to the slot's own length, and a slot that serves nobody (free,
  or holding a cached prefix) is not read at all — its attention is 0, and
  no token anyone reads depends on it.  A slot's input token is the
  PREVIOUS tick's result for it,
  taken on the device, unless the host hands one in (the first token
  after a prefill or an install, a prefix hit's owed prompt tokens): so a
  tick can be launched before the one ahead of it has been read back
  (``launch_tick`` / ``collect_tick``; ``tick`` is both in one call).

Token-exactness vs ``lm_generate`` row-by-row is a test invariant
(tests/test_serving.py): both paths run the identical per-row ops — the
batch dimension and the pool's extra cache rows are masked out with
exact zeros, so a request decoded in a shared pool emits bit-identical
tokens to the same request decoded alone.

TP composes exactly as in the closed-batch path: params stay in
``transformer_lm_specs`` layout, pool caches are sharded ``P(None,
None, model)`` (each chip holds its local heads' columns), and the
greedy pick is the (pmax, pmin) pair — the full logits never gather.
Inactive slots still run the tick's dense parts as rows (their output
is discarded) but none of the attention's cache reads; a real-traffic
engine keeps the pool near-full, which is the scheduler's job.
"""

from __future__ import annotations

from typing import Optional

import numpy as np

from ..observability import trace as _trace


class DecodeEngine:
    """Device half of the serving engine: owns the sharded params and the
    compiled prefill/tick programs; the :class:`~chainermn_tpu.serving
    .cache_pool.CachePool` owns the buffers the programs thread through,
    alone: every call here goes through ``pool.update``, which hands the
    program the buffers donated and binds the ones it returns.

    ``params`` are GLOBAL arrays in ``init_tp_transformer_lm`` layout —
    or, with ``arch`` (a ``parallel.blocks.LMArch``), in the layout that
    description names (RMSNorm, gated MLPs, latent attention, experts,
    an untied head: ``parallel/blocks.py``); ``mesh`` must carry
    ``axis_name`` (default: a fresh 1-D mesh over all local devices, like
    ``make_lm_generator``).  The programs thread the pool's caches as a
    pytree, whatever each layer declares — rows a token, a state a slot or
    a ring of a window's rows (``cache_pool.py``).  A model with experts,
    state layers or windowed layers is told
    which rows carry a token (the tick's busy slots, a prompt's real
    positions): the others go to no expert, and leave a layer's state as it
    is — a free or cached slot's state is not the tick's to write, and a
    padded prompt's state stands at its last real token, as its ring holds
    the rows before its last real token and no padded one.  A model with
    experts returns its routing in the
    SAME int32 vector as the tokens: the counts (``moe_counts_tick`` /
    ``moe_counts_prefill`` accumulate them) and the experts chosen for
    each emitted token (``tick_routes (n_slots, expert layers, top_k)``,
    ``prefill_routes (expert layers, top_k)``: the last call's).
    """

    def __init__(self, params, pool, mesh=None, axis_name: str = "model",
                 *, head_dim: int, prefill_bucket: int = 1, arch=None):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from .._compat import shard_map
        from ..parallel import blocks as _blocks
        from ..parallel.decode import _kv_heads

        if mesh is None:
            from ..topology import make_mesh
            mesh = make_mesh(axis_name=axis_name)
        self.mesh = mesh
        self.axis_name = axis_name
        self.head_dim = int(head_dim)
        self.pool = pool
        self.prefill_bucket = max(int(prefill_bucket), 1)
        self.n_kv_heads = _kv_heads(params, head_dim, arch)
        self.rope = "pos_embed" not in params
        self.max_positions = (None if self.rope
                              else int(params["pos_embed"].shape[0]))
        self.arch = _blocks.resolve(arch)
        self._specs = _blocks.lm_specs(self.arch, params, axis_name)
        self._params = jax.tree_util.tree_map(
            lambda x, sp: jax.device_put(x, NamedSharding(mesh, sp)),
            params, self._specs)
        self._shard_map = shard_map
        self._P = P
        self._cache_specs = pool.cache_specs
        # routing counts of the expert layers (``moe.COUNT_FIELDS`` then
        # one entry per held expert), summed over layers in the program
        # and over calls here; empty for a model without experts
        self.n_counts = _blocks.n_count_entries(self.arch)
        self.moe_counts_tick = np.zeros(self.n_counts, np.int64)
        self.moe_counts_prefill = np.zeros(self.n_counts, np.int64)
        self._route_shape = _blocks.route_shape(self.arch)
        self.tick_routes = self.prefill_routes = None
        self._prefill_progs = {}   # padded prompt length -> compiled fn
        self._tick_prog = self._build_tick()
        # the newest tick's result, on the device: the next tick's tokens
        # (placed as a result is, so the first tick is no other program)
        self._last_result = jax.device_put(
            np.zeros(result_size(self.arch, pool.n_slots), np.int32),
            NamedSharding(mesh, P()))
        self._uncollected = 0           # ticks launched and not yet read
        self._operands = {}             # name -> (host values, device array)
        self._prefix_copy_prog = None   # built lazily on first hit
        # program/compile accounting (flight bundles + /statusz report
        # these: a growing prefill-family or a tick_calls≈compile count
        # mismatch is the recompile postmortem signal)
        self.prefill_compiles = 0
        self.prefill_calls = 0
        self.tick_calls = 0
        # of those, the launches made while the tick before was still
        # unread: the device had the next tick queued behind the running one
        self.tick_launches_overlapped = 0
        self.prefix_copies = 0

    # ---- program builders ----
    def _build_tick(self):
        import jax
        import jax.numpy as jnp

        from ..parallel import blocks as _blocks
        from ..parallel.decode import _next_token, lm_decode_tick

        axis, head_dim, arch = self.axis_name, self.head_dim, self.arch
        P = self._P

        # the jitted programs are named after these functions: the
        # profiler's "XLA Modules" line says ``jit_serving_tick``,
        # ``jit_serving_prefill_<s_pad>``, ``jit_serving_prefix_copy``
        def serving_tick(params, caches, prev, override, pos, keys, temps,
                         live):
            # a slot's token: the host's where it hands one in, else what
            # the tick before chose for the slot (``prev``: its whole result)
            with jax.named_scope("tick/embed"):
                tokens = jnp.where(override >= 0, override,
                                   prev[:override.shape[0]])
            h_last, new_caches, routing = lm_decode_tick(
                params, tokens, caches, pos, head_dim=head_dim,
                axis_name=axis, arch=arch, live=live, with_routing=True)
            # the consumed token sits at row ``pos``; the selected next
            # token is position ``pos + 1`` — lm_generate's step_pos
            # salt, so sampling stays token-exact per request
            with jax.named_scope("tick/head"):
                nxt = _next_token(_blocks.head_table(arch, params), h_last,
                                  axis, keys, temps, pos + 1)
                return _with_routing(nxt, routing), new_caches

        # ``live``: the slots that carry a request's token — the attention
        # reads only their cache, expert layers route only them, state
        # layers move only them on.  The pool is DONATED to every program
        # that returns it: the row write lands in place (un-donated, XLA
        # copied every buffer first)
        return jax.jit(self._shard_map(
            serving_tick, mesh=self.mesh,
            in_specs=(self._specs, self._cache_specs) + (P(),) * 6,
            out_specs=(P(), self._cache_specs)), donate_argnums=(1,))

    def _build_prefill(self, s_pad: int):
        import jax
        import jax.numpy as jnp

        from ..parallel import blocks as _blocks
        from ..parallel.decode import _next_token, lm_prefill

        axis, head_dim, arch = self.axis_name, self.head_dim, self.arch
        P = self._P

        def prefill_inner(params, caches, prompt, s_real, slot, key, temp):
            # slab caches sized to the padded prompt only; pads are above
            # every real row and never read back (causal + pos mask).  A
            # state is not rows, nor is a ring: the pads must not move
            # them, and go to no expert (``real``)
            with jax.named_scope("prefill/embed"):
                real = (jnp.arange(s_pad) < s_real)[None]
            h, slabs, routing = lm_prefill(
                params, prompt, s_pad, head_dim=head_dim, axis_name=axis,
                arch=arch, live=real, with_routing=True)
            with jax.named_scope("prefill/head"):
                if routing is not None:     # the emitting position's routes
                    routing = (routing[0], jax.lax.dynamic_index_in_dim(
                        routing[1], s_real - 1, axis=1, keepdims=False))
                h_last = jax.lax.dynamic_index_in_dim(h, s_real - 1, axis=1,
                                                      keepdims=False)
                # first generated token = position s_real (lm_generate's
                # first = logits_next(h[:, -1], s_p) salt)
                tok = _next_token(_blocks.head_table(arch, params), h_last,
                                  axis, key[None], temp[None], s_real[None])
            # every buffer a layer declares gets its slab, at the slot's
            # rows [0, s_pad) — or the slot's whole state, or its whole ring
            with jax.named_scope("cache_write"):
                new_caches = jax.tree_util.tree_map(
                    lambda c, slab: jax.lax.dynamic_update_slice(
                        c, slab.astype(c.dtype),
                        (slot,) + (0,) * (c.ndim - 1)), caches, slabs)
            with jax.named_scope("prefill/head"):
                return _with_routing(tok, routing), new_caches

        prefill_inner.__name__ = f"serving_prefill_{s_pad}"
        return jax.jit(self._shard_map(
            prefill_inner, mesh=self.mesh,
            in_specs=(self._specs, self._cache_specs, P(), P(), P(), P(),
                      P()),
            out_specs=(P(), self._cache_specs)), donate_argnums=(1,))

    def _build_prefix_copy(self):
        """Slot-to-slot cache slab copy — the prefix cache's copy-on-
        extend device half (ISSUE 7).  Copies the ENTIRE src slot row
        into dst for every buffer of every layer (a K/V pair, a latent
        buffer, a state, a ring: whatever the pool declares): rows beyond the
        matched prefix length
        carry stale K/V, but they are unreachable by the standard
        above-``pos`` masking argument and the next occupant's writes
        land below its own pos first — so the program needs no length
        operand and compiles ONCE for the pool's lifetime (src/dst are
        tiny traced scalars, never static)."""
        import jax

        def serving_prefix_copy(caches, src, dst):
            return jax.tree_util.tree_map(
                lambda c: jax.lax.dynamic_update_slice(
                    c, jax.lax.dynamic_index_in_dim(c, src, axis=0,
                                                    keepdims=True),
                    (dst,) + (0,) * (c.ndim - 1)), caches)

        P = self._P
        return jax.jit(self._shard_map(
            serving_prefix_copy, mesh=self.mesh,
            in_specs=(self._cache_specs, P(), P()),
            out_specs=self._cache_specs), donate_argnums=(0,))

    # ---- serving faces (host-driven, one call per engine iteration) ----
    def padded_len(self, s_real: int) -> int:
        b = self.prefill_bucket
        return ((int(s_real) + b - 1) // b) * b

    def prefill_into_slot(self, prompt_tokens, slot: int, *,
                          rng=None, temperature: float = 0.0) -> int:
        """Prefill ``prompt_tokens (S,)`` into ``slot``: writes the K/V
        slab into the pool's caches, sets ``pool.pos[slot]``, and returns
        the FIRST generated token — greedy at ``temperature <= 0``,
        Gumbel-sampled with the request's ``rng`` key otherwise (the
        ``lm_generate`` sampling contract, ISSUE 9).  One compile per
        padded length, cached; rng/temperature are traced operands, so
        greedy and sampled requests share the program."""
        import jax.numpy as jnp

        prompt = np.asarray(prompt_tokens, np.int32).reshape(1, -1)
        s_real = prompt.shape[1]
        s_pad = self.padded_len(s_real)
        if s_pad > self.pool.max_total:
            raise ValueError(
                f"padded prompt length {s_pad} exceeds pool max_total "
                f"{self.pool.max_total}")
        if self.max_positions is not None and s_pad > self.max_positions:
            raise ValueError(
                f"padded prompt length {s_pad} exceeds the learned "
                f"pos_embed max_len {self.max_positions}")
        with _trace.span("serving/prefill/stage", cat="serving"):
            if s_pad > s_real:
                prompt = np.pad(prompt, ((0, 0), (0, s_pad - s_real)))
            prog = self._prefill_progs.get(s_pad)
            if prog is None:
                prog = self._prefill_progs[s_pad] = self._build_prefill(
                    s_pad)
                self.prefill_compiles += 1
                from ..observability import flight as _flight
                _flight.note("compile", program="serving_prefill",
                             padded_len=s_pad,
                             family_size=len(self._prefill_progs))
            self.prefill_calls += 1
            key = (np.zeros(2, np.uint32) if rng is None
                   else np.asarray(rng, np.uint32).reshape(2))
            operands = (jnp.asarray(prompt), jnp.int32(s_real),
                        jnp.int32(slot), jnp.asarray(key),
                        jnp.float32(temperature))
        with _trace.span("serving/prefill/dispatch", cat="serving"):
            tok = self.pool.update(
                lambda caches: prog(self._params, caches, *operands))
        self.pool.pos[slot] = s_real
        with _trace.span("serving/prefill/readback", cat="serving"):
            out = np.asarray(tok)
        if self.n_counts:
            self.moe_counts_prefill += out[1:1 + self.n_counts]
            self.prefill_routes = out[1 + self.n_counts:].reshape(
                self._route_shape)
        return int(out[0])

    def copy_prefix(self, src_slot: int, dst_slot: int,
                    prefix_len: int) -> None:
        """Copy-on-extend entry: clone ``src_slot``'s K/V slab into
        ``dst_slot`` and set ``pool.pos[dst_slot] = prefix_len`` so the
        occupant's next write lands at the first un-cached position.
        The source slot is READ-ONLY shared state (refcounted by the
        prefix cache).  The program takes the pool donated and writes
        the destination slot's rows in place; the source slot's rows
        are read before the write and are no part of it, so the cached
        rows stay as they were (``src == dst`` rewrites a row with
        itself).  One compiled program for the pool's lifetime (asserted
        by the ``serving.prefix_copy`` analysis entry point)."""
        import jax.numpy as jnp

        if not (0 < int(prefix_len) <= self.pool.max_total):
            raise ValueError(
                f"prefix_len {prefix_len} out of range (0, "
                f"{self.pool.max_total}]")
        if (self.pool.state_bytes_per_slot or self.pool.ring_bytes_per_slot) \
                and int(prefix_len) != int(self.pool.pos[src_slot]):
            # rows [0, k) are the rows of any prefix; a state is the state
            # of ONE position, the one the source slot stands at, and a
            # ring holds the window's rows before that position alone
            what = "state" if self.pool.state_bytes_per_slot else "ring"
            raise ValueError(
                f"slot {src_slot} holds a layer {what} at position "
                f"{int(self.pool.pos[src_slot])}: a prefix of {prefix_len} "
                f"tokens has no {what} to copy")
        if self._prefix_copy_prog is None:
            self._prefix_copy_prog = self._build_prefix_copy()
            from ..observability import flight as _flight
            _flight.note("compile", program="serving_prefix_copy")
        self.prefix_copies += 1
        src, dst = jnp.int32(src_slot), jnp.int32(dst_slot)
        self.pool.update(
            lambda caches: (None, self._prefix_copy_prog(caches, src, dst)))
        self.pool.pos[dst_slot] = int(prefix_len)

    def launch_tick(self, override: np.ndarray, keys=None, temps=None,
                    live=None):
        """Launch one decode tick for ALL slots and return without waiting
        for it: slot ``i`` consumes ``override[i]`` where that is >= 0,
        else the token the PREVIOUS launch chose for it (still on the
        device), at the pool's per-slot positions; K/V is appended in
        place and every ``live`` slot's position advances (``live``: the
        slots that carry a request's token, default the pool's busy slots
        — the others hold theirs: ``CachePool.advance``).  ``keys (n_slots,
        2) uint32`` / ``temps (n_slots,)`` carry each slot's request rng
        and temperature (ISSUE 9 sampling plumbing); None = all-greedy
        (dummy keys, never consumed).  The result starts on its way to the
        host at once; :meth:`collect_tick` takes what this returns."""
        import jax.numpy as jnp

        self.tick_calls += 1
        self.tick_launches_overlapped += bool(self._uncollected)
        with _trace.span("serving/tick/stage", cat="serving"):
            # COPY at the jax boundary: on CPU ``jnp.asarray`` may
            # zero-copy alias the host buffer, and dispatch is ASYNC — an
            # in-place ``pos += 1`` below would race the still-executing
            # tick (seen as a repeated first token under cold-compile
            # latency).
            pos = jnp.asarray(np.array(self.pool.pos, np.int32, copy=True))
            if keys is None:
                keys = np.zeros((self.pool.n_slots, 2), np.uint32)
            if temps is None:
                temps = np.zeros(self.pool.n_slots, np.float32)
            if live is None:
                live = self.pool.busy_mask()
            operands = (self._last_result,
                        self._staged("override", override, np.int32), pos,
                        self._staged("keys", keys, np.uint32),
                        self._staged("temps", temps, np.float32),
                        self._staged("live", live, bool))
        with _trace.span("serving/tick/dispatch", cat="serving"):
            nxt = self._last_result = self.pool.update(
                lambda caches: self._tick_prog(self._params, caches,
                                               *operands))
            nxt.copy_to_host_async()
        self.pool.advance(live)   # out-of-place: never mutate a buffer
        #                           jax might still read
        self._uncollected += 1
        return nxt

    def _staged(self, name: str, value, dtype):
        """A tick's per-slot operand on the device.  Between admissions and
        ends every one but the positions stands as it was the tick before
        (which slots are live, whose token comes from the device, the
        requests' sampling keys and temperatures), and a transfer costs the
        host more than comparing a few dozen numbers: while the values
        stand, the device array made for them is handed in again (operands
        are never donated).  The kept host copy is private and never
        written, so a CPU array that aliases it is safe."""
        import jax.numpy as jnp

        host = np.array(value, dtype, copy=True)
        kept = self._operands.get(name)
        if kept is None or not np.array_equal(kept[0], host):
            kept = self._operands[name] = (host, jnp.asarray(host))
        return kept[1]

    def collect_tick(self, launched) -> np.ndarray:
        """Wait for a launched tick and return its next token per slot
        (the caller keeps the rows that were live in it).  A model with
        experts: ``tick_routes`` and ``moe_counts_tick`` are this tick's
        from here on."""
        with _trace.span("serving/tick/readback", cat="serving"):
            out = np.asarray(launched)
        self._uncollected -= 1
        if self.n_counts:
            n = self.pool.n_slots
            self.moe_counts_tick += out[n:n + self.n_counts]
            self.tick_routes = out[n + self.n_counts:].reshape(
                (n,) + self._route_shape)
            out = out[:n]
        return out

    def tick(self, last_tokens: np.ndarray, keys=None,
             temps=None) -> np.ndarray:
        """One decode tick, launched and read back in one call: every
        slot consumes ``last_tokens (n_slots,)`` (all >= 0: the host
        hands every token in)."""
        return self.collect_tick(self.launch_tick(last_tokens, keys, temps))


def result_size(arch, n_slots: int) -> int:
    """Entries of a tick's one int32 result: a token a slot, then — where
    the model has expert layers — the routing counts and each slot's
    chosen experts (:func:`_with_routing`)."""
    from ..parallel import blocks as _blocks

    return (n_slots * (1 + int(np.prod(_blocks.route_shape(arch))))
            + _blocks.n_count_entries(arch))


def _with_routing(tokens, routing):
    """The program's one int32 result: the tokens, then — where the model
    has expert layers — their routing counts and the experts chosen for
    each emitting row; one readback, no second transfer."""
    import jax.numpy as jnp

    if routing is None:
        return tokens
    counts, routes = routing
    return jnp.concatenate([tokens, counts.astype(tokens.dtype),
                            routes.reshape(-1).astype(tokens.dtype)])
