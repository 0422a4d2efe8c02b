"""Expert parallelism: mixture-of-experts layer with all-to-all dispatch.

Reference relationship: SURVEY.md §2.8 lists EP as absent from the
reference — "``alltoall`` primitive exists, which is the EP substrate"
(``chainermn/functions/collective_communication.py`` [uv]).  This module is
the layer the substrate was pointing at, built the TPU way (the
Switch-Transformer / Mesh-TF dispatch formulation, which XLA maps well):

* routing is a dense argmax + cumsum over a ``(tokens, experts)`` one-hot —
  static shapes, no sorting, no dynamic gather — so the whole layer stays
  inside one jitted SPMD program;
* experts are sharded along a named mesh axis (``E_local = E / P`` experts
  per device) and tokens travel to their expert and back with exactly TWO
  ``jax.lax.all_to_all`` collectives riding ICI;
* capacity is fixed (``ceil(T/E * capacity_factor)``): overflow tokens are
  dropped (contribute zero, standard Switch behavior), keeping every shape
  static for XLA;
* the load-balancing auxiliary loss (Switch eq. 4) comes back alongside the
  output; gradients flow through dispatch/combine einsums and the
  all_to_alls automatically (shard_map transposes them).

:func:`moe_dropless` is the DROPLESS layer, served and trained (a served
token that loses an expert is a wrong answer, so nothing is dropped): by
``MoEConfig.router`` sigmoid scores with a selection-only bias,
group-limited top-k, renormalised and scaled gates
(:func:`sigmoid_group_route`) or a softmax over all experts with plain top-k
(:func:`softmax_topk_route`), a layer that is TOLD which experts it holds
(``held = (first, n)``), routes over all of them and computes its own
experts' part, with grouped products over the experts that have tokens
(``ops/moe_gmm.py``: the gate and up projections and their activation one
kernel, the down projection another; at a served tick's few rows two
kernels that take the rows and give the gated sum themselves) beside a
shared expert every token takes (where the model has one).  It runs
without an exchange: a chip's result is its PART of the layer (the exchange
between the parts waits for a four-chip cell).
"""

from __future__ import annotations

import math
from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from .._compat import pcast_varying
from ..topology import DEFAULT_AXIS_NAME


def moe_mlp(x, params, *, axis_name: str, num_experts: int,
            capacity_factor: float = 1.25, activation=jax.nn.gelu,
            router_topk: int = 1):
    """Top-1 (Switch) or top-2 (GShard) MoE MLP over expert-sharded weights.

    Call INSIDE ``shard_map``.  ``x``: local token shard ``(T, D)`` (token/
    batch axis sharded over ``axis_name``).  ``params``:

    * ``router``: replicated ``(D, E)``;
    * ``wi (E_local, D, F)``, ``bi (E_local, F)``, ``wo (E_local, F, D)``,
      ``bo (E_local, D)``: this device's expert shards (``in_spec
      P(axis_name)`` over globally expert-stacked weights).

    ``router_topk=2`` routes each token to its two best experts with
    normalized gates (GShard): second choices queue BEHIND all first
    choices at their expert, so under capacity pressure first choices win —
    the standard priority rule.  Capacity scales with ``router_topk``.

    Returns ``(y, aux_loss)``: ``y (T, D)`` with dropped tokens zero,
    ``aux_loss`` the load-balancing scalar (already globally averaged).
    """
    if router_topk not in (1, 2):
        raise ValueError(f"router_topk must be 1 or 2, got {router_topk}")
    p_size = jax.lax.axis_size(axis_name)
    e = num_experts
    if e % p_size != 0:
        raise ValueError(f"num_experts {e} not divisible by axis size {p_size}")
    e_local = e // p_size
    t, d = x.shape
    capacity = int(math.ceil(router_topk * t / e * capacity_factor))

    # --- route: fp32 softmax for stable gating ---
    logits = jnp.matmul(x, params["router"],
                        preferred_element_type=jnp.float32)  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    expert_idx = jnp.argmax(probs, axis=-1)                  # (T,)
    onehot = jax.nn.one_hot(expert_idx, e, dtype=probs.dtype)  # (T, E)
    gate1 = jnp.sum(probs * onehot, axis=-1)                 # (T,)

    # Load-balancing aux (Switch eq. 4) over GLOBAL first-choice statistics:
    # fraction_e and mean_prob_e are each pmean'd across devices BEFORE the
    # product (mean-of-products ≠ product-of-means when routing is skewed
    # across devices), so the scalar equals the single-device computation on
    # the gathered batch.
    fraction = jax.lax.pmean(jnp.mean(onehot, axis=0), axis_name)
    mean_prob = jax.lax.pmean(jnp.mean(probs, axis=0), axis_name)
    aux = e * jnp.sum(fraction * mean_prob)

    # --- dispatch tensors: position of each token within its expert ---
    # (cumsum-1)*onehot is zero at non-assigned entries, so the row sum is
    # exactly the token's arrival index at its expert.
    position = (jnp.cumsum(onehot, axis=0) - 1.0) * onehot   # (T, E)
    pos_idx = jnp.sum(position, axis=-1).astype(jnp.int32)   # (T,)
    keep = pos_idx < capacity
    pos_onehot = jax.nn.one_hot(pos_idx, capacity, dtype=x.dtype)  # (T, C)
    dispatch = (onehot.astype(x.dtype)[:, :, None] * pos_onehot[:, None, :]
                * keep[:, None, None])                       # (T, E, C)

    if router_topk == 2:
        probs2 = probs * (1.0 - onehot)  # mask the first choice
        idx2 = jnp.argmax(probs2, axis=-1)
        onehot2 = jax.nn.one_hot(idx2, e, dtype=probs.dtype)
        gate2 = jnp.sum(probs * onehot2, axis=-1)
        # Second choices queue behind ALL first choices at their expert.
        first_counts = jnp.sum(onehot, axis=0)               # (E,)
        position2 = (jnp.cumsum(onehot2, axis=0) - 1.0) * onehot2
        pos2_idx = (jnp.sum(position2 + first_counts[None] * onehot2,
                            axis=-1)).astype(jnp.int32)
        keep2 = pos2_idx < capacity
        pos2_onehot = jax.nn.one_hot(pos2_idx, capacity, dtype=x.dtype)
        dispatch2 = (onehot2.astype(x.dtype)[:, :, None]
                     * pos2_onehot[:, None, :] * keep2[:, None, None])
        # Normalized gates over the two choices (standard GShard combine).
        denom = jnp.maximum(gate1 + gate2, 1e-9)
        combine = (dispatch * (gate1 / denom).astype(x.dtype)[:, None, None]
                   + dispatch2
                   * (gate2 / denom).astype(x.dtype)[:, None, None])
        dispatch = dispatch + dispatch2
    else:
        combine = dispatch * gate1.astype(x.dtype)[:, None, None]  # (T, E, C)

    # --- to experts: (T,E,C)×(T,D) → (E,C,D), then all_to_all over ICI ---
    expert_in = jnp.einsum("tec,td->ecd", dispatch, x)
    # Split the expert dim across devices; receive every device's tokens
    # for MY local experts: (E, C, D) → (P·E_local, C, D) blocks.
    recv = jax.lax.all_to_all(expert_in, axis_name, split_axis=0,
                              concat_axis=0, tiled=True)
    # Block p holds device p's tokens for my experts; group per expert.
    recv = recv.reshape(p_size, e_local, capacity, d)
    recv = recv.transpose(1, 0, 2, 3).reshape(e_local, p_size * capacity, d)

    # --- expert compute: batched matmuls, MXU-friendly ---
    h = jnp.einsum("egd,edf->egf", recv, params["wi"],
                   preferred_element_type=jnp.float32).astype(x.dtype)
    h = activation(h + params["bi"][:, None, :])
    out = jnp.einsum("egf,efd->egd", h, params["wo"],
                     preferred_element_type=jnp.float32).astype(x.dtype)
    out = out + params["bo"][:, None, :]

    # --- back to token owners: inverse reshuffle + second all_to_all ---
    out = out.reshape(e_local, p_size, capacity, d).transpose(1, 0, 2, 3)
    out = out.reshape(e, capacity, d)
    back = jax.lax.all_to_all(out, axis_name, split_axis=0,
                              concat_axis=0, tiled=True)     # (E, C, D)
    y = jnp.einsum("tec,ecd->td", combine, back)
    return y.astype(x.dtype), aux.astype(x.dtype)


def init_moe_mlp_params(rng, d_model: int, d_hidden: int, num_experts: int,
                        dtype=jnp.float32) -> dict:
    """GLOBAL params for :func:`moe_mlp` (expert-stacked leaves, leading dim
    ``E``); shard per :func:`moe_mlp_specs`."""
    kr, k1, k2 = jax.random.split(rng, 3)
    e = num_experts
    si = (2.0 / d_model) ** 0.5
    so = (2.0 / d_hidden) ** 0.5
    return {
        "router": (jax.random.normal(kr, (d_model, e)) * 0.02).astype(dtype),
        "wi": (jax.random.normal(k1, (e, d_model, d_hidden)) * si).astype(dtype),
        "bi": jnp.zeros((e, d_hidden), dtype),
        "wo": (jax.random.normal(k2, (e, d_hidden, d_model)) * so).astype(dtype),
        "bo": jnp.zeros((e, d_model), dtype),
    }


def moe_mlp_specs(axis_name: str = DEFAULT_AXIS_NAME) -> dict:
    """PartitionSpecs: router replicated, expert-stacked weights sharded on
    the expert-stack (leading) dim."""
    return {
        "router": P(),
        "wi": P(axis_name),
        "bi": P(axis_name),
        "wo": P(axis_name),
        "bo": P(axis_name),
    }


def make_moe_mlp(num_experts: int, mesh: Optional[Mesh] = None,
                 axis_name: Optional[str] = None,
                 capacity_factor: float = 1.25, activation=jax.nn.gelu,
                 router_topk: int = 1):
    """Eager/jit face: ``fn(x, global_params) -> (y, aux)`` over global
    arrays, tokens sharded over the mesh axis; compiles once per shape."""
    from ._factory import make_global_apply, resolve_mesh_axis

    mesh, ax = resolve_mesh_axis(mesh, axis_name)
    specs = moe_mlp_specs(ax)
    return make_global_apply(
        partial(moe_mlp, axis_name=ax, num_experts=num_experts,
                capacity_factor=capacity_factor, activation=activation,
                router_topk=router_topk),
        mesh, (P(ax), specs), (P(ax), P()))


# --------------------------------------------------------------------------
# the dropless serving layer
# --------------------------------------------------------------------------

#: leading entries of the int32 routing-count vector ``moe_dropless``
#: returns; one entry per held expert (its tokens) follows
COUNT_FIELDS = ("assignments_total", "assignments_held", "experts_hit")


def sigmoid_group_route(x, router, bias, cfg):
    """Sigmoid scoring with a selection-only bias and group-limited top-k
    over ALL ``cfg.n_experts``: ``(idx (T, k) int32, gates (T, k) f32)``.

    ``s = sigmoid(x @ router)`` in float32; selection scores ``s + bias``;
    a group's score is the sum of its two largest selection scores; the
    ``topk_group`` best groups are kept and the ``top_k`` best selection
    scores inside them chosen; the gates are ``s`` (NOT ``s + bias``) at
    the chosen, divided by their sum, times ``routed_scaling_factor``."""
    t = x.shape[0]
    e, g = cfg.n_experts, cfg.n_group
    s = jax.nn.sigmoid(jnp.matmul(
        x.astype(jnp.float32), router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    sel = s + bias.astype(jnp.float32)
    group_score = jax.lax.top_k(sel.reshape(t, g, e // g), 2)[0].sum(-1)
    _, keep = jax.lax.top_k(group_score, cfg.topk_group)          # (T, kg)
    in_kept = (jnp.arange(g)[None, :, None] == keep[:, None, :]).any(-1)
    masked = jnp.where(jnp.repeat(in_kept, e // g, axis=1), sel, -jnp.inf)
    _, idx = jax.lax.top_k(masked, cfg.top_k)
    gates = jnp.take_along_axis(s, idx, axis=1)
    if cfg.norm_topk_prob:
        gates = gates / (gates.sum(-1, keepdims=True) + 1e-20)
    return idx.astype(jnp.int32), gates * cfg.routed_scaling_factor


def softmax_topk_route(x, router, cfg):
    """Softmax over ALL ``cfg.n_experts`` in float32, the ``top_k`` largest
    chosen, their probabilities the gates — divided by their sum where
    ``cfg.norm_topk_prob`` — times ``routed_scaling_factor``: ``(idx (T, k)
    int32, gates (T, k) f32)``.  No bias, no groups.  The gates
    differentiate into the router and ``x``; the choice does not."""
    probs = jax.nn.softmax(jnp.matmul(
        x.astype(jnp.float32), router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST), axis=-1)
    gates, idx = jax.lax.top_k(probs, cfg.top_k)
    if cfg.norm_topk_prob:
        gates = gates / (gates.sum(-1, keepdims=True) + 1e-20)
    return idx.astype(jnp.int32), gates * cfg.routed_scaling_factor


def _row_tile(n_assign: int) -> int:
    """Rows of one grouped-product tile: small while a tick's few rows per
    expert would mostly be padding, MXU-high for a prefill, and for a
    training step's tens of thousands of rows an expert high enough that
    the weight gradient's float32 accumulator (read and written once a
    tile) is not what its product waits for."""
    if n_assign > 65536:
        return 512
    return 32 if n_assign <= 2048 else 128


def _row_chunk(n_assign: int, tm: int) -> Optional[int]:
    """Rows of one chunk of the backward's row-side pass
    (:func:`_live_chunks`), a whole number of tiles, or None: the rows'
    buffer is walked whole.  The line is :func:`_row_tile`'s own — a
    training step's sizes, where the buffer is 139,264 rows a layer of
    which a quarter to a half are live; a tick's buffer is under one chunk
    and a prefill's a few, and neither pays for a ``while``."""
    return 16 * tm if n_assign > 65536 else None


#: the float32 sum of a layer whose rows stay in a kernel's fast memory
_RESIDENT_BYTES = 4 << 20


def _rows_resident(t: int, d: int, n_assign: int) -> bool:
    """Is the layer small enough that ``x (T, D)`` and its float32 result
    stay WHOLE in a kernel's fast memory — a served tick's few dozen rows —
    so that the grouped products take their rows and give their sum
    themselves (:func:`_resident_product`)?  The line is :func:`_row_tile`'s
    small one, with a bound on the bytes; a prefill's thousand rows and a
    training step's are over it and stage their rows in ``(M, D)``
    buffers."""
    return n_assign <= 2048 and 4 * t * d <= _RESIDENT_BYTES


def _live_chunks(per_chunk, row_args, n_live, chunk: Optional[int]):
    """``per_chunk(*row_args)`` — a tuple of arrays by row, of arrays by row
    (leading dimension ``M``) — computed over the LIVE rows only: the rows'
    buffer is sorted by expert with its live tiles a prefix of ``n_live``
    rows, so a ``fori_loop`` of ``ceil(n_live / chunk)`` trips runs
    ``per_chunk`` on one chunk of every ``row_args`` a trip and writes the
    results in place into zero-filled buffers.  Rows past the last live
    chunk hold zeros (nothing reads them: ``moe_gmm`` skips dead tiles and
    no choice names a dead row); where ``M`` is no multiple of ``chunk`` the
    last chunk overlaps the one before it.  ``chunk`` None, or a buffer of
    one chunk or less: ``per_chunk`` over the whole buffer, no loop.  The
    loop's bound is a traced scalar, so this is called from the backward
    FUNCTION of a custom VJP and never differentiated."""
    m = row_args[0].shape[0]
    if chunk is None or m <= chunk:
        return per_chunk(*row_args)
    cut = lambda start: [jax.lax.dynamic_slice_in_dim(a, start, chunk)
                         for a in row_args]
    outs = []
    for like in jax.eval_shape(lambda: per_chunk(*cut(0))):
        zeros = jnp.zeros((m,) + like.shape[1:], like.dtype)
        for ax in sorted(like.vma or ()):
            zeros = pcast_varying(zeros, ax)
        outs.append(zeros)

    def body(i, outs):
        start = jnp.minimum(i * chunk, m - chunk)
        return tuple(jax.lax.dynamic_update_slice_in_dim(out, got, start, 0)
                     for out, got in zip(outs, per_chunk(*cut(start))))

    return jax.lax.fori_loop(0, -(-n_live // chunk), body, tuple(outs))


@partial(jax.custom_vjp, nondiff_argnums=(4,))
def _gather_rows(x, row_token, dest, is_held, mode):
    """``x[row_token]``: the routed rows in their experts' groups, ALL
    ``M`` of them in one gather (a dead row reads ``x[0]``): its source is
    ``(T, D)``, small enough that the chip's compiler keeps it in fast
    memory, where a gathered row costs 7 ns — walking the live chunks
    alone cost MORE, by the copy of each chunk into the buffer and the
    buffer's zero fill (PERF.md, Findings PR 39).  ``mode``: ``jnp.take``'s (every ``row_token`` is in
    range: a training step says ``'clip'`` and saves the fill mode's
    select over all ``M`` rows; the served programs keep the default they
    were compiled with).  Its transpose is written as the gather it is — a
    token reads back the rows of its own held choices, ``dest (T, k)`` —
    and not as the scatter-add of every row (dead and padding rows among
    them) that autodiff would derive from ``jnp.take``."""
    return jnp.take(x, row_token, axis=0, mode=mode)


def _gather_rows_fwd(x, row_token, dest, is_held, mode):
    return jnp.take(x, row_token, axis=0, mode=mode), (dest, is_held)


def _gather_rows_bwd(mode, res, d_rows):
    dest, is_held = res
    return _weighted_rows(d_rows, None, dest, is_held, mode="clip").astype(
        d_rows.dtype), None, None, None


_gather_rows.defvjp(_gather_rows_fwd, _gather_rows_bwd)


def _weighted_rows(rows, gates, dest, is_held, mode=None):
    """``sum_j held[t, j] * gates[t, j] * rows[dest[t, j]]`` in float32
    (``gates`` None: 1), one choice at a time: each token reads its own
    held rows back; rows of dead tiles are never named (a non-held choice
    reads a clamped row and is masked, not multiplied).  ``mode``:
    ``jnp.take``'s (every ``dest`` is in range: the backward passes say
    ``'clip'``, whose gather keeps its scope in the compiled program; the
    forward keeps the default the served programs were compiled with)."""
    y = jnp.zeros((dest.shape[0], rows.shape[1]), jnp.float32)
    for j in range(dest.shape[1]):
        picked = jnp.take(rows, dest[:, j], axis=0, mode=mode).astype(
            jnp.float32)
        if gates is not None:
            picked = picked * gates[:, j, None]
        y = y + jnp.where(is_held[:, j, None], picked, 0.0)
    return y


@partial(jax.custom_vjp, nondiff_argnums=(6,))
def _combine(rows, gates, dest, is_held, row_token, n_live, chunk):
    """The gather-combine of the experts' result rows.  Transposed, both
    cotangents are taken on the ROW side, in one pass over the live chunks
    (:func:`_live_chunks`) that gathers each row's token's cotangent
    (``row_token``: another gather, no scatter of ``(M, D)`` rows): a
    result row's is that, times its gate; a gate's is the dot of its row
    with it — a scalar a row, which goes back to its choice with no row
    moved.  ``chunk`` None (a tick, a prefill): the same pass over the
    whole buffer."""
    return _weighted_rows(rows, gates, dest, is_held)


def _combine_fwd(rows, gates, dest, is_held, row_token, n_live, chunk):
    return (_weighted_rows(rows, gates, dest, is_held),
            (rows, gates, dest, is_held, row_token, n_live))


def _combine_bwd(chunk, res, dy):
    rows, gates, dest, is_held, row_token, n_live = res
    m, a = rows.shape[0], dest.size
    # each held choice's row (m: none) and, by it, each live row's gate and
    # choice (0 and a: a padding or dead row, which no choice names)
    row_of = jnp.where(is_held, dest, m).reshape(-1)
    row_gate = jnp.zeros((m,), jnp.float32).at[row_of].set(
        gates.reshape(-1), mode="drop")
    row_choice = jnp.full((m,), a, jnp.int32).at[row_of].set(
        jnp.arange(a, dtype=jnp.int32), mode="drop")

    def per_chunk(token, gate, row):
        dy_row = jnp.take(dy, token, axis=0, mode="clip")
        return ((dy_row * gate[:, None]).astype(row.dtype),
                (row.astype(jnp.float32) * dy_row).sum(-1))

    d_rows, row_dot = _live_chunks(per_chunk, [row_token, row_gate, rows],
                                   n_live, chunk)
    # (scattered back, not gathered by ``dest``: an index costs the chip
    # 4 ns scattered and 16 gathered, PERF.md, Findings PR 39)
    d_gates = jnp.zeros((a,), gates.dtype).at[row_choice].set(
        row_dot.astype(gates.dtype), mode="drop").reshape(gates.shape)
    return d_rows, d_gates, None, None, None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


class _Route(NamedTuple):
    """The index work of ``block/moe/dispatch``: where each held choice's
    row lies in the rows' buffer (``M`` rows in tile-aligned groups by
    expert, the live tiles a prefix)."""
    dest: jax.Array         # (T, k) the choice's row (a clamped one: not held)
    is_held: jax.Array      # (T, k) bool
    row_token: jax.Array    # (M,) the row's token
    tile_expert: jax.Array  # (M // tm,) the tile's held expert
    n_live: jax.Array       # rows of the live tiles
    n_valid: jax.Array      # live tiles


def _staged_product(x, w_gate, w_up, w_down, gates, route: _Route, tm: int,
                    chunk: Optional[int], interpret: bool):
    """The held experts' gated sum through ``(M, D)`` buffers: the rows
    gathered whole (a padding row reads token 0), three grouped products
    over the live tiles — the first two and their activation ONE kernel,
    forward and transposed (``moe_gmm_glu``) — a gather-combine (no
    scatter)."""
    from ..ops.moe_gmm import moe_gmm, moe_gmm_glu

    xs = _gather_rows(x, route.row_token, route.dest, route.is_held,
                      None if chunk is None else "clip")        # (M, D)
    tiles = dict(tm=tm, interpret=interpret)
    hidden = moe_gmm_glu(xs, w_gate, w_up, route.tile_expert, route.n_valid,
                         **tiles)                               # (M, F)
    rows = moe_gmm(hidden, w_down, route.tile_expert, route.n_valid,
                   **tiles)                                     # (M, D)
    return _combine(rows, gates, route.dest, route.is_held, route.row_token,
                    route.n_live, chunk)


@partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def _resident_product(x, w_gate, w_up, w_down, gates, row_gate,
                      route: _Route, tm: int, interpret: bool):
    """:func:`_staged_product`'s sum where :func:`_rows_resident`: two
    kernels and nothing else (``ops/moe_gmm.py``) — ``moe_gmm_rows`` takes a
    live tile's rows out of the resident ``x`` and writes ``hidden``,
    ``moe_gmm_sum`` adds each result row, times its gate, into its token's
    row of the resident float32 ``y``.  ``row_gate (M,)`` float32 is
    ``gates`` by row (``gates`` itself is here for the backward);
    ``route.row_token`` names NO token (``T``) in a padding row.  The same
    roundings; a token's held rows are added by expert, not by choice.
    Differentiated through the staged path."""
    from ..ops.moe_gmm import moe_gmm_rows, moe_gmm_sum

    hidden = moe_gmm_rows(x, w_gate, w_up, route.row_token,
                          route.tile_expert, route.n_valid, tm=tm,
                          interpret=interpret)
    return moe_gmm_sum(hidden, w_down, row_gate, route.row_token,
                       route.tile_expert, route.n_valid,
                       n_tokens=x.shape[0], tm=tm, interpret=interpret)


def _resident_product_fwd(x, w_gate, w_up, w_down, gates, row_gate, route,
                          tm, interpret):
    return (_resident_product(x, w_gate, w_up, w_down, gates, row_gate,
                              route, tm, interpret),
            (x, w_gate, w_up, w_down, gates, route))


def _resident_product_bwd(tm, interpret, res, dy):
    *diff, route = res
    # (the staged path gathers every row: a padding row reads token 0)
    route = route._replace(
        row_token=jnp.minimum(route.row_token, diff[0].shape[0] - 1))
    staged = lambda *diff: _staged_product(*diff, route, tm, None, interpret)
    return jax.vjp(staged, *diff)[1](dy) + (None, None)


_resident_product.defvjp(_resident_product_fwd, _resident_product_bwd)


def _held_experts_product(x, p, idx, gates, first, n_held: int,
                          use_kernel: bool, interpret: bool):
    """``Σ_{chosen ∧ held} gate · E(x)`` over the held experts ``[first,
    first + n_held)`` and the per-held-expert token counts.  Kernel path:
    assignments sorted by expert into tile-aligned groups whose live tiles
    are a prefix of the ``M`` rows, and ONE algorithm — grouped products
    over that prefix — that stages its rows by the size it sees (static:
    :func:`_rows_resident`).  A served tick's few rows stay in the kernels'
    fast memory: two kernels take the rows and give the sum themselves
    (:func:`_resident_product`).  A prefill's and a training step's go
    through ``(M, D)`` buffers (:func:`_staged_product`): a gather, three
    grouped products in two kernels, a gather-combine (no scatter).  The
    backward is the staged path's at every size: its row-side pass (each
    row's token's cotangent gathered, scaled for the products and dotted
    with the row for the gates) follows the same work list — at a training
    step's sizes (the static ``n_assign = T·k``: :func:`_row_chunk`) it
    walks the live chunks and leaves the dead rows zero, at a tick's and a
    prefill's it would walk the buffer whole; the staged rows are gathered
    whole (:func:`_gather_rows` says why).  Fallback: a dense loop over the
    held experts (tiny CPU sizes)."""
    t, d = x.shape
    k = idx.shape[1]
    # the index work between the routing and the product: which choices
    # are held here, each one's place in its expert's tile-aligned group
    with jax.named_scope("block/moe/dispatch"):
        local = idx - first
        is_held = (local >= 0) & (local < n_held)
        onehot = (jnp.where(is_held, local, n_held).reshape(-1, 1)
                  == jnp.arange(n_held)[None, :])              # (T·k, E_h)
        counts = onehot.sum(0).astype(jnp.int32)
    if not use_kernel:
        from .blocks import swiglu
        with jax.named_scope("block/moe/gmm"):
            y = jnp.zeros((t, d), jnp.float32)
            for e in range(n_held):
                g_e = jnp.where(local == e, gates, 0.0).sum(-1)  # (T,)
                y_e = swiglu(x, {n: p[n][e] for n in
                                 ("w_gate", "w_up", "w_down")})
                y = y + y_e.astype(jnp.float32) * g_e[:, None]
        return y, counts

    a = t * k
    tm = _row_tile(a)
    m_pad = -(-(a + n_held * (tm - 1)) // tm) * tm
    resident = _rows_resident(t, d, a)
    with jax.named_scope("block/moe/dispatch"):
        padded = -(-counts // tm) * tm
        ends = jnp.cumsum(padded)
        starts = ends - padded
        rank = ((jnp.cumsum(onehot, axis=0) - 1) * onehot).sum(-1)  # (T·k,)
        flat_held = is_held.reshape(-1)
        dest = jnp.where(flat_held,
                         starts[jnp.clip(local.reshape(-1), 0, n_held - 1)]
                         + rank, m_pad).astype(jnp.int32)
        # a padding row's token: none (``t``) where the kernels take the
        # rows themselves, the first where they are gathered whole
        row_token = (jnp.full((m_pad,), t, jnp.int32) if resident
                     else jnp.zeros((m_pad,), jnp.int32)).at[dest].set(
            jnp.repeat(jnp.arange(t, dtype=jnp.int32), k), mode="drop")
        tile_expert = jnp.minimum(jnp.searchsorted(
            ends, jnp.arange(m_pad // tm, dtype=jnp.int32) * tm,
            side="right"), n_held - 1)
        n_live = ends[-1]
        n_valid = n_live // tm
        if resident:    # each row's gate (0: a row that no choice names)
            row_gate = jnp.zeros((m_pad,), jnp.float32).at[dest].set(
                jax.lax.stop_gradient(gates).reshape(-1), mode="drop")
    weights = (p["w_gate"], p["w_up"], p["w_down"])
    with jax.named_scope("block/moe/gmm"):
        route = _Route(jnp.minimum(dest, m_pad - 1).reshape(t, k), is_held,
                       row_token, tile_expert, n_live, n_valid)
        if resident:
            y = _resident_product(x, *weights, gates, row_gate, route, tm,
                                  interpret)
        else:
            y = _staged_product(x, *weights, gates, route, tm,
                                _row_chunk(a, tm), interpret)
    return y, counts


def moe_dropless(x, params, cfg, *, live=None,
                 interpret: Optional[bool] = None):
    """Dropless expert FFN of ``x (T, D)``: ``(y, counts, idx)`` — ``y =
    E_shared(x) + Σ_{chosen ∧ held here} gate · E_i(x)`` (no ``E_shared``
    where ``cfg.n_shared`` is 0), the int32
    routing-count vector (:data:`COUNT_FIELDS`, then the tokens of each
    held expert) and the chosen experts ``idx (T, top_k)``.

    ``cfg`` is a ``blocks.MoEConfig``; ``cfg.held = (first, n)`` names the
    routed experts this chip holds — ``params['w_gate'|'w_up'|'w_down']``
    stack exactly those.  The router scores ALL ``cfg.n_experts`` and the
    gates are normalised over all chosen, held here or not: with ``n <
    n_experts`` the result is this chip's PART of the layer (what expert
    parallelism asks of a chip anyway).

    ``live (T,) bool`` names the rows that carry a token (None: all).  A
    row that carries none — a free slot of the serving tick, a prefill's
    padding — is routed to NO expert: it reads no expert's weights, its
    routed part is zero, it is in no count, and its ``idx`` is
    ``cfg.n_experts`` (no expert's number).

    The grouped product is the kernel ``moe_gmm`` on a TPU and a dense
    loop over the held experts elsewhere; ``interpret=True`` runs the
    kernel path in interpret mode (tests).

    Differentiable in ``x`` and every parameter: through the gates (into
    the router), the rows' gather, the three grouped products
    (``moe_gmm_glu``'s and ``moe_gmm``'s own VJPs) and the gather-combine —
    a tick's resident forward (``moe_gmm_rows`` + ``moe_gmm_sum``) through
    that same staged path; the choice of experts and the counts carry no
    gradient.
    """
    from .blocks import swiglu

    use_kernel = interpret is not None or jax.default_backend() == "tpu"
    first, n_held = cfg.held
    with jax.named_scope("block/moe/route"):
        if cfg.router == "softmax":
            idx, gates = softmax_topk_route(x, params["router"], cfg)
        elif cfg.router == "sigmoid_group":
            idx, gates = sigmoid_group_route(x, params["router"],
                                             params["router_bias"], cfg)
        else:
            raise ValueError(f"MoEConfig.router {cfg.router!r}: "
                             "'sigmoid_group' or 'softmax'")
        if live is not None:
            idx = jnp.where(live[:, None], idx, jnp.int32(cfg.n_experts))
    y, per_expert = _held_experts_product(
        x, params, idx, gates, first, n_held, use_kernel, bool(interpret))
    if cfg.n_shared:
        with jax.named_scope("block/moe/shared"):
            y = y + swiglu(x, params["shared"]).astype(jnp.float32)
    with jax.named_scope("block/moe/route"):    # the routing's counts
        n_rows = jnp.int32(x.shape[0]) if live is None else live.sum()
        counts = jnp.concatenate([
            jnp.stack([(n_rows * idx.shape[1]).astype(jnp.int32),
                       per_expert.sum(),
                       (per_expert > 0).sum().astype(jnp.int32)]),
            per_expert])
    # (the cast of the layer's sum: the shared expert's scope where there
    # is one, as ever; else the product's)
    with jax.named_scope("block/moe/shared" if cfg.n_shared
                         else "block/moe/gmm"):
        return y.astype(x.dtype), counts, idx


def book_routing_counts(counts, steps: int = 1) -> None:
    """Book routing-count vectors that are ALREADY ON THE HOST (a numpy
    array or a list of ints, :data:`COUNT_FIELDS` then one entry a held
    expert, summed over ``steps`` train steps and over the expert layers)
    with the process tracer, as counters ``train/moe_steps``,
    ``train/moe_assignments_total``, ``train/moe_assignments_held`` and
    ``train/moe_expert_tokens/<i>``.  Nothing here touches the
    device: the caller reads the step's aux back where it reads its loss
    back anyway, or hands in a step whose result is known to be ready.  Off
    (one attribute read) while the tracer is disabled."""
    from ..observability import trace as _trace

    tr = _trace.get_tracer()
    if not tr.enabled:
        return
    n = len(COUNT_FIELDS)
    tr.add_counter("train/moe_steps", float(steps))
    for name, value in zip(COUNT_FIELDS[:2], counts[:2]):
        tr.add_counter(f"train/moe_{name}", float(value))
    for i, value in enumerate(counts[n:]):
        tr.add_counter(f"train/moe_expert_tokens/{i}", float(value))
