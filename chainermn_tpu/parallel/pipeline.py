"""Pipeline parallelism with a real microbatch schedule (GPipe-style).

Reference relationship: the reference's only inter-layer parallelism is
``MultiNodeChainList`` (``chainermn/links/multi_node_chain_list.py`` [uv]) —
strictly sequential, one rank active at a time, "no microbatching, no 1F1B
schedule" (SURVEY.md §2.3, §2.8 "PP: absent").  Our
``links/multi_node_chain_list.py`` keeps that parity surface; THIS module is
the scheduler the reference never had, built the TPU way:

* stages live on devices along a named mesh axis — stage ``i``'s weights are
  the ``i``-th slice of a stage-stacked pytree (sharded by ``shard_map``);
* the schedule is a ``lax.scan`` over ``M + P - 1`` ticks.  Every tick, all
  ``P`` devices run the SAME stage function on their in-flight microbatch
  (SPMD — XLA sees one program, no data-dependent control flow) and a single
  ``ppermute`` hands activations to the next stage over the ICI ring;
* backward needs no hand-written schedule: ``lax.scan`` reverses the ticks
  and the transpose of ``ppermute(+1)`` is ``ppermute(-1)``, so autodiff
  yields the reverse pipeline automatically — the property the reference
  hand-built with Send/Recv FunctionNodes (SURVEY.md §3.5).

Bubble fraction is ``(P-1)/(M+P-1)`` (GPipe): pick ``num_microbatches >> P``.
Memory is O(M) stashed activations; wrap ``stage_fn`` in ``jax.checkpoint``
to trade FLOPs for HBM (rematerialised backward).

Constraints (the homogeneous-pipeline contract, same as e.g. praxis):
``stage_fn(stage_params, x) -> y`` with ``y.shape == x.shape`` and
``y.dtype == x.dtype`` (the activation rides the ring through every stage),
and ``num_microbatches`` divides the global batch.
"""

from __future__ import annotations

from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from .._compat import pcast_varying


def pipeline_apply(stage_fn: Callable, stage_params, x, *, axis_name: str,
                   num_microbatches: int, squeeze_stage_axis: bool = True,
                   remat: bool = False):
    """Run ``x`` through ``P`` pipeline stages with GPipe microbatching.

    Call INSIDE ``shard_map``.  ``stage_params``: this device's stage slice.
    With ``squeeze_stage_axis=True`` (the default, matching an ``in_spec``
    of ``P(axis_name)`` over stage-stacked params) every leaf must carry a
    leading stage axis of length 1, which is stripped before ``stage_fn``
    sees it; pass ``False`` when handing in an already-squeezed pytree.
    ``x``: the full local batch ``(B, ...)``, replicated across the axis.
    Returns ``stage_P-1 ∘ ... ∘ stage_0`` applied to every microbatch, i.e.
    the same value on every device (merged with one psum at the end).
    """
    p_size = jax.lax.axis_size(axis_name)
    stage = jax.lax.axis_index(axis_name)
    if remat:
        # Rematerialized backward: the scan stashes only the tick carries,
        # stage activations are recomputed — O(M) ride-along activations
        # become O(1) per stage, the HBM/FLOP trade SURVEY's §2.8 PP note
        # and the module docstring advertise.
        stage_fn = jax.checkpoint(stage_fn)
    m = num_microbatches
    if x.shape[0] % m != 0:
        raise ValueError(
            f"batch {x.shape[0]} not divisible by num_microbatches {m}")

    if squeeze_stage_axis:
        bad = [a.shape for a in jax.tree_util.tree_leaves(stage_params)
               if a.ndim == 0 or a.shape[0] != 1]
        if bad:
            raise ValueError(
                f"stage_params leaves must carry a leading stage axis of "
                f"length 1 per device (got shapes {bad}); the stacked stage "
                f"count must equal the '{axis_name}' mesh axis size "
                f"({p_size}), or pass squeeze_stage_axis=False for "
                f"already-squeezed params")
        stage_params = jax.tree_util.tree_map(lambda a: a[0], stage_params)

    mb = x.reshape((m, x.shape[0] // m) + x.shape[1:])
    # Pad the injection stream with P-1 zero microbatches so one scan body
    # covers fill, steady state and drain without data-dependent branches.
    pad = jnp.zeros((p_size - 1,) + mb.shape[1:], mb.dtype)
    inject = jnp.concatenate([mb, pad], axis=0)

    def tick(carry, inp):
        state, out_buf, t = carry
        # Stage 0 picks up the next microbatch; everyone else keeps the
        # activation ppermute delivered last tick.
        state = jnp.where(stage == 0, inp, state)
        y = stage_fn(stage_params, state)
        # The last stage emits microbatch t-(P-1) once the pipe is full;
        # masked writes of zeros during fill are overwritten later.
        emit = (stage == p_size - 1) & (t >= p_size - 1)
        slot = jnp.maximum(t - (p_size - 1), 0)
        out_buf = jax.lax.dynamic_update_index_in_dim(
            out_buf, jnp.where(emit, y, jnp.zeros_like(y)), slot, axis=0)
        # Hand the activation to the next stage over the ICI ring.
        perm = [(i, (i + 1) % p_size) for i in range(p_size)]
        state = jax.lax.ppermute(y, axis_name, perm=perm)
        return (state, out_buf, t + 1), None

    # The carry becomes device-varying inside the loop (ppermute /
    # stage-dependent writes), so the initial carry must carry that type too.
    def varying_zeros(shape, dtype):
        z = jnp.zeros(shape, dtype)
        return pcast_varying(z, axis_name)

    state0 = varying_zeros(mb.shape[1:], mb.dtype)
    out0 = varying_zeros(mb.shape, mb.dtype)
    (_, out_buf, _), _ = jax.lax.scan(
        tick, (state0, out0, jnp.int32(0)), inject)

    # Only the last stage holds real outputs (others all-zero): one psum
    # replicates the result — the in-jit form of "bcast from the last rank".
    out = jax.lax.psum(out_buf, axis_name)
    return out.reshape(x.shape)


def pipeline_1f1b_grads(stage_fn: Callable, loss_fn: Callable, stage_params,
                        x, targets, *, axis_name: str, num_microbatches: int,
                        squeeze_stage_axis: bool = True):
    """1F1B pipeline schedule: returns ``(loss, param_grads)`` directly.

    Beyond-reference AND beyond :func:`pipeline_apply` (GPipe): the backward
    is part of the schedule, not a scan reversal.  Every tick each stage
    runs ONE forward microbatch and ONE backward microbatch (lockstep 1F1B):

    * forward: stage ``s`` processes microbatch ``f = t - s``; activations
      ride the ``+1`` ICI ring exactly as in GPipe;
    * backward: stage ``s`` processes microbatch ``b = t - 2(P-1) + s`` —
      the last stage seeds the cotangent from ``loss_fn`` the same tick its
      forward finishes, and cotangents ride the ``-1`` ring;
    * each stage keeps only a ``2P-1``-slot circular buffer of its INPUTS
      (the vjp is recomputed at backward time), so stashed-activation memory
      is **O(P), independent of num_microbatches** — GPipe's scan stashes
      O(M) even under remat.  That is what lets ``M`` grow to amortise the
      bubble (``2(P-1)/(M+2P-2)``) without HBM growing with it.

    Call INSIDE ``shard_map``.  ``stage_fn(params, x) -> y`` with
    ``y.shape == x.shape`` (the homogeneous-pipeline contract);
    ``loss_fn(y_mb, target_mb) -> scalar`` (a mean over the microbatch).
    Returns the mean loss over microbatches and gradients w.r.t. this
    device's stage params (leading stage axis of 1, matching an
    ``out_spec`` of ``P(axis_name)``).
    """
    p_size = jax.lax.axis_size(axis_name)
    stage = jax.lax.axis_index(axis_name)
    m = num_microbatches
    if x.shape[0] % m != 0:
        raise ValueError(
            f"batch {x.shape[0]} not divisible by num_microbatches {m}")

    if squeeze_stage_axis:
        bad = [a.shape for a in jax.tree_util.tree_leaves(stage_params)
               if a.ndim == 0 or a.shape[0] != 1]
        if bad:
            raise ValueError(
                f"stage_params leaves must carry a leading stage axis of "
                f"length 1 per device (got shapes {bad})")
        stage_params = jax.tree_util.tree_map(lambda a: a[0], stage_params)

    mb = x.reshape((m, x.shape[0] // m) + x.shape[1:])
    tgt = targets.reshape((m, targets.shape[0] // m) + targets.shape[1:])
    n_ticks = m + 2 * (p_size - 1)
    buf_len = 2 * p_size - 1  # proof of safety: see _1F1B buffer note below

    def varying(z):
        # Idempotent: zeros_like(sharded input) is already axis-varying and
        # pcast rejects a varying→varying cast.
        try:
            return pcast_varying(z, axis_name)
        except ValueError:
            return z

    # Circular input buffer: slot f % buf_len.  Unconditional writes are
    # safe: at stage s the entry for microbatch f is consumed 2(P-1-s)
    # ticks after its write, and the next write to the same slot (f +
    # buf_len) happens buf_len = 2P-1 > 2(P-1) ticks later; out-of-range
    # f (fill/drain) only ever lands in slots whose occupant is already
    # consumed or never valid.
    buf0 = varying(jnp.zeros((buf_len,) + mb.shape[1:], mb.dtype))
    fwd0 = varying(jnp.zeros(mb.shape[1:], mb.dtype))
    cot0 = varying(jnp.zeros(mb.shape[1:], mb.dtype))
    # Accumulate grads in fp32 regardless of param dtype: with bf16 params
    # and large M (the regime 1F1B exists for) per-microbatch contributions
    # would drown in a growing bf16 accumulator (same rationale as
    # train._accumulated_local_grads).
    g0 = jax.tree_util.tree_map(
        lambda a: varying(jnp.zeros(a.shape, jnp.float32)), stage_params)

    fwd_perm = [(i, (i + 1) % p_size) for i in range(p_size)]
    bwd_perm = [(i, (i - 1) % p_size) for i in range(p_size)]

    def tick(carry, t):
        fwd_state, cot_in, buf, grads, loss_acc = carry
        f = t - stage                      # forward microbatch index
        b = t - 2 * (p_size - 1) + stage   # backward microbatch index
        valid_f = (f >= 0) & (f < m)
        valid_b = (b >= 0) & (b < m)
        is_last = stage == p_size - 1

        # ---- forward half-tick -------------------------------------------
        inj = jax.lax.dynamic_index_in_dim(
            mb, jnp.clip(t, 0, m - 1), 0, keepdims=False)
        x_in = jnp.where(stage == 0, inj, fwd_state)
        y = stage_fn(stage_params, x_in)
        buf = jax.lax.dynamic_update_index_in_dim(
            buf, x_in, jnp.mod(f, buf_len), axis=0)

        # ---- loss + cotangent seed at the last stage ---------------------
        t_mb = jax.lax.dynamic_index_in_dim(
            tgt, jnp.clip(f, 0, m - 1), 0, keepdims=False)
        l_f, seed = jax.value_and_grad(loss_fn)(y, t_mb)
        loss_acc = loss_acc + jnp.where(is_last & valid_f, l_f, 0.0)

        # ---- backward half-tick ------------------------------------------
        # The last stage back-propagates the microbatch it JUST forwarded
        # (f == b there); everyone else uses the cotangent ppermute
        # delivered last tick, against the input stashed at forward time.
        cot = jnp.where(is_last, jnp.where(valid_f, seed, 0.0), cot_in)
        x_saved = jax.lax.dynamic_index_in_dim(
            buf, jnp.mod(b, buf_len), 0, keepdims=False)
        x_bwd = jnp.where(is_last, x_in, x_saved)
        _, vjp = jax.vjp(stage_fn, stage_params, x_bwd)
        dparams, dx = vjp(cot.astype(y.dtype))
        grads = jax.tree_util.tree_map(
            lambda g, d: g + jnp.where(valid_b, d.astype(jnp.float32), 0.0),
            grads, dparams)

        # Activations to the next stage, cotangents to the previous one.
        fwd_state = jax.lax.ppermute(y, axis_name, perm=fwd_perm)
        cot_in = jax.lax.ppermute(dx, axis_name, perm=bwd_perm)
        return (fwd_state, cot_in, buf, grads, loss_acc), None

    (_, _, _, grads, loss_acc), _ = jax.lax.scan(
        tick, (fwd0, cot0, buf0, g0, varying(jnp.float32(0.0))),
        jnp.arange(n_ticks))

    # Only the last stage accumulated loss; grads/loss are means over M.
    # Grads come back in the param dtype (fp32 accumulator cast at the end).
    loss = jax.lax.psum(loss_acc, axis_name) / m
    grads = jax.tree_util.tree_map(
        lambda g, a: (g[None] / m).astype(a.dtype), grads, stage_params)
    return loss, grads


def make_pipeline_1f1b(stage_fn: Callable, loss_fn: Callable,
                       mesh: Optional[Mesh] = None,
                       axis_name: Optional[str] = None,
                       num_microbatches: int = 8):
    """Eager/jit face of :func:`pipeline_1f1b_grads`:
    ``fn(stage_stacked_params, x, targets) -> (loss, stage_stacked_grads)``.

    Use the returned grads with any optax optimizer (state stacked like the
    params); compose with DP by running this inside an outer data axis and
    pmean-ing the grads.
    """
    from ._factory import make_global_apply, resolve_mesh_axis

    mesh, ax = resolve_mesh_axis(mesh, axis_name)
    n_stages = mesh.shape[ax]
    inner = make_global_apply(
        partial(pipeline_1f1b_grads, stage_fn, loss_fn, axis_name=ax,
                num_microbatches=num_microbatches),
        mesh, (P(ax), P(), P()), (P(), P(ax)))

    def apply(stage_stacked_params, x, targets):
        for leaf in jax.tree_util.tree_leaves(stage_stacked_params):
            if leaf.ndim == 0 or leaf.shape[0] != n_stages:
                raise ValueError(
                    f"stage-stacked leaf has leading dim "
                    f"{leaf.shape[0] if leaf.ndim else None}, but the "
                    f"'{ax}' mesh axis has {n_stages} stages")
        return inner(stage_stacked_params, x, targets)

    return apply


def stack_stage_params(per_stage_params) -> object:
    """Stack a list of per-stage pytrees (one per stage, same structure)
    into the stage-stacked pytree ``make_pipeline`` shards: every leaf gains
    a leading axis of length ``P``."""
    return jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves), *per_stage_params)


def make_pipeline(stage_fn: Callable, mesh: Optional[Mesh] = None,
                  axis_name: Optional[str] = None,
                  num_microbatches: int = 8, remat: bool = False):
    """Eager/jit face: ``fn(stage_stacked_params, x) -> y`` over globals.

    ``stage_stacked_params``: pytree whose leaves have leading dim ``P``
    (see :func:`stack_stage_params`); it is sharded one-stage-per-device
    along the mesh axis, ``x`` replicated; compiles once per shape.
    Differentiable: param grads come back stage-stacked.
    """
    from ._factory import make_global_apply, resolve_mesh_axis

    mesh, ax = resolve_mesh_axis(mesh, axis_name)
    n_stages = mesh.shape[ax]
    inner = make_global_apply(
        partial(pipeline_apply, stage_fn, axis_name=ax,
                num_microbatches=num_microbatches, remat=remat),
        mesh, (P(ax), P()), P())

    def apply(stage_stacked_params, x):
        for leaf in jax.tree_util.tree_leaves(stage_stacked_params):
            if leaf.ndim == 0 or leaf.shape[0] != n_stages:
                raise ValueError(
                    f"stage-stacked leaf has leading dim "
                    f"{leaf.shape[0] if leaf.ndim else None}, but the "
                    f"'{ax}' mesh axis has {n_stages} stages")
        return inner(stage_stacked_params, x)

    return apply
