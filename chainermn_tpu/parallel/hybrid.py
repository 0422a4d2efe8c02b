"""Hybrid data x model parallelism: one jitted step over an N-D mesh.

Reference parity: SURVEY.md §2.8 "Hybrid DP×MP" — the reference composed
2-D layouts by hand from ``CommunicatorBase.split(color, key)``
sub-communicators (``communicator_base.py :: split`` [uv]) and the
``examples/model_parallel`` graphs [uv]: a data-parallel allreduce among
same-position ranks x an activation pipeline among same-replica ranks.

TPU-native there are two faces, both over one :func:`topology.make_nd_mesh`
``('data', 'model')`` mesh:

* **pjit face** (:func:`make_hybrid_train_step`) — the idiomatic one.
  Params are placed with per-leaf ``NamedSharding`` (model-dim sharded,
  data-replicated; see :func:`shard_pytree`), the batch is sharded over
  ``'data'``, and the step is a *plain* ``jax.jit``: XLA's sharding
  propagation (GSPMD) inserts the TP psums/all-gathers AND the DP gradient
  reduce-scatter from the shardings alone — the scaling-book recipe ("pick
  a mesh, annotate shardings, let XLA insert collectives").
* **shard_map face** (:func:`make_hybrid_shard_map_step`) — the explicit
  one, for models written against ``parallel.tensor_parallel``'s per-rank
  layers: both axes are bound, TP layers psum over ``'model'`` themselves,
  and the loss is pmean'd over ``'data'`` so autodiff inserts the DP
  gradient reduction exactly like the 1-D :func:`train.make_train_step`.
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .._compat import shard_map


def _data_axis(mesh: Mesh, axis_name: Optional[str]) -> str:
    """Resolve the DP axis: explicit name, or the mesh's sole axis.

    ``make_mesh()`` names its 1-D axis ``'mn'`` while the hybrid builders
    historically defaulted to ``'data'`` — resolving against the mesh kills
    that trap: a 1-D mesh needs no axis argument at all, an N-D mesh demands
    an explicit one.
    """
    if axis_name is not None:
        if axis_name not in mesh.axis_names:
            raise ValueError(
                f"axis {axis_name!r} not in mesh axes {mesh.axis_names}")
        return axis_name
    if len(mesh.axis_names) == 1:
        return mesh.axis_names[0]
    raise ValueError(
        f"mesh has axes {mesh.axis_names}; pass axis_name= explicitly")


def shard_pytree(tree, mesh: Mesh, specs):
    """Place ``tree`` on ``mesh`` with a matching pytree of PartitionSpecs.

    ``specs`` may be a single spec (applied to every leaf) or a pytree
    matching ``tree``'s structure.
    """
    if isinstance(specs, P):
        specs = jax.tree_util.tree_map(lambda _: specs, tree)
    return jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), tree, specs)


def make_hybrid_train_step(
    loss_fn: Callable,
    optimizer: optax.GradientTransformation,
    has_aux: bool = False,
    donate: bool = True,
):
    """Hybrid-parallel train step, pjit face.

    ``loss_fn(params, batch)`` is written over the GLOBAL logical batch
    (plain jnp ops; sprinkle ``jax.lax.with_sharding_constraint`` on
    activations to pin layouts).  Parallelism comes entirely from the
    shardings the caller placed on ``params`` (via :func:`shard_pytree`)
    and ``batch`` — XLA derives the TP collectives and the DP gradient
    reduction, so the same step runs 1-D DP, 1-D TP, or 2-D DP×TP
    depending only on how the arrays are laid out.

    ``opt_state`` should be created with ``jax.jit(optimizer.init)(params)``
    so its shardings are inferred to follow the params.
    """

    def step(params, opt_state, batch):
        def global_loss(p):
            out = loss_fn(p, batch)
            if has_aux:
                return out
            return out, None

        (loss, aux), grads = jax.value_and_grad(global_loss, has_aux=True)(params)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        if has_aux:
            return params, opt_state, loss, aux
        return params, opt_state, loss

    return jax.jit(step, donate_argnums=(0, 1) if donate else ())


def state_specs_like(optimizer: optax.GradientTransformation, params,
                     param_specs):
    """PartitionSpecs for ``optimizer.init(params)``'s state pytree.

    Optax states nest sub-pytrees structurally identical to ``params``
    (momentum/trace, Adam's mu/nu); each such subtree inherits
    ``param_specs`` wholesale, every other leaf (step counts, scalars) is
    replicated.  This is what lets the shard_map face wrap arbitrary optax
    optimizers without per-optimizer spec plumbing.
    """
    state = jax.eval_shape(optimizer.init, params)
    pdef = jax.tree_util.tree_structure(params)

    def params_like(node):
        try:
            return jax.tree_util.tree_structure(node) == pdef
        except Exception:
            return False

    return jax.tree_util.tree_map(
        lambda sub: (param_specs if params_like(sub)
                     else jax.tree_util.tree_map(lambda _: P(), sub)),
        state, is_leaf=params_like)


def zero1_specs(params, mesh: Mesh, axis_name: Optional[str] = None):
    """ZeRO-1 PartitionSpecs: each param-shaped leaf sharded over
    ``axis_name`` on its first divisible dimension, scalars/indivisible
    leaves replicated.

    Beyond-reference (the reference replicated optimizer state on every
    rank): with ``P`` data-parallel chips, Adam's m/v live ``1/P`` per chip.

    .. note:: breaking default change (round 2): ``axis_name`` defaults to
       ``None`` — resolved to the mesh's only axis, raising on multi-axis
       meshes instead of silently assuming ``'data'``.  Callers on N-D
       meshes must name the axis explicitly.
    """
    axis_name = _data_axis(mesh, axis_name)
    n = mesh.shape[axis_name]

    def spec_for(leaf):
        shape = getattr(leaf, "shape", ())
        for d, s in enumerate(shape):
            if s % n == 0 and s >= n:
                return P(*([None] * d + [axis_name]))
        return P()

    return jax.tree_util.tree_map(spec_for, params)


def init_zero1_state(optimizer: optax.GradientTransformation, params,
                     mesh: Mesh, axis_name: Optional[str] = None):
    """Optimizer state laid out ZeRO-1: param-shaped subtrees sharded per
    :func:`zero1_specs`, everything else replicated."""
    pspecs = zero1_specs(params, mesh, axis_name)
    sspecs = state_specs_like(optimizer, params, pspecs)
    shardings = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), sspecs)
    return jax.jit(optimizer.init, out_shardings=shardings)(params)


def make_zero1_train_step(
    loss_fn: Callable,
    optimizer: optax.GradientTransformation,
    mesh: Mesh,
    axis_name: Optional[str] = None,
    has_aux: bool = False,
    donate: bool = True,
):
    """ZeRO-1 data-parallel train step (pjit face).

    The gradient all-reduce becomes a REDUCE-SCATTER (each chip receives
    only its ``1/P`` gradient shard), the optimizer update runs on sharded
    state (:func:`init_zero1_state`), and the parameter delta is
    all-gathered back to replicated — reduce_scatter + update/P + all_gather
    instead of all_reduce + P× redundant update, with optimizer memory cut
    by ``P``.  All three collectives are GSPMD-inserted from the sharding
    constraints; params stay replicated at the step boundary so everything
    else (checkpointing, eval, export) is unchanged.
    """
    def step(params, opt_state, batch):
        pspecs = zero1_specs(params, mesh, axis_name)

        def global_loss(p):
            out = loss_fn(p, batch)
            if has_aux:
                return out
            return out, None

        (loss, aux), grads = jax.value_and_grad(global_loss, has_aux=True)(params)
        # Shard the grads like the state: AD's cross-batch reduction + this
        # constraint lower to one reduce_scatter per leaf.
        grads = jax.tree_util.tree_map(
            lambda g, s: jax.lax.with_sharding_constraint(
                g, NamedSharding(mesh, s)),
            grads, pspecs)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        # All-gather the delta, keep params replicated at the boundary.
        updates = jax.tree_util.tree_map(
            lambda u: jax.lax.with_sharding_constraint(
                u, NamedSharding(mesh, P())),
            updates)
        params = optax.apply_updates(params, updates)
        if has_aux:
            return params, opt_state, loss, aux
        return params, opt_state, loss

    return jax.jit(step, donate_argnums=(0, 1) if donate else ())


def init_fsdp_params(params, mesh: Mesh, axis_name: Optional[str] = None):
    """Place ``params`` FSDP-style: each leaf sharded over ``axis_name`` on
    its first divisible dimension (:func:`zero1_specs` layout), so parameter
    memory per chip is ``1/P`` of the model.  Returns the sharded pytree."""
    return shard_pytree(params, mesh, zero1_specs(params, mesh, axis_name))


def init_fsdp_state(optimizer: optax.GradientTransformation, params,
                    mesh: Mesh, axis_name: Optional[str] = None):
    """Optimizer state matching :func:`init_fsdp_params`'s layout: the
    param-shaped subtrees (momentum, Adam m/v) shard exactly like the
    params, scalars replicated."""
    return init_zero1_state(optimizer, params, mesh, axis_name)


def make_fsdp_train_step(
    loss_fn: Callable,
    optimizer: optax.GradientTransformation,
    mesh: Mesh,
    axis_name: Optional[str] = None,
    has_aux: bool = False,
    donate: bool = True,
):
    """FSDP / ZeRO-3 data-parallel train step (pjit face).

    Beyond-reference (SURVEY.md §2.8 lists only replicated-parameter DP):
    parameters, gradients AND optimizer state all live sharded ``1/P`` per
    chip over ``axis_name`` (:func:`zero1_specs` layout) — the full ZeRO-3
    memory split, the TPU-idiomatic way:

    * forward/backward: ``loss_fn`` is written over global logical arrays;
      GSPMD sees sharded params meeting a ``'data'``-sharded batch and
      inserts the per-use **all-gather** of each weight (and, in the
      backward, the matching **reduce-scatter** of its gradient) — the
      hand-written bucketing/prefetch machinery of GPU FSDP is the
      compiler's job here.
    * the gradient constraint to the param layout makes the cross-replica
      reduction a reduce-scatter (never a full all-reduce), and the update
      runs on ``1/P`` of the state per chip.
    * params stay sharded at the step boundary — peak HBM is
      O(model/P + largest gathered layer), which is what lets a model
      ``P×`` bigger than one chip train at all.

    Wrap big ``loss_fn`` blocks in ``jax.checkpoint`` with a
    ``save_only_these_names``/dots policy to avoid re-gathering weights in
    the backward if XLA's rematerialisation choices need steering.
    """
    def step(params, opt_state, batch):
        pspecs = zero1_specs(params, mesh, axis_name)

        def global_loss(p):
            out = loss_fn(p, batch)
            if has_aux:
                return out
            return out, None

        (loss, aux), grads = jax.value_and_grad(global_loss, has_aux=True)(params)
        # Reduce-scatter: grads land in the same 1/P layout as the state.
        grads = jax.tree_util.tree_map(
            lambda g, s: jax.lax.with_sharding_constraint(
                g, NamedSharding(mesh, s)),
            grads, pspecs)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        # Keep params sharded at the boundary (the ZeRO-3 point — contrast
        # make_zero1_train_step, which all-gathers them back to replicated).
        params = jax.tree_util.tree_map(
            lambda x, s: jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, s)),
            params, pspecs)
        if has_aux:
            return params, opt_state, loss, aux
        return params, opt_state, loss

    return jax.jit(step, donate_argnums=(0, 1) if donate else ())


def make_hybrid_shard_map_step(
    loss_fn: Callable,
    optimizer: optax.GradientTransformation,
    mesh: Mesh,
    params,
    param_specs,
    data_axis: str = "data",
    batch_spec: Optional[P] = None,
    has_aux: bool = False,
    donate: bool = True,
    aux_specs=None,
):
    """Hybrid-parallel train step, explicit shard_map face.

    ``loss_fn(params, local_batch)`` runs with BOTH mesh axes bound: TP
    layers (``parallel.tensor_parallel``) psum over the model axis
    themselves; this builder pmeans the loss over ``data_axis`` so autodiff
    inserts the cross-replica gradient reduction (and ONLY that — params
    varying over the model axis get no spurious model-axis psum).

    ``has_aux``: ``loss_fn`` returns ``(loss, aux)`` and the step hands out
    ``aux`` reduced over ``data_axis`` — integer leaves (counts: an expert
    layer's routing counts) SUMMED, every other leaf averaged.  ``aux_specs``
    (a ``PartitionSpec`` for each leaf of ``aux``; None: ``P()`` for all)
    names the leaves that are NOT reduced: a leaf whose spec names
    ``data_axis`` is a value a sample (an expert layer's chosen experts) and
    comes out as it is, sharded as its spec says.

    ``params``/``param_specs``: the TP layout (e.g. ``wi`` sharded on its
    output dim over ``'model'``); used to derive optimizer-state specs via
    :func:`state_specs_like`.  ``batch_spec`` defaults to sharding the
    leading axis over ``data_axis``.
    """
    if batch_spec is None:
        batch_spec = P(data_axis)
    st_specs = state_specs_like(optimizer, params, param_specs)

    def train_step(params, opt_state, batch):
        def global_loss(p):
            out = loss_fn(p, batch)
            if has_aux:
                local, aux = out
            else:
                local, aux = out, None
            return jax.lax.pmean(local, data_axis), aux

        with jax.named_scope("loss_grad"):
            (loss, aux), grads = jax.value_and_grad(
                global_loss, has_aux=True)(params)
        with jax.named_scope("optimizer"):
            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
        if has_aux:
            # a count (an integer leaf) is SUMMED over the replicas, every
            # other leaf averaged as the loss is; a leaf a sample stays
            def reduced(a, spec=P()):
                if data_axis in jax.tree_util.tree_leaves(tuple(spec)):
                    return a
                return (jax.lax.psum if jnp.issubdtype(a.dtype, jnp.integer)
                        else jax.lax.pmean)(a, data_axis)

            with jax.named_scope("loss_grad"):
                aux = (jax.tree_util.tree_map(reduced, aux)
                       if aux_specs is None else
                       jax.tree_util.tree_map(reduced, aux, aux_specs))
            return params, opt_state, loss, aux
        return params, opt_state, loss

    out_specs = ((param_specs, st_specs, P(),
                  P() if aux_specs is None else aux_specs) if has_aux
                 else (param_specs, st_specs, P()))
    # the jitted program is named after the function: ``jit_train_step``
    # on the profiler's "XLA Modules" line
    smapped = shard_map(
        train_step, mesh=mesh,
        in_specs=(param_specs, st_specs, batch_spec),
        out_specs=out_specs,
    )
    return jax.jit(smapped, donate_argnums=(0, 1) if donate else ())
