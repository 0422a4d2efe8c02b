"""One token of a Mamba-1 selective state-space layer on a per-slot state,
in place.

A selective-state layer keeps, for each sequence, one ``(N, E)`` float32
state ``s`` (``E`` inner channels, ``N`` states a channel) instead of a row
a token.  A decode tick moves every BUSY slot's state one token on::

    s_new = exp(dt * A) * s + (dt * c) B^T      dt, c: (E,)   B, C: (N,)
    y     = C . s_new + D * c                   A: (N, E) < 0

and has to leave every other slot's state as it is, bit for bit
(``ops/kda_step.py``'s contract, for its reasons: a free slot's state is
the next occupant's start, a cached slot's is what a prefix hit copies).  So
the busy slots are compacted to the front of the grid by a scalar-prefetched
index vector (the tick's own busy list, ``ops/kv_cache.py::busy_slots``),
the steps past the last busy slot map to the block the last busy step
already holds, and the state operand is aliased to the state result: the
blocks no step maps to are neither fetched nor written.

The layout makes every operation of a step elementwise.  The state is
stored ``(N, E / L, L)`` with ``L`` the lane width (:func:`lanes`): state
index ``n`` of every channel is one ``(E / L, L)`` slab of whole vector
registers, the token's ``c`` and ``dt`` are slabs of the same shape, and
``B[n]`` and ``C[n]`` are SCALARS read from scalar memory — no broadcast
along lanes, no reduction across sublanes.  Float32 throughout: the state
is the layer's memory of the whole sequence.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .._compat import shape_dtype_struct as _sds
from .kv_cache import BusySlots, _inherit_vma, busy_slots

__all__ = ["lanes", "ssm_step", "ssm_step_xla"]


def lanes(d_inner: int) -> int:
    """The lane width ``L`` of a state ``(N, d_inner / L, L)``: whole
    128-lane registers where the width allows, else the width itself (tiny
    test models)."""
    return 128 if d_inner % 128 == 0 else d_inner


def slabs(v, rows: int, lane: int):
    """``v (..., E)`` as float32 ``(..., rows, lane)`` slabs of channels."""
    return v.astype(jnp.float32).reshape(v.shape[:-1] + (rows, lane))


def scalars(b, cc):
    """``B`` and ``C`` ``(..., N)`` as the flat float32 vector the kernels
    read from scalar memory: ``[B | C]`` a row."""
    return jnp.concatenate([b, cc], -1).astype(jnp.float32).reshape(-1)


def ssm_step_xla(c, dt, b, cc, a, d, state, busy):
    """The same step in plain ``jax.numpy`` (other backends, and the
    kernel's oracle): ``c, dt (n, E)``, ``b, cc (n, N)``, ``a (N, E)`` (the
    NEGATIVE rates, ``-exp(A_log)``), ``d (E,)``, ``state (n, N, E / L, L)``
    float32, ``busy (n,) bool``.  Returns ``(y (n, E), new state)``; rows
    that are not busy keep their state and read 0."""
    shape = state.shape
    s = state.reshape(shape[0], shape[1], -1)
    s_new = jnp.exp(dt[:, None, :] * a[None]) * s \
        + (dt * c)[:, None, :] * b[:, :, None]
    y = (s_new * cc[:, :, None]).sum(1) + d * c
    return (jnp.where(busy[:, None], y, 0.0),
            jnp.where(busy[:, None, None, None], s_new.reshape(shape), state))


def _kernel(slot_ref, n_busy_ref, bc_ref, x_ref, a_ref, d_ref, s_ref,
            y_ref, so_ref):
    n_state = s_ref.shape[1]
    i = pl.program_id(0)

    @pl.when(i < n_busy_ref[0])
    def _step():
        at = slot_ref[i] * (2 * n_state)      # the slot's [B | C] scalars
        c, dt = x_ref[0, 0], x_ref[0, 1]
        dtc = dt * c
        y = d_ref[...] * c
        for n in range(n_state):
            s_new = jnp.exp(dt * a_ref[n]) * s_ref[0, n] + dtc * bc_ref[at + n]
            so_ref[0, n] = s_new
            y = y + s_new * bc_ref[at + n_state + n]
        y_ref[0] = y

    @pl.when(n_busy_ref[0] == 0)
    def _nothing_busy():
        # every step maps to one block; hand it back as it came
        so_ref[...] = s_ref[...]
        y_ref[...] = jnp.zeros_like(y_ref)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssm_step(c, dt, b, cc, a, d, state, busy,
             slots: Optional[BusySlots] = None, *, interpret: bool = False):
    """:func:`ssm_step_xla` as one Pallas pass over the busy slots' state,
    written in place (``state`` is aliased to the result: donate it).
    Shapes as there.  ``slots``: the tick's busy list (built here from
    ``busy`` when not given)."""
    n, n_state, rows, lane = state.shape
    f32 = jnp.float32
    if slots is None:
        slots = busy_slots(busy, n)
    x = jnp.stack([slabs(c, rows, lane), slabs(dt, rows, lane)], axis=1)

    slot_map = lambda i, s, nb, bc: (s[i], 0, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3, grid=(n,),
        in_specs=[
            pl.BlockSpec((1, 2, rows, lane), slot_map),
            pl.BlockSpec((n_state, rows, lane), lambda i, s, nb, bc: (0, 0, 0)),
            pl.BlockSpec((rows, lane), lambda i, s, nb, bc: (0, 0)),
            pl.BlockSpec((1, n_state, rows, lane), slot_map),
        ],
        out_specs=[
            pl.BlockSpec((1, rows, lane), lambda i, s, nb, bc: (s[i], 0, 0)),
            pl.BlockSpec((1, n_state, rows, lane), slot_map),
        ])
    vma = _inherit_vma(c, state)
    y, new_state = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=[_sds((n, rows, lane), f32, vma=vma),
                   _sds(state.shape, f32, vma=vma)],
        # operands count the three prefetched scalars: state is the seventh
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="ssm_step",
        interpret=interpret,
    )(slots.slot, slots.n, scalars(b, cc), x, slabs(a, rows, lane),
      slabs(d, rows, lane), state)
    # a slot that is not busy was given no block: its read-out is whatever
    # the buffer held
    return jnp.where(busy[:, None], y.reshape(n, -1), 0.0), new_state
