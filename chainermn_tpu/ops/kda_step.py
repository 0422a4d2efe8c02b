"""One step of a gated delta rule on a per-slot recurrent state, in place.

A delta-rule linear-attention layer keeps, for each sequence and head, one
``(d_k, d_v)`` float32 state ``S`` instead of a row a token.  A decode tick
moves every BUSY slot's state one token on::

    S' = Diag(exp(g)) S
    S_new = S' + beta k (v - S'^T k)^T
    o = S_new^T q

and has to leave every other slot's state as it is, bit for bit: a free
slot's state is the next occupant's zero start and a cached slot's state is
what a prefix hit copies.  So the kernel does not run "every slot, then
select": the busy slots are compacted to the front of the grid's slot axis
by a scalar-prefetched index vector, the steps past the last busy slot map
to the block the last busy step already holds (no fetch, no write-back:
``ops/moe_gmm.py``'s rule), and the state operand is aliased to the state
result, so the blocks no step maps to are never touched.  What a tick reads
and writes of the pool's state is then the busy slots' share, not the pool.

The work of a step is a handful of passes over ``S`` on the vector unit
(decay, ``S'^T k``, the rank-1 update, the read-out), all float32: the
state is the layer's memory of the whole sequence and a rounding here is
carried to every later token.  The column forms of ``k``, ``q`` and the
decay come from one in-kernel transpose of an ``(heads, d_k)`` tile each.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .._compat import shape_dtype_struct as _sds
from .kv_cache import BusySlots, busy_slots

__all__ = ["kda_step", "kda_step_xla"]


def kda_step_xla(q, k, v, g, beta, state, busy):
    """The same step in plain ``jax.numpy`` (other backends, and the
    kernel's oracle): ``q, k, g (N, H, d_k)``, ``v (N, H, d_v)``, ``beta
    (N, H)``, ``state (N, H, d_k, d_v)`` float32, ``busy (N,) bool``.
    Returns ``(o (N, H, d_v), new state)``; rows that are not busy keep
    their state and read 0."""
    s_dec = state * jnp.exp(g)[..., None]
    u = (s_dec * k[..., None]).sum(-2)
    r = beta[..., None] * (v - u)
    s_new = s_dec + k[..., None] * r[..., None, :]
    o = (s_new * q[..., None]).sum(-2)
    keep = busy[:, None, None]
    return (jnp.where(keep, o, 0.0),
            jnp.where(keep[..., None], s_new, state))


def _kernel(slot_ref, n_busy_ref, beta_ref, q_ref, k_ref, g_ref, v_ref, s_ref,
            o_ref, so_ref):
    n_heads = s_ref.shape[1]                  # the heads of this block
    i = pl.program_id(1)
    # the slot's betas, scalars: one a head of the whole layer
    at = (slot_ref[i] * pl.num_programs(0) + pl.program_id(0)) * n_heads

    @pl.when(i < n_busy_ref[0])
    def _step():
        # (heads, d_k) tiles to (d_k, heads): column j is head j's vector
        q_t = q_ref[0].T
        k_t = k_ref[0].T
        a_t = jnp.exp(g_ref[0]).T
        for j in range(n_heads):
            k_col = k_t[:, j:j + 1]
            s_dec = s_ref[0, j] * a_t[:, j:j + 1]
            u = (s_dec * k_col).sum(0, keepdims=True)            # (1, d_v)
            r = beta_ref[at + j] * (v_ref[0, j:j + 1] - u)
            s_new = s_dec + k_col * r
            so_ref[0, j] = s_new
            o_ref[0, j:j + 1] = (s_new * q_t[:, j:j + 1]).sum(
                0, keepdims=True)

    @pl.when(n_busy_ref[0] == 0)
    def _nothing_busy():
        # every step maps to one block; hand it back as it came
        so_ref[...] = s_ref[...]
        o_ref[...] = jnp.zeros_like(o_ref)


def _head_block(n_heads: int) -> int:
    return 8 if n_heads % 8 == 0 else n_heads


@functools.partial(jax.jit, static_argnames=("interpret",))
def kda_step(q, k, v, g, beta, state, busy,
             slots: Optional[BusySlots] = None, *, interpret: bool = False):
    """:func:`kda_step_xla` as one Pallas pass over the busy slots'
    state, written in place (``state`` is aliased to the result: donate
    it).  Shapes as there; ``d_k`` and ``d_v`` whole lane tiles on a TPU.
    ``slots``: the tick's busy list (built here from ``busy`` when not
    given).  Every vector is read where it lies, a busy slot's head block
    a step; ``beta`` from scalar memory."""
    n, h, dk = q.shape
    dv = v.shape[-1]
    hb = _head_block(h)
    f32 = jnp.float32
    # busy slots first, in slot order; the rest of the axis repeats the
    # last busy slot, whose blocks the kernel then already holds
    if slots is None:
        slots = busy_slots(busy, n)

    heads = lambda b, i, s, nb, bt: (s[i], b, 0)
    states = pl.BlockSpec((1, hb, dk, dv),
                          lambda b, i, s, nb, bt: (s[i], b, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3, grid=(h // hb, n),
        in_specs=[pl.BlockSpec((1, hb, dk), heads)] * 3
        + [pl.BlockSpec((1, hb, dv), heads), states],
        out_specs=[pl.BlockSpec((1, hb, dv), heads), states])
    vma = frozenset().union(*(getattr(getattr(a, "aval", None), "vma", None)
                              or () for a in (q, state)))
    o, new_state = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=[_sds((n, h, dv), f32, vma=vma),
                   _sds(state.shape, f32, vma=vma)],
        # operands count the three prefetched scalars: state is the eighth
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        name="kda_step",
        interpret=interpret,
    )(slots.slot, slots.n, beta.astype(f32).reshape(-1), q.astype(f32),
      k.astype(f32), g.astype(f32), v.astype(f32), state)
    # a slot that is not busy was given no block: its read-out is whatever
    # the buffer held, and must not reach the rows above a cached prefix
    return jnp.where(busy[:, None, None], o, 0.0), new_state
