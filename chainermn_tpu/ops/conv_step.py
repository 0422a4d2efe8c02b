"""One token of a causal depthwise convolution on a per-slot window, in
place.

A layer whose recurrence is fed through a short convolution over time (a
gated delta-rule layer's fused ``q | k | v``) keeps, for each sequence, the
last ``W - 1`` rows of the convolution's input: the WINDOW, ``(W - 1, C)``
in the pool's dtype.  A decode tick moves every BUSY slot one token on::

    y      = sum_i x_i * w_i        x = [window rows 0 .. W-2, the new row]
    window = [window rows 1 .. W-2, the new row]

in float32, summed in the order written (``parallel/kda.py::_short_conv``'s
own), and has to leave every other slot's window as it is, bit for bit
(``ops/kda_step.py``'s contract, for its reasons: a free slot's window is
the next occupant's start, a cached slot's is what a prefix hit copies).

Where the pool keeps it, an ``(N, W - 1, C)`` window with ``W - 1`` under a
sublane tile lies ROW-MAJOR OVER THE SLOTS: the chip's default layout puts
the short axis outermost, ``[W - 1][N][C]``, so a slot is one sublane of a
tile that holds :data:`SLOTS` of them and no copy can name it alone.  The
kernel therefore walks the window as the ``(W - 1, N, C)`` array it is (the
transposes around the call are bitcasts), a BLOCK of :data:`SLOTS` slots a
grid step: the blocks that hold a busy slot compacted to the front of the
grid (:func:`busy_blocks`), the grid's bound their number, the
window aliased to its result — a block without a busy slot is neither
fetched nor written — and inside a block a busy slot's rows move by a
select, so an idle slot's come back as the bits they were.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .._compat import shape_dtype_struct as _sds
from .kv_cache import BusySlots, _inherit_vma

__all__ = ["SLOTS", "busy_blocks", "conv_step", "conv_step_xla", "fits"]

#: slots a grid step: the sublanes of a 2-byte buffer's tile
SLOTS = 16
_COLUMNS = 4096     # channels a grid step, at most


def conv_step_xla(window, new, weight, busy):
    """The same step in plain ``jax.numpy`` (other backends, and the
    kernel's oracle): ``window (N, W-1, C)``, ``new (N, 1, C)``, ``weight
    (W, C)`` (``weight[-1]`` meets the new row), ``busy (N,) bool``.
    Returns ``(y (N, 1, C) float32, new window)``; rows that are not busy
    keep their window and read 0."""
    xs = jnp.concatenate([window.astype(new.dtype), new], axis=1)
    wf = weight.astype(jnp.float32)
    y = sum(xs[:, i:i + 1].astype(jnp.float32) * wf[i]
            for i in range(weight.shape[0]))
    keep = busy[:, None, None]
    return (jnp.where(keep, y, 0.0),
            jnp.where(keep, xs[:, 1:].astype(window.dtype), window))


def fits(window) -> bool:
    """Whether the kernel takes a pool's ``window (N, W-1, C)``: whole
    blocks of slots."""
    return window.shape[0] % SLOTS == 0


def busy_blocks(busy, n: int) -> BusySlots:
    """The blocks of :data:`SLOTS` slots that hold a busy slot of ``busy
    (n,) bool``, first, in order, as ``ops/kv_cache.py::busy_slots`` lists
    slots: entry ``t < n[0]`` is grid step ``t``'s block, the entries from
    there on repeat the last.  No sort: a block's place is the count of
    held blocks before it — a handful of operations on ``n / SLOTS``
    entries, which every layer of a tick writes alike and the compiler
    keeps once."""
    held = busy.reshape(n // SLOTS, SLOTS).any(-1)
    n_held = held.sum().astype(jnp.int32)
    place = jnp.cumsum(held) - held
    step = jnp.minimum(jnp.arange(n // SLOTS), jnp.maximum(n_held - 1, 0))
    block = (held & (place == step[:, None])).argmax(-1)
    return BusySlots(block.astype(jnp.int32), n_held.reshape(1))


def _kernel(block_ref, n_ref, new_ref, w_ref, busy_ref, win_ref,
            y_ref, wout_ref):
    """Grid step ``(t, j)``: column block ``j`` of the slots of block
    ``block[t]``; with nothing busy the one step hands its block back as it
    came (its mask is all false).  An idle slot's row of ``y`` is whatever
    its window gives: the caller masks it."""
    del block_ref, n_ref                      # read by the index maps only
    f32 = jnp.float32
    width = w_ref.shape[0]
    keep = busy_ref[...] != 0                                   # (SLOTS, 1)
    new = new_ref[...]                                          # (SLOTS, cb)
    rows = [win_ref[i] for i in range(width - 1)]
    xs = [r.astype(new.dtype) for r in rows] + [new]
    y = xs[0].astype(f32) * w_ref[0:1, :].astype(f32)
    for i in range(1, width):
        y = y + xs[i].astype(f32) * w_ref[i:i + 1, :].astype(f32)
    y_ref[...] = y
    moved = rows[1:] + [new.astype(wout_ref.dtype)]
    for i in range(width - 1):
        wout_ref[i] = jnp.where(keep, moved[i], rows[i])


@functools.partial(jax.jit, static_argnames=("interpret",))
def conv_step(window, new, weight, busy, *, interpret: bool = False):
    """:func:`conv_step_xla` as one Pallas pass over the blocks of slots
    that hold a busy one, written in place (``window`` is aliased to the
    result: donate it).  Shapes as there, where :func:`fits`."""
    n, keep, c = window.shape
    width = weight.shape[0]
    blocks = busy_blocks(busy, n)
    cb = next((b for b in range(min(c, _COLUMNS), 127, -128) if c % b == 0),
              c)

    rows = lambda t, j, blk, nb: (blk[t], j)
    planes = pl.BlockSpec((keep, SLOTS, cb),
                          lambda t, j, blk, nb: (0, blk[t], j))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(jnp.maximum(blocks.n[0], 1), c // cb),
        in_specs=[
            pl.BlockSpec((SLOTS, cb), rows),
            pl.BlockSpec((width, cb), lambda t, j, blk, nb: (0, j)),
            pl.BlockSpec((SLOTS, 1), lambda t, j, blk, nb: (blk[t], 0)),
            planes,
        ],
        out_specs=[pl.BlockSpec((SLOTS, cb), rows), planes])
    vma = _inherit_vma(window, new)
    y, planes_out = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=[_sds((n, c), jnp.float32, vma=vma),
                   _sds((keep, n, c), window.dtype, vma=vma)],
        # operands count the two prefetched scalars: the window is the sixth
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        name="conv_step",
        interpret=interpret,
    )(blocks.slot, blocks.n, new[:, 0], weight,
      busy.astype(jnp.int32)[:, None], jnp.swapaxes(window, 0, 1))
    # a slot that is not busy reads whatever its window gives, or, in a
    # block that was given no step, whatever the buffer held
    return (jnp.where(busy[:, None, None], y[:, None], 0.0),
            jnp.swapaxes(planes_out, 0, 1))
