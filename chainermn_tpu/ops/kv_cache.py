"""The decoders' cache writes: one new row a sequence, written in place.

An incremental decoder adds ONE row a token to each cache buffer of each
layer.  Written as ``lax.dynamic_update_slice`` the compiler either fuses
the update into the buffer's readers as a select over the whole buffer or,
for per-row positions (``vmap`` of it), runs a ``while`` loop over every row
of the batch whose body is an update, a select and two slices: the served
tick of 32 slots x 24 layers x (K, V) ran 48 such loops of 32 iterations,
4.8 ms of a 6.0 ms tick, to write 3 busy slots' rows (PERF.md, Findings
PR 35 and PR 37).  The two kernels here map ONLY the sublane block that
holds the new row (a scalar-prefetched index map), replace that row by an
iota select, and alias every cache operand to its result
(``input_output_aliases``): a donated buffer is written in place and nothing
else of it is touched.

* :func:`write_rows` — the SERVED TICK's writer: every slot of a pool at its
  own position, ``pos (N,)``, and only the ``busy`` slots written.  One call
  writes all of a layer's buffers (K and V; one latent buffer; a ring at
  ``pos % window``, which the caller computes) over a compacted list of the
  busy slots (:func:`busy_slots`, built once a tick and handed to every
  layer): grid step ``t < n_busy`` rewrites the block of slot ``slot[t]``
  that holds row ``min(pos, rows - 1)``; the grid's bound is the list's
  length (one step where nothing is busy, which hands its block back as it
  came).  CONTRACT, the same on the kernel and on the XLA path beside it: a
  slot that is not busy gets its buffers back bit for bit — a free slot's
  zeros, a cached prefix, a ring nobody decodes from; ``busy`` None writes
  every slot's row.
* :func:`cache_append` — a SCALAR position for a whole batch
  (``lm_generate``'s closed batch: kernel ``kv_cache_write``, one block of all
  batch rows), multi-row writes with ``rows | 8``, and the
  ``dynamic_update_slice`` everywhere else (other backends, a prefill's
  slab, an unaligned total).  Its per-row face (rank-1 ``pos``) is
  :func:`write_rows`.

Semantics of both are exactly ``dynamic_update_slice`` at ``pos`` along the
row axis, start clamped inside the buffer.  The Pallas paths are
parity-tested off-chip in interpret mode (tests/test_kv_cache.py), compiled
for a described chip in tests/test_chip_compile.py, and run compiled by the
serving cells of the benchmark (the custom call ``cache_write_rows`` in a
traced tick's ``breakdown.device_ops``).

Reference relationship: the reference had no incremental decoding at all
(its seq2seq example re-ran the full decoder per token —
examples/seq2seq/seq2seq.py :: translate_one [uv], SURVEY.md §2.9).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax

import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .._compat import shape_dtype_struct as _sds

__all__ = ["cache_append", "write_rows", "busy_slots", "BusySlots"]


def _inherit_vma(*xs) -> frozenset:
    """Union of the inputs' varying-mesh-axes sets (same helper as
    ops/flash_attention.py) — pallas_call under shard_map must declare how
    its outputs vary."""
    vma = set()
    for x in xs:
        v = getattr(getattr(x, "aval", None), "vma", None)
        if v:
            vma |= set(v)
    return frozenset(vma)


class BusySlots(NamedTuple):
    """A tick's busy slots, compacted, on the device: ``slot[t]`` is grid
    step ``t``'s slot for ``t < n[0]``, the grid's bound; the entries from
    there on repeat the last busy slot (:func:`busy_slots`)."""
    slot: jax.Array     # (N,) int32
    n: jax.Array        # (1,) int32


def busy_slots(busy, n: int) -> BusySlots:
    """The busy slots of ``busy (n,) bool`` first, in slot order (None:
    every slot).  An argsort of ``n`` entries: a tick builds the list once
    and hands it to every layer's :func:`write_rows`."""
    if busy is None:
        return BusySlots(jnp.arange(n, dtype=jnp.int32),
                         jnp.full((1,), n, jnp.int32))
    n_busy = busy.sum().astype(jnp.int32)
    order = jnp.argsort(~busy, stable=True).astype(jnp.int32)
    slot = jnp.where(jnp.arange(n) < n_busy, order,
                     order[jnp.maximum(n_busy - 1, 0)])
    return BusySlots(slot, n_busy.reshape(1))


def _sublanes(dtype) -> int:
    """Rows of the smallest second-minor block Mosaic takes whole for
    ``dtype``: 8 of 4 bytes, 16 of 2, 32 of 1."""
    return 8 * max(4 // jnp.dtype(dtype).itemsize, 1)


def _rows_kernel(slot_ref, n_ref, pos_ref, *refs, subs):
    """Grid step ``t``: in each buffer's mapped block, the row ``min(pos,
    rows - 1) % sub`` of slot ``slot[t]`` becomes the slot's new row (iota
    select, no dynamic store); a step past the list's end — the one step of
    a tick with nothing busy — hands the block back as it came."""
    k = len(subs)
    t = pl.program_id(0)
    listed = t < n_ref[0]
    p = pos_ref[slot_ref[t]]
    for new, cin, cout, (sub, rows) in zip(refs[:k], refs[k:2 * k],
                                           refs[2 * k:], subs):
        idx = jax.lax.broadcasted_iota(jnp.int32, cin.shape, 1)
        sel = (idx == jnp.minimum(p, rows - 1) % sub) & listed
        cout[...] = jnp.where(sel, new[...], cin[...])


@functools.partial(jax.jit, static_argnames=("interpret",))
def _write_rows_kernel(bufs, rows, pos, slots: BusySlots, *,
                       interpret: bool = False):
    """The kernel face of :func:`write_rows` (traced once a shape):
    ``bufs`` a tuple of ``(N, R, C_i)``, ``rows`` of ``(N, 1, C_i)`` in the
    buffers' dtypes, ``pos (N,) int32``."""
    subs = tuple((_sublanes(c.dtype), c.shape[1]) for c in bufs)

    def new_spec(c):
        return pl.BlockSpec((1, 1, c.shape[2]),
                            lambda t, slot, n, p: (slot[t], 0, 0))

    def block_spec(c, sub, total):
        return pl.BlockSpec(
            (1, sub, c.shape[2]),
            lambda t, slot, n, p: (
                slot[t], jnp.minimum(p[slot[t]], total - 1) // sub, 0))

    blocks = [block_spec(c, *s) for c, s in zip(bufs, subs)]
    vma = _inherit_vma(*bufs, *rows)
    k = len(bufs)
    out = pl.pallas_call(
        functools.partial(_rows_kernel, subs=subs),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(jnp.maximum(slots.n[0], 1),),
            in_specs=[new_spec(c) for c in bufs] + blocks,
            out_specs=blocks),
        out_shape=[_sds(c.shape, c.dtype, vma=vma) for c in bufs],
        # operands count the three prefetched scalars and the new rows
        input_output_aliases={3 + k + i: i for i in range(k)},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="cache_write_rows",
        interpret=interpret,
    )(slots.slot, slots.n, pos, *rows, *bufs)
    return tuple(out)


def _write_rows_xla(c, new, pos, busy, axis: int = 1):
    """One buffer's per-row write along ``axis`` as a vmapped
    ``dynamic_update_slice``; a slot that is not busy (``busy`` None: none
    such) is handed the rows it holds there."""
    def one(c_i, new_i, p, b):
        at = tuple(p if d == axis - 1 else 0 for d in range(c_i.ndim))
        if b is not None:
            new_i = jnp.where(b, new_i, jax.lax.dynamic_slice(
                c_i, at, new_i.shape))
        return jax.lax.dynamic_update_slice(c_i, new_i, at)

    return jax.vmap(one, in_axes=(0, 0, 0, None if busy is None else 0))(
        c, new, pos, busy)


def write_rows(bufs, rows, pos, busy=None, *,
               slots: Optional[BusySlots] = None, interpret: bool = False):
    """Write slot ``b``'s new rows ``rows[i][b]`` into ``bufs[i][b]`` at row
    ``pos[b]`` (clamped inside the buffer), for every buffer ``i`` of a
    layer and every BUSY slot ``b``; returns the tuple of updated buffers.

    ``bufs``: ``(N, R, C_i)`` buffers (donate them: the kernel writes in
    place); ``rows``: ``(N, S_q, C_i)``; ``pos (N,) int32``; ``busy (N,)
    bool`` or None (every slot).  A slot that is not busy gets its buffers
    back bit for bit.  One new row a slot (``S_q == 1``) on a TPU, ``R``
    whole sublane blocks, takes the kernel (``cache_write_rows``) over the
    list ``slots`` (:func:`busy_slots` of ``busy``; built here when not
    given); everything else the vmapped ``dynamic_update_slice``.
    ``interpret=True`` runs the kernel in interpret mode (off-chip parity
    tests) and raises where the kernel does not fit."""
    bufs, rows = tuple(bufs), tuple(rows)
    n = bufs[0].shape[0]
    if pos.shape != (n,) or any(c.shape[0] != n for c in bufs):
        raise ValueError(
            f"per-row pos length {pos.shape[0]} != leading (row) dim "
            f"of the caches {[c.shape for c in bufs]}")
    rows = tuple(r.astype(c.dtype) for r, c in zip(rows, bufs))
    fits = (interpret or jax.default_backend() == "tpu") and all(
        c.ndim == 3 and r.shape[1] == 1
        and c.shape[1] % _sublanes(c.dtype) == 0 for c, r in zip(bufs, rows))
    if interpret and not fits:
        raise ValueError(
            f"the kernel writes one row a slot into (N, rows, columns) "
            f"buffers of whole sublane blocks; got buffers "
            f"{[c.shape for c in bufs]}, rows {[r.shape for r in rows]}")
    if not fits:
        return tuple(_write_rows_xla(c, r, pos, busy)
                     for c, r in zip(bufs, rows))
    if slots is None:
        slots = busy_slots(busy, n)
    return _write_rows_kernel(bufs, rows, pos.astype(jnp.int32), slots,
                              interpret=interpret)


_ROWS = 8  # sublane tile: the smallest legal second-minor block


def _append_kernel(pos_ref, knew_ref, vnew_ref, kin_ref, vin_ref,
                   kout_ref, vout_ref, *, rows):
    """Rewrite the 8-row sublane block containing ``pos``, replacing only
    rows [pos, pos+rows) (iota-range select — no dynamic stores).

    The new-row operands arrive TILED to the full 8-row block
    (8/rows copies): because ``rows | 8`` and the caller guarantees
    ``pos % rows == 0``, the in-block start ``pos % 8`` is a multiple of
    ``rows``, so ``tiled[j] == new[j - start]`` for every selected row —
    placement needs no dynamic shift at all."""
    start = pos_ref[0] % _ROWS
    idx = jax.lax.broadcasted_iota(jnp.int32, kin_ref.shape,
                                   kin_ref.ndim - 2)
    sel = (idx >= start) & (idx < start + rows)
    kout_ref[...] = jnp.where(sel, knew_ref[...], kin_ref[...])
    vout_ref[...] = jnp.where(sel, vnew_ref[...], vin_ref[...])


def cache_append(kc, vc, k_new, v_new, pos, *, axis: int = 1,
                 impl: str = "auto", pos_aligned: bool = False,
                 interpret: bool = False):
    """Write ``k_new``/``v_new`` into ``kc``/``vc`` at ``pos`` along
    ``axis``; returns the updated ``(kc, vc)``.

    ``impl='auto'`` uses the Pallas scatter on TPU when the write is
    ``rows`` rows with ``rows | 8`` (one row = the decode tick; more: no
    caller in the package since beam search went, ROADMAP D15(a)), and
    the XLA ``dynamic_update_slice`` everywhere else (other backends, and
    slab prefill writes where a full-pass update is amortized and XLA's
    slab write is fine).  CONTRACT for rows > 1: ``pos`` must be a
    multiple of ``rows`` — the in-tile placement relies on it.  A concrete misaligned ``pos`` falls
    back to the exact dus (or raises under ``impl='pallas'``); a TRACED
    ``pos`` cannot be checked, so multi-row auto-dispatch additionally
    requires the caller's ``pos_aligned=True`` promise — without it the
    write takes the dus path rather than risk silent corruption.
    ``interpret=True`` (with ``impl='pallas'``) runs the kernel in
    interpret mode for off-chip parity tests.

    **Per-row positions** (the serving cache pool's contract): ``pos``
    may be a RANK-1 vector of length ``kc.shape[0]`` — row ``b`` of the
    new K/V is then written at ``pos[b]`` along ``axis``, independently
    per row.  Every slot in a continuous-batching pool sits at its own
    sequence length, so the one-token-per-active-slot tick needs exactly
    this ragged write: it is :func:`write_rows` with every row busy (the
    tick itself calls that, with its busy mask), whose own kernel runs
    where it fits; ``impl='xla'`` keeps the vmapped
    ``dynamic_update_slice``.  Scalar ``pos`` behavior is unchanged;
    ``impl='pallas'`` with a vector raises — this function's scatter kernel
    maps a single block per call.
    """
    if impl not in ("auto", "pallas", "xla"):
        raise ValueError(f"impl must be auto|pallas|xla, got {impl!r}")
    if not isinstance(pos, (int, np.integer)) and getattr(pos, "ndim", 0) == 1:
        if impl == "pallas":
            raise ValueError(
                "impl='pallas' supports scalar pos only; a per-row position "
                "vector takes the vmapped dynamic_update_slice path "
                "(impl='auto' or 'xla')")
        if axis < 1:
            raise ValueError(
                f"per-row pos needs the row axis (0) distinct from the "
                f"write axis, got axis={axis}")
        if pos.shape[0] != kc.shape[0]:
            raise ValueError(
                f"per-row pos length {pos.shape[0]} != leading (row) dim "
                f"{kc.shape[0]} of the cache {kc.shape}")

        if impl == "auto" and axis == 1:
            return write_rows((kc, vc), (k_new, v_new), pos)
        return tuple(_write_rows_xla(c, n.astype(c.dtype), pos, None, axis)
                     for c, n in ((kc, k_new), (vc, v_new)))
    # Pallas envelope: a single-row write whose position axis is the
    # SECOND-MINOR dim (the attention-native cache layouts put positions
    # there) with an 8-divisible extent — the mapped block is then the
    # (8, minor) sublane tile containing ``pos``, the smallest Mosaic
    # will address.
    rows = k_new.shape[axis]
    concrete = isinstance(pos, (int, np.integer))
    aligned = (rows == 1
               or (concrete and pos % rows == 0)
               or (not concrete and pos_aligned))
    fits = (rows >= 1 and _ROWS % rows == 0 and axis == kc.ndim - 2
            and kc.shape[axis] % _ROWS == 0 and aligned)
    use_pallas = (impl == "pallas"
                  or (impl == "auto" and fits
                      and jax.default_backend() == "tpu"))
    if not use_pallas:
        return (jax.lax.dynamic_update_slice_in_dim(kc, k_new, pos, axis),
                jax.lax.dynamic_update_slice_in_dim(vc, v_new, pos, axis))
    if not fits:
        raise ValueError(
            f"impl='pallas' needs a write of rows dividing {_ROWS} along "
            f"the second-minor axis with an 8-divisible extent, at a "
            f"rows-aligned pos (traced pos needs pos_aligned=True); got "
            f"axis {axis} of shape {kc.shape} writing "
            f"{k_new.shape[axis]} rows at pos {pos!r}")
    if not interpret and jax.default_backend() != "tpu":
        # Forced pallas off-chip: fail at dispatch with an actionable
        # message instead of deep in Mosaic lowering (ADVICE round 5) —
        # compiled Pallas is TPU-only.
        raise ValueError(
            f"impl='pallas' with interpret=False requires a TPU backend "
            f"(current backend: {jax.default_backend()!r}); pass "
            f"interpret=True for off-chip parity runs, or impl='auto'/"
            f"'xla' to take the dynamic_update_slice path")

    block = tuple(_ROWS if d == axis else n for d, n in enumerate(kc.shape))
    new_block = tuple(1 if d == axis else n for d, n in enumerate(kc.shape))
    zero_idx = (0,) * kc.ndim

    def at_pos(i, p):
        # block index map in units of the block shape: the position axis
        # uses 8-row blocks, so the block index is pos // 8
        return tuple(p[0] // _ROWS if d == axis else 0
                     for d in range(kc.ndim))

    vma = _inherit_vma(kc, vc, k_new, v_new)
    # rows == 1 keeps the 1-row new-operand block (the hot greedy tick:
    # the where broadcasts it for free); rows > 1 tiles the new rows to
    # the full 8-row block so in-tile placement is shift-free (see
    # _append_kernel).
    nb = new_block if rows == 1 else block
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(1,),
        in_specs=[pl.BlockSpec(nb, lambda i, p: zero_idx),
                  pl.BlockSpec(nb, lambda i, p: zero_idx),
                  pl.BlockSpec(block, at_pos),
                  pl.BlockSpec(block, at_pos)],
        out_specs=[pl.BlockSpec(block, at_pos),
                   pl.BlockSpec(block, at_pos)])
    new_shape = kc.shape[:axis] + (rows,) + kc.shape[axis + 1:]
    kn = k_new.reshape(new_shape).astype(kc.dtype)
    vn = v_new.reshape(new_shape).astype(vc.dtype)
    if rows > 1:
        reps = tuple(_ROWS // rows if d == axis else 1
                     for d in range(kc.ndim))
        kn, vn = jnp.tile(kn, reps), jnp.tile(vn, reps)
    return pl.pallas_call(
        functools.partial(_append_kernel, rows=rows), grid_spec=grid_spec,
        out_shape=[_sds(kc.shape, kc.dtype, vma=vma),
                   _sds(vc.shape, vc.dtype, vma=vma)],
        input_output_aliases={3: 0, 4: 1},  # kc, vc (after the scalar arg)
        name="kv_cache_write",
        interpret=interpret,
    )(jnp.asarray([pos], jnp.int32).astype(jnp.int32), kn, vn, kc, vc)
