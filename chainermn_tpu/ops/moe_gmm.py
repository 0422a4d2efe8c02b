"""Grouped matmul over the experts that have tokens (Pallas, ``moe_gmm``).

The dropless expert layer (``parallel/moe.py::moe_dropless``) sorts its
token-to-expert assignments by expert and lays each expert's rows in a
group ALIGNED to the row tile ``tm``, so every row tile belongs to exactly
one expert.  This kernel multiplies each row tile by its expert's weight:

    out[tile t] = x[tile t] @ w[tile_expert[t]]            (t < n_valid)

* ``tile_expert`` and ``n_valid`` arrive by scalar prefetch.  The weight
  block's index map reads ``tile_expert``, so an expert with no token is
  never named and costs NO weight read; consecutive tiles of one expert
  map the same block and Pallas issues no second copy.
* Row tiles at or past ``n_valid`` (the worst-case padding a dropless
  layer must size for) hold the index maps at the last live tile and skip
  the body under ``pl.when`` — no copy, no matmul, about a grid step's
  overhead each (as the steps past the last busy slot in
  ``ops/kda_step.py``).
  Their output rows are never written: the caller masks them.
* Grid ``(N tiles, row tiles)``, rows fastest, the whole contraction in
  one block: a weight block is read once per (expert, N tile), the small
  row tile once per N tile; no accumulator, no K loop.

CPU and other backends take the same kernel in interpret mode in tests;
the layer's XLA fallback (a dense loop over the held experts) is in
``parallel/moe.py``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .._compat import shape_dtype_struct as _sds

__all__ = ["moe_gmm", "pick_tn"]

_LANES = 128
#: a weight block (K x tn, double-buffered by Pallas) stays under this
_W_BLOCK_BYTES = 8 << 20


def pick_tn(k: int, n: int, itemsize: int = 2) -> int:
    """Widest lane-multiple divisor of ``n`` whose ``(k, tn)`` weight block
    stays under :data:`_W_BLOCK_BYTES`; ``n`` itself when it is small or
    has no such divisor."""
    if k * n * itemsize <= _W_BLOCK_BYTES or n % _LANES:
        return n
    best = _LANES
    for tn in range(_LANES, n + 1, _LANES):
        if n % tn == 0 and k * tn * itemsize <= _W_BLOCK_BYTES:
            best = tn
    return best


def _kernel(tile_expert_ref, n_valid_ref, x_ref, w_ref, o_ref):
    del tile_expert_ref                       # read by the index maps only

    @pl.when(pl.program_id(1) < n_valid_ref[0])
    def _tile():
        o_ref[...] = jax.lax.dot_general(
            x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tm", "tn", "interpret"))
def moe_gmm(x, w, tile_expert, n_valid, *, tm: int, tn: int = 0,
            interpret: bool = False):
    """``x (M, K)`` rows grouped by expert in ``tm``-aligned groups,
    ``w (E, K, N)`` the held experts' weights, ``tile_expert (M // tm,)``
    int32 the expert of each row tile, ``n_valid`` int32 scalar: the
    number of leading row tiles that hold rows.  Returns ``(M, N)`` in
    ``x.dtype``; the rows of tiles at or past ``n_valid`` are
    UNSPECIFIED (never written) — mask them, do not multiply them."""
    m, k = x.shape
    e, k2, n = w.shape
    assert k == k2 and m % tm == 0, (x.shape, w.shape, tm)
    tn = tn or pick_tn(k, n, w.dtype.itemsize)
    assert n % tn == 0, (n, tn)
    n_tiles_m, n_tiles_n = m // tm, n // tn

    def last(i, nv_ref):
        return jnp.minimum(i, jnp.maximum(nv_ref[0] - 1, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(n_tiles_n, n_tiles_m),
        in_specs=[
            pl.BlockSpec((tm, k), lambda j, i, te, nv: (last(i, nv), 0)),
            pl.BlockSpec((None, k, tn),
                         lambda j, i, te, nv: (te[last(i, nv)], 0, j)),
        ],
        out_specs=pl.BlockSpec((tm, tn),
                               lambda j, i, te, nv: (last(i, nv), j)))
    vma = frozenset().union(*(getattr(getattr(a, "aval", None), "vma", None)
                              or () for a in (x, w)))
    block_bytes = 2 * (k * tn * w.dtype.itemsize
                       + tm * (k + tn) * x.dtype.itemsize)
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=_sds((m, n), x.dtype, vma=vma),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=max(block_bytes + (8 << 20), 32 << 20)),
        name="moe_gmm",
        interpret=interpret,
    )(tile_expert.astype(jnp.int32),
      jnp.asarray(n_valid, jnp.int32).reshape(1), x, w)
