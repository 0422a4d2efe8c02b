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

The product differentiates (``jax.custom_vjp``): ``dX[tile t] = dY[tile t]
@ w[tile_expert[t]]^T`` is the same kernel on the transposed weights, and
``dW[e] = sum_t x[tile t]^T @ dY[tile t]`` over the expert's tiles is the
kernel ``moe_gmm_dw`` (same grid, a float32 accumulator an expert's run of
tiles, the result aliased to a zero buffer so that an expert with no rows
reads nothing and gets zeros).  Dead tiles contribute to neither.

CPU and other backends take the same kernels in interpret mode in tests;
the layer's XLA fallback (a dense loop over the held experts) is in
``parallel/moe.py``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .._compat import pcast_varying, shape_dtype_struct as _sds
from .flash_attention import _inherit_vma as _vma

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


def _last_live(i, nv_ref):
    """Row tile ``i``, held at the last live one past ``n_valid``."""
    return jnp.minimum(i, jnp.maximum(nv_ref[0] - 1, 0))


def _product(x, w, tile_expert, n_valid, tm: int, tn: int, interpret: bool):
    """The forward kernel: ``tile_expert`` int32, ``n_valid (1,)`` int32."""
    m, k = x.shape
    e, k2, n = w.shape
    assert k == k2 and m % tm == 0, (x.shape, w.shape, tm)
    tn = tn or pick_tn(k, n, w.dtype.itemsize)
    assert n % tn == 0, (n, tn)
    n_tiles_m, n_tiles_n = m // tm, n // tn
    last = _last_live

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(n_tiles_n, n_tiles_m),
        in_specs=[
            pl.BlockSpec((tm, k), lambda j, i, te, nv: (last(i, nv), 0)),
            pl.BlockSpec((None, k, tn),
                         lambda j, i, te, nv: (te[last(i, nv)], 0, j)),
        ],
        out_specs=pl.BlockSpec((tm, tn),
                               lambda j, i, te, nv: (last(i, nv), j)))
    block_bytes = 2 * (k * tn * w.dtype.itemsize
                       + tm * (k + tn) * x.dtype.itemsize)
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=_sds((m, n), x.dtype, vma=_vma(x, w)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=max(block_bytes + (8 << 20), 32 << 20)),
        name="moe_gmm",
        interpret=interpret,
    )(tile_expert, n_valid, x, w)


def _dw_kernel(tile_expert_ref, n_valid_ref, x_ref, dy_ref, zeros_ref, o_ref,
               acc_ref, *, n_tiles: int):
    """One row tile's ``x^T dy`` added to its expert's float32 accumulator;
    the expert's LAST tile writes the block.  ``zeros_ref`` is the result's
    own buffer (aliased, never read): the block of an expert no tile names
    is never visited and keeps its zeros."""
    del zeros_ref
    i, nv = pl.program_id(1), n_valid_ref[0]

    @pl.when(jnp.logical_and(i == 0, nv == 0))
    def _no_rows():     # the one block the held index maps still name
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(i < nv)
    def _tile():
        mine = tile_expert_ref[i]
        first = jnp.logical_or(
            i == 0, tile_expert_ref[jnp.maximum(i - 1, 0)] != mine)
        last = jnp.logical_or(
            i == nv - 1,
            tile_expert_ref[jnp.minimum(i + 1, n_tiles - 1)] != mine)
        part = jax.lax.dot_general(
            x_ref[...], dy_ref[...], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

        @pl.when(first)
        def _set():
            acc_ref[...] = part

        @pl.when(jnp.logical_not(first))
        def _add():
            acc_ref[...] += part

        @pl.when(last)
        def _write():
            o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _weight_grad(x, dy, tile_expert, n_valid, n_experts: int, tm: int,
                 interpret: bool, dtype):
    """``dW[e] = sum over e's row tiles of x_tile^T dy_tile`` — ``(E, K, N)``
    in ``dtype`` (kernel ``moe_gmm_dw``).  Grid ``(N tiles, row tiles)``,
    rows fastest: consecutive tiles of one expert map the same result block
    and accumulate in a float32 scratch; tiles at or past ``n_valid`` hold
    every index and do nothing; an expert with no rows reads nothing and
    its block stays the zeros it is handed."""
    m, k = x.shape
    n = dy.shape[1]
    itemsize = jnp.dtype(dtype).itemsize
    # the float32 accumulator is the large block here
    tn = pick_tn(k, n, 4)
    n_tiles_m, n_tiles_n = m // tm, n // tn
    last = _last_live
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(n_tiles_n, n_tiles_m),
        in_specs=[
            pl.BlockSpec((tm, k), lambda j, i, te, nv: (last(i, nv), 0)),
            pl.BlockSpec((tm, tn), lambda j, i, te, nv: (last(i, nv), j)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((None, k, tn),
                               lambda j, i, te, nv: (te[last(i, nv)], 0, j)),
        scratch_shapes=[pltpu.VMEM((k, tn), jnp.float32)])
    vma = _vma(x, dy)
    zeros = jnp.zeros((n_experts, k, n), dtype)
    for ax in sorted(vma):
        zeros = pcast_varying(zeros, ax)
    block_bytes = k * tn * (4 + 2 * itemsize) \
        + 2 * tm * (k + tn) * x.dtype.itemsize
    return pl.pallas_call(
        functools.partial(_dw_kernel, n_tiles=n_tiles_m),
        grid_spec=grid_spec,
        out_shape=_sds((n_experts, k, n), dtype, vma=vma),
        # operands count the two prefetched scalars
        input_output_aliases={4: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=max(block_bytes + (8 << 20), 32 << 20)),
        name="moe_gmm_dw",
        interpret=interpret,
    )(tile_expert, n_valid, x, dy, zeros)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _gmm(x, w, tile_expert, n_valid, tm, tn, interpret):
    return _product(x, w, tile_expert, n_valid, tm, tn, interpret)


def _gmm_fwd(x, w, tile_expert, n_valid, tm, tn, interpret):
    return (_product(x, w, tile_expert, n_valid, tm, tn, interpret),
            (x, w, tile_expert, n_valid))


def _gmm_bwd(tm, tn, interpret, res, dy):
    """``dX = dY W[e]^T`` a row tile (the forward kernel on the transposed
    weights), zero in the tiles at or past ``n_valid`` (the kernel never
    writes them); ``dW`` by :func:`_weight_grad`.  What the forward left
    unspecified in the dead tiles therefore reaches neither."""
    x, w, tile_expert, n_valid = res
    dx = _product(dy, jnp.swapaxes(w, 1, 2), tile_expert, n_valid, tm, 0,
                  interpret)
    live = (jnp.arange(x.shape[0], dtype=jnp.int32) // tm) < n_valid[0]
    dx = jnp.where(live[:, None], dx, jnp.zeros((), dx.dtype))
    dw = _weight_grad(x, dy, tile_expert, n_valid, w.shape[0], tm, interpret,
                      w.dtype)
    return dx, dw, None, None


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


@functools.partial(jax.jit, static_argnames=("tm", "tn", "interpret"))
def moe_gmm(x, w, tile_expert, n_valid, *, tm: int, tn: int = 0,
            interpret: bool = False):
    """``x (M, K)`` rows grouped by expert in ``tm``-aligned groups,
    ``w (E, K, N)`` the held experts' weights, ``tile_expert (M // tm,)``
    int32 the expert of each row tile, ``n_valid`` int32 scalar: the
    number of leading row tiles that hold rows.  Returns ``(M, N)`` in
    ``x.dtype``; the rows of tiles at or past ``n_valid`` are
    UNSPECIFIED (never written) — mask them, do not multiply them.

    Differentiable in ``x`` and ``w`` (``jax.custom_vjp``): ``dX`` is this
    kernel on ``w`` transposed, zero in the dead tiles; ``dW[e]`` sums
    ``x_tile^T dy_tile`` over the expert's row tiles in float32 (kernel
    ``moe_gmm_dw``), zero for an expert with no rows."""
    # The custom VJP replaces autodiff's transpose, so a cross-replica
    # gradient reduction has to come from OUTSIDE it: inside ``shard_map``
    # the rows vary over the data axis and the weights do not, and the
    # promotion's own transpose is the psum of ``dW`` over that axis (as
    # ``transformer.vocab_parallel_logits_loss`` does for the fused loss).
    xv, wv = _vma(x), _vma(w)
    for ax in sorted(wv - xv):
        x = pcast_varying(x, ax)
    for ax in sorted(xv - wv):
        w = pcast_varying(w, ax)
    return _gmm(x, w, tile_expert.astype(jnp.int32),
                jnp.asarray(n_valid, jnp.int32).reshape(1), tm, tn, interpret)
