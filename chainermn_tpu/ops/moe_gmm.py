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

The layer's first two products read the same rows and meet in ``silu(gate) *
up``: ``moe_gmm_glu`` is both and the activation in one kernel on this
grid, with a transposed kernel of its own (``moe_gmm_glu_dx``); below.

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
from ..observability import trace as _trace
from .flash_attention import _inherit_vma as _vma

__all__ = ["moe_gmm", "moe_gmm_glu", "moe_gmm_rows", "moe_gmm_sum", "pick_tn"]

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


# --------------------------------------------------------------------------
# the layer's first two products and their activation: ONE grouped kernel
# --------------------------------------------------------------------------
#
# ``hidden = silu(xs @ w_gate[e]) * (xs @ w_up[e])`` a row tile: the gate and
# the up product read the SAME rows, so a live tile is multiplied by both
# weight blocks of its expert in one grid step and the activation is the
# step's epilogue — no element-wise pass over all ``M`` rows beside the
# kernels, which knows nothing of the work list.  Transposed
# (``moe_gmm_glu_dx``) a live tile forms both products' cotangents from
# ``d_hidden`` and writes ONE ``dxs`` tile: no second ``(M, D)`` cotangent,
# no sum of two.  The roundings are the three separate products' and
# autodiff's: each product rounded to the rows' dtype, the activation and
# its derivative in float32, each ``dX`` product rounded and the two added
# in the rows' dtype.

def _glu_tile(rows, wg_ref, wu_ref, dtype):
    """``(g, u, silu(g) * u)`` of one row tile by both weight blocks of its
    expert: each product accumulated in float32 and rounded to ``dtype``,
    the activation in float32 of the ROUNDED products, rounded again — the
    epilogue of ``moe_gmm_glu`` and of a tick's ``moe_gmm_rows``."""
    f32 = jnp.float32
    product = lambda w_ref: jax.lax.dot_general(
        rows, w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=f32).astype(dtype)
    g = product(wg_ref)
    act = jax.nn.silu(g.astype(f32))
    u = product(wu_ref)
    return g, u, (act * u.astype(f32)).astype(dtype)


def _glu_kernel(tile_expert_ref, n_valid_ref, x_ref, wg_ref, wu_ref, o_ref,
                *gu_refs):
    """``gu_refs``: none, or the two rounded products' own results (the
    backward's residuals: the forward under differentiation)."""
    del tile_expert_ref                       # read by the index maps only

    @pl.when(pl.program_id(1) < n_valid_ref[0])
    def _tile():
        g, u, o_ref[...] = _glu_tile(x_ref[...], wg_ref, wu_ref, o_ref.dtype)
        for ref, product in zip(gu_refs, (g, u)):
            ref[...] = product


@functools.partial(jax.jit,
                   static_argnames=("tm", "interpret", "keep_products"))
def _glu_product(x, w_gate, w_up, tile_expert, n_valid, *, tm: int,
                 interpret: bool, keep_products: bool):
    """``hidden (M, F)`` — with ``keep_products`` ``(hidden, g, u)`` — on
    :func:`_product`'s grid: ``(F tiles, row tiles)``, rows fastest, two
    ``(D, tn)`` weight blocks a step."""
    m, d = x.shape
    e, d2, f = w_gate.shape
    assert d == d2 and w_up.shape == w_gate.shape and m % tm == 0, (
        x.shape, w_gate.shape, w_up.shape, tm)
    tn = pick_tn(2 * d, f, w_gate.dtype.itemsize)
    last = _last_live
    weight = pl.BlockSpec((None, d, tn),
                          lambda j, i, te, nv: (te[last(i, nv)], 0, j))
    tile = pl.BlockSpec((tm, tn), lambda j, i, te, nv: (last(i, nv), j))
    n_out = 3 if keep_products else 1
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(f // tn, m // tm),
        in_specs=[pl.BlockSpec((tm, d), lambda j, i, te, nv: (last(i, nv), 0)),
                  weight, weight],
        out_specs=[tile] * n_out)
    out = _sds((m, f), x.dtype, vma=_vma(x, w_gate, w_up))
    # both weight blocks and every row tile twice (Pallas's double
    # buffers), the two float32 products and the activation once
    block_bytes = (4 * d * tn * w_gate.dtype.itemsize
                   + 2 * tm * (d + n_out * tn) * x.dtype.itemsize
                   + 12 * tm * tn)
    got = pl.pallas_call(
        _glu_kernel,
        grid_spec=grid_spec,
        out_shape=[out] * n_out,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=max(block_bytes + (8 << 20), 32 << 20)),
        name="moe_gmm_glu",
        interpret=interpret,
    )(tile_expert, n_valid, x, w_gate, w_up)
    return tuple(got) if keep_products else got[0]


def _glu_dx_kernel(tile_expert_ref, n_valid_ref, dh_ref, g_ref, u_ref, wg_ref,
                   wu_ref, dx_ref, dg_ref, du_ref):
    """A live tile's ``dg = dh * u * silu'(g)`` and ``du = dh * silu(g)`` in
    float32 (``silu``'s own VJP: autodiff's operations in autodiff's order),
    rounded as the products were, and its block of ``dxs = dg @ w_gate[e]^T
    + du @ w_up[e]^T`` — contracted over ``F`` against the weights as they
    lie, each product rounded to the rows' dtype, the two added in it (on
    float32 registers, rounded once more: what an addition in the rows'
    dtype is)."""
    del tile_expert_ref
    f32 = jnp.float32

    @pl.when(pl.program_id(1) < n_valid_ref[0])
    def _tile():
        dh = dh_ref[...].astype(f32)
        g = g_ref[...].astype(f32)
        # (differentiated at a zero offset from ``g``: inside ``shard_map`` a
        # value read from a kernel's reference keeps the operand's varying
        # type and what the kernel computes carries none, which ``jax.vjp``
        # refuses of a cotangent — a float32 ``g`` would be such a value)
        act, silu_vjp = jax.vjp(lambda zero: jax.nn.silu(g + zero),
                                jnp.zeros(g.shape, f32))
        du = (dh * act).astype(du_ref.dtype)
        dg, = silu_vjp(dh * u_ref[...].astype(f32))
        dg = dg.astype(dg_ref.dtype)
        dg_ref[...], du_ref[...] = dg, du
        product = lambda dy, w_ref: jax.lax.dot_general(
            dy, w_ref[...], (((1,), (1,)), ((), ())),
            preferred_element_type=f32).astype(dx_ref.dtype).astype(f32)
        dx_ref[...] = (product(dg, wg_ref)
                       + product(du, wu_ref)).astype(dx_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tm", "interpret"))
def _glu_dx(dh, g, u, w_gate, w_up, tile_expert, n_valid, *, tm: int,
            interpret: bool):
    """``(dxs (M, D), dg (M, F), du (M, F))`` (kernel ``moe_gmm_glu_dx``).
    Grid ``(D tiles, row tiles)``, rows fastest, two ``(td, F)`` weight
    blocks a step — the whole contraction in one block, so no accumulator;
    where ``D`` is tiled a row tile's ``dg``, ``du`` are formed (and written,
    the same) once a ``D`` tile; where it is not (the widths that train),
    they are written over ``g`` and ``u``, which nothing reads after them.
    Tiles at or past ``n_valid`` hold every index and are neither read nor
    written."""
    m, f = dh.shape
    e, d, f2 = w_gate.shape
    assert f == f2 and m % tm == 0, (dh.shape, w_gate.shape, tm)
    td = pick_tn(2 * f, d, w_gate.dtype.itemsize)
    last = _last_live
    rows = pl.BlockSpec((tm, f), lambda j, i, te, nv: (last(i, nv), 0))
    weight = pl.BlockSpec((None, td, f),
                          lambda j, i, te, nv: (te[last(i, nv)], j, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(d // td, m // tm),
        in_specs=[rows, rows, rows, weight, weight],
        out_specs=[pl.BlockSpec((tm, td),
                                lambda j, i, te, nv: (last(i, nv), j)),
                   rows, rows])
    vma = _vma(dh, g, u, w_gate, w_up)
    # both weight blocks and every row tile twice, the float32 forms of the
    # five ``(tm, F)`` tiles and of the two ``(tm, td)`` products once
    block_bytes = (4 * td * f * w_gate.dtype.itemsize
                   + 2 * tm * (5 * f + td) * dh.dtype.itemsize
                   + 4 * tm * (6 * f + 2 * td))
    return pl.pallas_call(
        _glu_dx_kernel,
        grid_spec=grid_spec,
        out_shape=[_sds((m, d), dh.dtype, vma=vma),
                   _sds((m, f), dh.dtype, vma=vma),
                   _sds((m, f), dh.dtype, vma=vma)],
        # operands count the two prefetched scalars
        input_output_aliases={3: 1, 4: 2} if td == d else {},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=max(block_bytes + (8 << 20), 32 << 20)),
        name="moe_gmm_glu_dx",
        interpret=interpret,
    )(tile_expert, n_valid, dh, g, u, w_gate, w_up)


def _count_fused(which: str) -> None:
    """Book one traced staged layer's fused products with the tracer (as
    ``flash/score_blocks_*``: at trace time, off when disabled)."""
    _trace.get_tracer().add_counter(f"moe/glu_products_fused{which}", 1)


# The custom VJP's three rules are traced once a CALL (a layer) and book the
# counter there; the kernels under them are ``jit`` functions, traced and
# lowered once a shape whatever the layers.

@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _glu(x, w_gate, w_up, tile_expert, n_valid, tm, interpret):
    _count_fused("")
    return _glu_product(x, w_gate, w_up, tile_expert, n_valid, tm=tm,
                        interpret=interpret, keep_products=False)


def _glu_fwd(x, w_gate, w_up, tile_expert, n_valid, tm, interpret):
    _count_fused("")
    hidden, g, u = _glu_product(x, w_gate, w_up, tile_expert, n_valid, tm=tm,
                                interpret=interpret, keep_products=True)
    return hidden, (x, w_gate, w_up, g, u, tile_expert, n_valid)


_weight_grad_jit = jax.jit(_weight_grad, static_argnums=(4, 5, 6, 7))


def _glu_bwd(tm, interpret, res, dh):
    """What the forward, ``d_hidden``'s producer and the residuals left
    unspecified in the dead tiles reaches nothing, and ``dxs``, ``dg``,
    ``du`` are UNSPECIFIED there in their turn: the weight gradients skip
    them, and a rows' gather reads back held choices' rows only."""
    x, w_gate, w_up, g, u, tile_expert, n_valid = res
    _count_fused("_vjp")
    dx, dg, du = _glu_dx(dh, g, u, w_gate, w_up, tile_expert, n_valid, tm=tm,
                         interpret=interpret)
    dw = lambda dy, w: _weight_grad_jit(x, dy, tile_expert, n_valid,
                                        w.shape[0], tm, interpret, w.dtype)
    return dx, dw(dg, w_gate), dw(du, w_up), None, None


_glu.defvjp(_glu_fwd, _glu_bwd)


def moe_gmm_glu(x, w_gate, w_up, tile_expert, n_valid, *, tm: int,
                interpret: bool = False):
    """``hidden (M, F)`` in ``x.dtype`` of the grouped rows ``x (M, D)``:
    ``silu(x_tile @ w_gate[e]) * (x_tile @ w_up[e])`` a live row tile, each
    product rounded to ``x.dtype`` first and the activation taken in
    float32 — bit for bit ``moe_gmm``'s two products and the element-wise
    line between them (kernel ``moe_gmm_glu``; its ``N`` tile follows the
    two weight blocks' bytes).  ``tile_expert``, ``n_valid``, ``tm`` as
    :func:`moe_gmm`'s; the rows of tiles at or past ``n_valid`` are
    UNSPECIFIED.

    Differentiable in ``x`` and both weights (``jax.custom_vjp``): the
    forward under differentiation also keeps the two rounded products (live
    tiles only); the kernel ``moe_gmm_glu_dx`` turns ``d_hidden`` into the
    two products' cotangents and ONE ``dx = dg @ w_gate[e]^T + du @
    w_up[e]^T`` (each product rounded, the two added in ``x.dtype``, as
    two ``moe_gmm`` transposes and autodiff's sum would), and ``moe_gmm_dw``
    takes the weight gradients.  Unlike ``moe_gmm``'s, this ``dx`` is
    UNSPECIFIED in the dead tiles too — never written, never to be read: a
    padding row INSIDE a live tile is the caller's (its ``d_hidden`` row
    has to be zero).  Books ``moe/glu_products_fused`` (and ``…_vjp`` in the
    backward) with the tracer, once a traced call — which is why the
    ``jit`` that wraps its siblings whole wraps the kernels alone here: its
    cache would make that once a shape."""
    # (the weights' promotion outside the custom VJP: ``moe_gmm`` says why)
    x, w_gate, w_up = _vary_alike(x, w_gate, w_up)
    return _glu(x, w_gate, w_up, tile_expert.astype(jnp.int32),
                jnp.asarray(n_valid, jnp.int32).reshape(1), tm, interpret)


# --------------------------------------------------------------------------
# the layer at a tick's sizes: rows taken and summed INSIDE the products
# --------------------------------------------------------------------------
#
# Where the layer's tokens are few enough that ``x (T, D)`` and the float32
# result ``y (T, D)`` stay in a kernel's fast memory (``parallel/moe.py::
# _rows_resident``: a served tick), the rows' gather is the prologue of the
# first two products and the gated sum the epilogue of the third:
#
# * ``moe_gmm_rows``: ``hidden[tile t] = silu(x[row_token] @ w_gate[e]) *
#   (x[row_token] @ w_up[e])`` — ``x`` is ONE block (constant index map: read
#   once a call, widened once to a float32 scratch whose rows a dynamic index
#   can name), a live tile copies the rows of its tokens (``row_token`` from
#   scalar memory; a padding row names no token, ``T``, and stays zero) and
#   multiplies by BOTH weight blocks of its expert.  No ``(M, D)`` buffer.
# * ``moe_gmm_sum``: ``y[row_token[r]] += f32(hidden[r] @ w_down[e]) *
#   row_gate[r]`` over the live tiles' real rows — the ``(T, tn)`` block of
#   ``y`` stays resident over the row tiles (the fast grid axis), zeroed at
#   the first.  No ``(M, D)`` result rows, no gather a choice.
#
# The roundings are the three separate products': bfloat16 products with
# float32 accumulation, each cast to the rows' dtype, a float32 gate multiply
# and a float32 sum — a token's held rows are added by EXPERT (it has at most
# one row a tile), where the gather-combine adds them by choice.  A row only
# ever reaches its own token: nothing multiplies another row by zero.  Both
# are forward kernels; the layer differentiates through the staged path.

def _rows_kernel(tile_expert_ref, n_valid_ref, row_token_ref, x_ref, wg_ref,
                 wu_ref, o_ref, wide_ref, rows_ref, *, tm: int):
    del tile_expert_ref                       # read by the index maps only
    f32 = jnp.float32
    i = pl.program_id(1)

    @pl.when(jnp.logical_and(pl.program_id(0) == 0, i == 0))
    def _widen():
        wide_ref[...] = x_ref[...].astype(f32)

    @pl.when(i < n_valid_ref[0])
    def _tile():
        rows_ref[...] = jnp.zeros_like(rows_ref)

        def take(r, carry):
            token = row_token_ref[i * tm + r]

            @pl.when(token < x_ref.shape[0])
            def _row():
                rows_ref[pl.ds(r, 1), :] = wide_ref[pl.ds(token, 1), :]

            return carry

        jax.lax.fori_loop(0, tm, take, 0)
        o_ref[...] = _glu_tile(rows_ref[...].astype(x_ref.dtype), wg_ref,
                               wu_ref, o_ref.dtype)[2]


def _sum_kernel(tile_expert_ref, n_valid_ref, row_token_ref, row_gate_ref,
                h_ref, w_ref, y_ref, rows_ref, *, tm: int):
    del tile_expert_ref
    f32 = jnp.float32
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _zero():
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(i < n_valid_ref[0])
    def _tile():
        rows_ref[...] = jax.lax.dot_general(
            h_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=f32).astype(h_ref.dtype).astype(f32)

        def add(r, carry):
            token = row_token_ref[i * tm + r]

            @pl.when(token < y_ref.shape[0])
            def _row():
                y_ref[pl.ds(token, 1), :] += (rows_ref[pl.ds(r, 1), :]
                                              * row_gate_ref[i * tm + r])

            return carry

        jax.lax.fori_loop(0, tm, add, 0)


def _row_scalars(tile_expert, n_valid, row_token):
    return (tile_expert.astype(jnp.int32),
            jnp.asarray(n_valid, jnp.int32).reshape(1),
            row_token.astype(jnp.int32))


def _vary_alike(*arrays):
    """``arrays``, each varying over every mesh axis any of them varies
    over (inside ``shard_map`` the rows vary and the weights do not)."""
    union = _vma(*arrays)
    out = []
    for a in arrays:
        for ax in sorted(union - _vma(a)):
            a = pcast_varying(a, ax)
        out.append(a)
    return out


@functools.partial(jax.jit, static_argnames=("tm", "interpret"))
def moe_gmm_rows(x, w_gate, w_up, row_token, tile_expert, n_valid, *,
                 tm: int, interpret: bool = False):
    """``hidden (M, F)`` in ``x.dtype`` of ``x (T, D)`` whole: row ``r`` of a
    live tile is ``silu(x[row_token[r]] @ w_gate[e]) * (x[row_token[r]] @
    w_up[e])`` for the tile's expert ``e``, each product rounded to
    ``x.dtype`` first; a row whose ``row_token`` is ``T`` (padding) is zero.
    ``row_token (M,)``, ``tile_expert (M // tm,)`` and ``n_valid`` as
    :func:`moe_gmm`'s; the rows of tiles at or past ``n_valid`` are
    UNSPECIFIED.  Forward only."""
    x, w_gate, w_up = _vary_alike(x, w_gate, w_up)
    t, d = x.shape
    e, d2, f = w_gate.shape
    m = row_token.shape[0]
    assert d == d2 and w_up.shape == w_gate.shape and m % tm == 0, (
        x.shape, w_gate.shape, w_up.shape, m, tm)
    tn = pick_tn(d, f, w_gate.dtype.itemsize)
    last = _last_live
    weight = pl.BlockSpec((None, d, tn),
                          lambda j, i, te, nv, rt: (te[last(i, nv)], 0, j))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3, grid=(f // tn, m // tm),
        in_specs=[pl.BlockSpec((t, d), lambda j, i, te, nv, rt: (0, 0)),
                  weight, weight],
        out_specs=pl.BlockSpec((tm, tn),
                               lambda j, i, te, nv, rt: (last(i, nv), j)),
        scratch_shapes=[pltpu.VMEM((t, d), jnp.float32),
                        pltpu.VMEM((tm, d), jnp.float32)])
    block_bytes = (4 * d * tn * w_gate.dtype.itemsize
                   + t * d * (4 + 2 * x.dtype.itemsize) + 4 * tm * d
                   + 2 * tm * tn * x.dtype.itemsize)
    return pl.pallas_call(
        functools.partial(_rows_kernel, tm=tm),
        grid_spec=grid_spec,
        out_shape=_sds((m, f), x.dtype, vma=_vma(x, w_gate, w_up)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=max(block_bytes + (8 << 20), 32 << 20)),
        name="moe_gmm_rows",
        interpret=interpret,
    )(*_row_scalars(tile_expert, n_valid, row_token), x, w_gate, w_up)


@functools.partial(jax.jit, static_argnames=("n_tokens", "tm", "interpret"))
def moe_gmm_sum(hidden, w_down, row_gate, row_token, tile_expert, n_valid, *,
                n_tokens: int, tm: int, interpret: bool = False):
    """``y (n_tokens, D)`` float32: ``y[row_token[r]] += f32(hidden[r] @
    w_down[e]) * row_gate[r]`` over the rows ``r`` of the live tiles whose
    ``row_token`` is a token's (under ``n_tokens``), the product rounded to
    ``hidden.dtype`` first, the tiles in order; zeros where no row names a
    token, and everywhere with ``n_valid == 0``.  The rows of dead tiles
    (``hidden``'s unwritten ones) and the padding rows are never read into
    ``y``.  ``row_gate (M,)`` float32.  Forward only."""
    hidden, w_down = _vary_alike(hidden, w_down)
    m, f = hidden.shape
    e, f2, d = w_down.shape
    assert f == f2 and m % tm == 0, (hidden.shape, w_down.shape, tm)
    tn = pick_tn(f, d, w_down.dtype.itemsize)
    last = _last_live
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4, grid=(d // tn, m // tm),
        in_specs=[
            pl.BlockSpec((tm, f),
                         lambda j, i, te, nv, rt, rg: (last(i, nv), 0)),
            pl.BlockSpec((None, f, tn),
                         lambda j, i, te, nv, rt, rg: (te[last(i, nv)], 0, j)),
        ],
        out_specs=pl.BlockSpec((n_tokens, tn),
                               lambda j, i, te, nv, rt, rg: (0, j)),
        scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)])
    block_bytes = (2 * f * tn * w_down.dtype.itemsize
                   + 2 * tm * f * hidden.dtype.itemsize
                   + 4 * tn * (2 * n_tokens + tm))
    return pl.pallas_call(
        functools.partial(_sum_kernel, tm=tm),
        grid_spec=grid_spec,
        out_shape=_sds((n_tokens, d), jnp.float32, vma=_vma(hidden, w_down)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=max(block_bytes + (8 << 20), 32 << 20)),
        name="moe_gmm_sum",
        interpret=interpret,
    )(*_row_scalars(tile_expert, n_valid, row_token),
      row_gate.astype(jnp.float32), hidden, w_down)
