"""Pallas backward kernels for 3x3/1x1 stride-1 convolutions (TPU).

**Status: a measured NEGATIVE result — opt-in, not the default.**  See
docs/PERF.md "Conv backward: why the Pallas kernels lost".  The kernels
are parity-exact and compile inside the full sharded train step, but lose
to XLA's native conv engine at every ResNet shape (2x at 14x14x256 up to
~30x at 56x56x64; NF-ResNet-50 end-to-end 119.6 vs 40.6 ms/step,
docs/PERF.md).  Two findings worth the price of the experiment:

1. XLA's backward convs already run AT the HBM-roofline floor in
   wall-clock (56x56x64 dgrad: 0.12 ms measured vs 0.126 ms floor).  The
   "1.7-2.6x floor" excess that motivated this module came from XLA's
   ``bytes accessed`` cost analysis, which counts lane-padded logical
   bytes, not HBM traffic — the metric, not the lowering, carried the
   slack.  docs/PERF.md's round-4 "custom kernels worth ~41 -> ~25 ms"
   projection inherited that artifact and is withdrawn there.
2. A shifted-matmul (roll+mask) conv decomposition is VPU-bound on TPU:
   every tap pays ~2 full VMEM passes (rotate + mask/cast) over the
   activation plane, which exceeds the MXU cost of the tap's MACs at
   ResNet channel counts.  XLA's conv engine applies the 9 taps in
   registers while the plane streams once — a thing jnp-level kernel code
   cannot express.  Custom conv kernels on TPU need the conv unit's
   register-level reuse, not data-movement decompositions.

Design notes (kept for the record; the machinery is reused verbatim by
any future windowed kernel):

* ResNet bottleneck planes are small (56x56x64 bf16 = 401 KB ... 7x7x512 =
  50 KB), so a kernel instance holds the ENTIRE spatial extent of a few
  images in VMEM (~16 MB/core) and grids only over batch.  Each X / dY
  element is read from HBM exactly once; accumulation happens on-chip in
  fp32.  HBM traffic = the analytic floor.
* A 3x3/pad-1 conv is 9 shifted matmuls.  Mosaic cannot reshape or
  multi-dim-contract odd-sized slices (55x55 blocks fail layout
  inference), so the shift is done on a FLATTENED spatial axis: inputs
  arrive as (bn, H*W, C) and the tap shift (dh, dw) becomes one
  ``pltpu.roll`` by ``dh*W + dw`` along the second-minor dim, plus an
  iota-derived border mask.  Rolls only support 32-bit data, so the
  rolled operand upcasts to fp32 in VMEM (VPU work, no HBM bytes) and
  drops back to bf16 for the MXU dot:

      dW[kh,kw] = (roll(X) * mask)^T dY            contraction over bn*H*W
      dX       += (roll(dY) * mask) W[kh,kw]^T     9 taps, fp32 scratch

* Forward stays on XLA's conv (measured within ~1.2x of ITS floor);
  ``conv2d`` only swaps the VJP, and falls back to XLA's transpose rule
  for shapes the kernels don't cover — behavior never gates on coverage.

Parity: tests/test_conv_backward.py (interpret mode, any host) and
chip_smoke.py's kernel-parity phase (compiled, on the chip).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax

import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .._compat import pcast_varying as _pcast_varying
from .._compat import shape_dtype_struct as _sds
from .._compat import tpu_compiler_params as _tpu_compiler_params

__all__ = ["conv2d", "conv3x3_dgrad", "conv3x3_wgrad"]

_VMEM_BUDGET = 5 * 1024 * 1024  # headroom under the 16 MB/core scoped
# limit: the pipeline double-buffers input/output blocks, and Mosaic's
# stack holds the rolled fp32 copy, its border mask and the bf16 cast LIVE
# simultaneously with inputs and the accumulator — so the per-image
# estimates below charge ~16 bytes/pixel for the rolled operand
# (2 in + 4 cast + 4 roll + 4 mask + 2 re-cast), not its nominal 2, and
# the budget is set to ~half of a conservative target.  bn=1 on the 56x56
# stage still gives >3000 contraction rows per dot — MXU-efficient.


def _inherit_vma(*xs) -> frozenset:
    """Union of the inputs' varying-mesh-axes sets — pallas_call inside
    shard_map requires out_shapes to declare how outputs vary (same helper
    as ops/flash_attention.py)."""
    vma = set()
    for x in xs:
        v = getattr(getattr(x, "aval", None), "vma", None)
        if v:
            vma |= set(v)
    return frozenset(vma)


def _promote_vma(x, vma: frozenset):
    """Promote ``x`` to vary over ``vma`` (no-op outside shard_map).

    Interpret mode executes the kernel body as plain jnp under the
    shard_map trace, where a dot between a batch-sharded dy and a
    replicated w fails VMA agreement — promote the lagging operand first
    (compiled Mosaic never sees vma, so this is interpret-only in
    practice but harmless everywhere)."""
    have = getattr(getattr(x, "aval", None), "vma", frozenset()) or frozenset()
    missing = tuple(sorted(set(vma) - set(have)))
    if not missing:
        return x
    return _pcast_varying(x, missing)


def _same_pad(h: int, k: int, s: int) -> Tuple[int, int]:
    """XLA SAME padding (lo, hi) for one spatial dim."""
    out = -(-h // s)
    total = max((out - 1) * s + k - h, 0)
    return total // 2, total - total // 2


def _pick_bn(n: int, per_image_bytes: int, fixed_bytes: int) -> int:
    """Images per grid step: as many as fit the VMEM budget, dividing n."""
    room = max(_VMEM_BUDGET - fixed_bytes, per_image_bytes)
    bn = max(1, min(n, room // per_image_bytes))
    while n % bn:
        bn -= 1
    return bn


def _pad_rows(v, sp):
    """Zero-pad the flattened-spatial dim (axis 1) up to ``sp`` rows inside
    VMEM.  ``tpu.dynamic_rotate`` and leading-dim reshapes need the
    second-minor dim sublane-aligned (multiple of 8); 14x14 planes (196
    rows) are not.  Zero rows are inert in every dot below, and the border
    masks plus prefix stores keep them out of real outputs."""
    if v.shape[1] == sp:
        return v
    z = jnp.zeros((v.shape[0], sp - v.shape[1], v.shape[2]), v.dtype)
    return jnp.concatenate([v, z], axis=1)


def _rolled(v32, ww, dh, dw, flip):
    """Roll ``v32`` (fp32, flattened spatial) by tap shift (dh, dw).

    ``dh``/``dw`` may be traced scalars: the taps run under a fori_loop so
    only ONE tap's roll temporaries are ever live — a Python-unrolled tap
    loop let Mosaic schedule all 9 rolled copies concurrently and blew the
    16 MB scoped-VMEM stack.  The roll lowers to ``tpu.dynamic_rotate``
    either way, so the traced shift costs nothing.  Border masking is the
    caller's job (``_tap_mask``, applied after the bf16 downcast)."""
    rows = v32.shape[1]  # the PADDED extent — rolls wrap at the array edge
    sh = dh * ww + dw
    if flip:
        sh = -sh
    return pltpu.roll(v32, (rows - sh) % rows, 1)  # out[s] = v[s + sh]


def _make_hw(sp, ww):
    """(h, w) plane coordinates of each flattened row, shaped (1, sp, 1).

    Built ONCE per kernel invocation and shared by every tap: full-shape
    per-tap iotas and fp32 masks were the dominant VMEM transients (three
    (bn, sp, C) i32 iotas + an fp32 mask per tap blew the 16 MB scoped
    stack on the 56x56 stage)."""
    s = jax.lax.broadcasted_iota(jnp.int32, (1, sp, 1), 1)
    return s // ww, s % ww


def _tap_mask(h, w, hh, ww, dh, dw, flip, dtype):
    """(1, sp, 1) border mask for tap shift (dh, dw), in the DOT dtype so
    the multiply runs on the bf16 operand after the downcast."""
    if flip:
        dh, dw = -dh, -dw
    cond = ((h + dh >= 0) & (h + dh < hh)
            & (w + dw >= 0) & (w + dw < ww))
    return cond.astype(dtype)


# ---------------------------------------------------------------------------
# wgrad: dW[kh, kw, ci, co] = sum_{n, oh, ow} X[n, oh+dh, ow+dw, ci]
#                                             * dY[n, oh, ow, co]
# ---------------------------------------------------------------------------


def _wgrad_kernel(x_ref, dy_ref, dw_ref, *scratch, hh, ww, k, pad, ni):
    """Grid is (batch-blocks, k*k): ONE tap per grid cell.

    A fori_loop over taps inside one cell left all 9 rolled fp32 copies
    and masked casts live simultaneously (~16.9 MB scoped stack on the
    56x56 stage, over the 16 MB limit).  Grid cells are sequential by
    construction, so per-tap temporaries now peak at one tap's worth;
    inputs keep constant block indices across the k*k inner cells (fetched
    once per batch block) and dW accumulates in scratch, written to HBM
    exactly once at the final cell."""
    i, t = pl.program_id(0), pl.program_id(1)
    sp = -(-hh * ww // 8) * 8  # sublane-aligned flattened-spatial extent
    dy = _pad_rows(dy_ref[...], sp)
    dyf = dy.reshape(-1, dy.shape[-1])

    if k == 1:  # tapless: one floor-traffic matmul, no roll/mask/cast
        @pl.when(i == 0)
        def _init1():
            dw_ref[...] = jnp.zeros(dw_ref.shape, dw_ref.dtype)
        dw_ref[0] += jax.lax.dot_general(
            _pad_rows(x_ref[...], sp).reshape(-1, x_ref.shape[-1]), dyf,
            (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        return

    xbuf, dwacc = scratch

    @pl.when(t == 0)
    def _load():
        xbuf[...] = _pad_rows(x_ref[...].astype(jnp.float32), sp)

    @pl.when(jnp.logical_and(i == 0, t == 0))
    def _zero():
        dwacc[...] = jnp.zeros(dwacc.shape, dwacc.dtype)

    kh, kw = t // k, t % k
    dh, dw = kh - pad, kw - pad
    hs, ws = _make_hw(sp, ww)
    xs = (_rolled(xbuf[...], ww, dh, dw, flip=False).astype(dy.dtype)
          * _tap_mask(hs, ws, hh, ww, dh, dw, False, dy.dtype))
    part = jax.lax.dot_general(
        xs.reshape(-1, xs.shape[-1]), dyf,
        (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    dwacc[pl.dslice(t, 1)] += part[None]

    @pl.when(jnp.logical_and(i == ni - 1, t == k * k - 1))
    def _flush():
        dw_ref[...] = dwacc[...]


def conv3x3_wgrad(x, dy, stride: int = 1, *, ksize: int = 3,
                  interpret: bool = False):
    """dW for a kxk (k in {1, 3}) SAME stride-1 conv, NHWC/HWIO, at the
    HBM floor."""
    assert stride == 1, "stride-2 wgrad stays on XLA (see module docstring)"
    n, h, w, ci = x.shape
    co = dy.shape[-1]
    pad = _same_pad(h, ksize, 1)[0]
    # x: 2B in (x2 double-buffer) + fp32 cast/roll/mask/re-cast transients
    # when k>1; dy: 2B in (x2 double-buffer)
    per_img = h * w * (ci * (18 if ksize > 1 else 4) + co * 4)
    bn = _pick_bn(n, per_img, ksize * ksize * ci * co * 4)
    sp = -(-h * w // 8) * 8
    vma = _inherit_vma(x, dy)
    kernel = functools.partial(_wgrad_kernel, hh=h, ww=w, k=ksize, pad=pad,
                               ni=n // bn)
    dw = pl.pallas_call(
        kernel,
        grid=(n // bn, ksize * ksize),
        in_specs=[
            pl.BlockSpec((bn, h * w, ci), lambda i, t: (i, 0, 0)),
            pl.BlockSpec((bn, h * w, co), lambda i, t: (i, 0, 0)),
        ],
        scratch_shapes=([pltpu.VMEM((bn, sp, ci), jnp.float32),
                         pltpu.VMEM((ksize * ksize, ci, co), jnp.float32)]
                        if ksize > 1 else []),
        out_specs=pl.BlockSpec((ksize * ksize, ci, co),
                               lambda i, t: (0, 0, 0)),
        out_shape=_sds((ksize * ksize, ci, co), jnp.float32,
                                       vma=vma),
        compiler_params=_tpu_compiler_params(
            dimension_semantics=("arbitrary", "arbitrary")),
        name="conv_bwd_dw",
        interpret=interpret,
    )(_promote_vma(x.reshape(n, h * w, ci), vma),
      _promote_vma(dy.reshape(n, h * w, co), vma))
    return dw.reshape(ksize, ksize, ci, co).astype(x.dtype)


# ---------------------------------------------------------------------------
# dgrad: dX[n, h, w, ci] = sum_{kh, kw} dY[n, h-dh, w-dw, co] W[kh, kw, ci, co]
# ---------------------------------------------------------------------------


def _dgrad_kernel(dy_ref, w_ref, dx_ref, *scratch, hh, ww, k, pad):
    """Grid is (batch-blocks, k*k): one tap per cell — see _wgrad_kernel
    for why the tap loop lives in the grid and not a fori_loop."""
    if k == 1:  # tapless: one floor-traffic matmul
        dx_ref[...] = jax.lax.dot_general(
            dy_ref[...], w_ref[0], (((2,), (1,)), ((), ())),
            preferred_element_type=jnp.float32).astype(dx_ref.dtype)
        return

    t = pl.program_id(1)
    size = hh * ww
    sp = -(-size // 8) * 8  # sublane-aligned; acc is allocated at sp rows
    acc, dybuf = scratch

    @pl.when(t == 0)
    def _load():
        acc[...] = jnp.zeros(acc.shape, acc.dtype)
        dybuf[...] = _pad_rows(dy_ref[...].astype(jnp.float32), sp)

    kh, kw = t // k, t % k
    dh, dw = kh - pad, kw - pad
    wv = w_ref[pl.dslice(t, 1)][0]
    hs, ws = _make_hw(sp, ww)
    dys = (_rolled(dybuf[...], ww, dh, dw, flip=True).astype(wv.dtype)
           * _tap_mask(hs, ws, hh, ww, dh, dw, True, wv.dtype))
    acc[...] += jax.lax.dot_general(
        dys, wv, (((2,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(t == k * k - 1)
    def _flush():
        dx_ref[...] = acc[:, :size, :].astype(dx_ref.dtype)


def conv3x3_dgrad(dy, w, xshape, stride: int = 1, *,
                  interpret: bool = False):
    """dX for a kxk (k in {1, 3}) SAME stride-1 conv, NHWC/HWIO, at the
    HBM floor."""
    assert stride == 1, "stride-2 dgrad stays on XLA (see module docstring)"
    n, h, ww_, ci = xshape
    co = dy.shape[-1]
    k = w.shape[0]
    pad = _same_pad(h, k, 1)[0]
    # dy: 2B in (x2 double-buffer) + fp32 cast/roll/mask/re-cast transients
    # when k>1; out: 2B (x2 double-buffer) + fp32 acc scratch
    per_img = h * ww_ * (co * (18 if k > 1 else 4) + ci * 8)
    bn = _pick_bn(n, per_img, k * k * ci * co * w.dtype.itemsize)
    kernel = functools.partial(_dgrad_kernel, hh=h, ww=ww_, k=k, pad=pad)
    sp = -(-h * ww_ // 8) * 8
    vma = _inherit_vma(dy, w)
    dx = pl.pallas_call(
        kernel,
        grid=(n // bn, k * k),
        in_specs=[
            pl.BlockSpec((bn, h * ww_, co), lambda i, t: (i, 0, 0)),
            pl.BlockSpec((k * k, ci, co), lambda i, t: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((bn, h * ww_, ci), lambda i, t: (i, 0, 0)),
        out_shape=_sds((n, h * ww_, ci), dy.dtype,
                                       vma=vma),
        scratch_shapes=([pltpu.VMEM((bn, sp, ci), jnp.float32),
                         pltpu.VMEM((bn, sp, co), jnp.float32)]
                        if k > 1 else []),
        compiler_params=_tpu_compiler_params(
            dimension_semantics=("arbitrary", "arbitrary")),
        name="conv_bwd_dx",
        interpret=interpret,
    )(_promote_vma(dy.reshape(n, h * ww_, co), vma),
      _promote_vma(w.reshape(k * k, ci, co), vma))
    return dx.reshape(xshape)


# ---------------------------------------------------------------------------
# Drop-in conv with the Pallas VJP
# ---------------------------------------------------------------------------


def _xla_conv(x, w, stride):
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _eligible(xshape, wshape, stride) -> bool:
    """Shapes where the floor-traffic kernels beat XLA (probe-measured).

    Small planes (7x7) are excluded: their contraction runs are too short
    to load the MXU, the 512-channel fp32 dW accumulator dominates VMEM,
    and XLA is already within 1.7x of floor on tiny absolute bytes there."""
    kh, kw = wshape[:2]
    if (kh, kw) not in ((3, 3), (1, 1)) or stride != 1:
        return False
    h, w = xshape[1], xshape[2]
    return h * w >= 196


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def conv2d(x, w, stride: int = 1, interpret: bool = None):
    """SAME-padded NHWC conv; XLA forward, Pallas 3x3/1x1-s1 backward.

    Falls back to XLA's own transpose rule for shapes outside the kernels'
    envelope, so it is safe as a universal replacement.  ``interpret=None``
    auto-selects: compiled Pallas on TPU, the XLA transpose rule elsewhere
    (identical math; interpret-mode Pallas under shard_map trips VMA
    agreement on the kernel's dynamic index scalars, and is far slower
    than XLA on CPU anyway).  ``interpret=True`` forces interpret-mode
    kernels — the parity tests' oracle-vs-kernel mode, outside shard_map.
    """
    return _xla_conv(x, w, stride)


def _conv2d_fwd(x, w, stride, interpret):
    return _xla_conv(x, w, stride), (x, w)


def _conv2d_bwd(stride, interpret, res, dy):
    x, w = res
    if interpret is None and jax.default_backend() != "tpu":
        interpret = "xla"  # auto: off-TPU, the XLA transpose rule
    if interpret == "xla" or not _eligible(x.shape, w.shape, stride):
        _, vjp = jax.vjp(lambda x, w: _xla_conv(x, w, stride), x, w)
        return vjp(dy)
    dx = conv3x3_dgrad(dy, w, x.shape, stride, interpret=bool(interpret))
    dw = conv3x3_wgrad(x, dy, stride, ksize=w.shape[0],
                       interpret=bool(interpret))
    return dx, dw.astype(w.dtype)


conv2d.defvjp(_conv2d_fwd, _conv2d_bwd)
