"""ResNet family (v1.5) for the ImageNet DP benchmark.

Reference parity: ``examples/imagenet/models/resnet50.py`` [uv]
(SURVEY.md §2.9) — the headline data-parallel workload (BASELINE configs
#2/#4 use ResNet-50/152).

TPU-first design: convs and matmuls run in bfloat16 (MXU-native), while
parameters, BatchNorm statistics and the softmax/loss stay float32 for
numerical stability — the TPU analog of the reference's
``allreduce_grad_dtype=float16`` compute/compress split.  Shapes are NHWC
(XLA:TPU's preferred conv layout).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from chainermn_tpu.ops import conv_backward

ModuleDef = Any


class Affine(nn.Module):
    """Per-channel scale+shift — the zero-extra-pass floor for norm traffic.

    A pure elementwise epilogue XLA fuses into the producing conv, so a
    network built on it pays NO activation passes for normalization.  Used
    (a) as the probe that bounds how much of ResNet's HBM traffic BatchNorm
    costs (docs/PERF.md roofline) and (b) as the apply-side of the
    stale-stats BN below."""

    dtype: Any = jnp.bfloat16
    scale_init: Callable = nn.initializers.ones

    @nn.compact
    def __call__(self, x):
        c = x.shape[-1]
        scale = self.param("scale", self.scale_init, (c,), jnp.float32)
        bias = self.param("bias", nn.initializers.zeros, (c,), jnp.float32)
        return (x.astype(jnp.float32) * scale + bias).astype(self.dtype)


class StaleBatchNorm(nn.Module):
    """BatchNorm normalizing with the PREVIOUS step's batch statistics.

    Standard training BN cannot normalize until the CURRENT batch's
    mean/var exist, which forces the conv output through HBM extra times
    (a stats read plus a normalize read+write) — 8.4 GB of ResNet-50's
    44 GB/step on v5e (docs/PERF.md roofline, "the BN traffic
    measured").  Normalizing with statistics that are
    CONSTANTS at this step makes the apply side a per-channel affine — a
    pure elementwise epilogue XLA fuses into the producing conv — and
    the current batch's stats reduction fuses too (measured: within 2%
    of the zero-norm floor).  The statistics used are exactly one step
    stale: the previous step's batch mean/var.  Same 1-step-stale trade
    as the double-buffered allreduce (SURVEY.md §6 v1.2): semantics
    documented, opt-in.

    Eval uses the slow EMA (``mean``/``var``) exactly like
    ``nn.BatchNorm``; ``last_mean``/``last_var`` carry the one-step
    pipeline.  Flax auto-names the module path by class (``BatchNorm_0``
    vs ``StaleBatchNorm_0``), so converting a checkpoint between norms
    needs a module-name rename map — it is not drop-in.
    """

    train: bool = True
    momentum: float = 0.9
    epsilon: float = 1e-5
    dtype: Any = jnp.bfloat16
    scale_init: Callable = nn.initializers.ones

    @nn.compact
    def __call__(self, x):
        c = x.shape[-1]
        scale = self.param("scale", self.scale_init, (c,), jnp.float32)
        bias = self.param("bias", nn.initializers.zeros, (c,), jnp.float32)
        # Two stat pairs.  mean/var: the slow EMA, used in EVAL exactly like
        # nn.BatchNorm's running stats.  last_mean/last_var: the PREVIOUS
        # step's batch statistics, used to normalize in TRAIN — exactly one
        # step stale, no EMA lag.  An early variant normalized with the EMA
        # itself and destabilized (loss re-inflated after step ~50): the EMA
        # lags the drifting activations by ~momentum/(1-momentum) steps and
        # the feedback loop compounds.  The 1-step variant diverges even
        # faster at lr 0.05 (docs/evidence_stalebn_divergence.json) — this
        # module is a PERF PROBE, not a training path; nf_resnet50 is the
        # shipped BN-free alternative (docs/PERF.md "Round 4").
        ra_mean = self.variable("batch_stats", "mean",
                                lambda: jnp.zeros((c,), jnp.float32))
        ra_var = self.variable("batch_stats", "var",
                               lambda: jnp.ones((c,), jnp.float32))
        last_mean = self.variable("batch_stats", "last_mean",
                                  lambda: jnp.zeros((c,), jnp.float32))
        last_var = self.variable("batch_stats", "last_var",
                                 lambda: jnp.ones((c,), jnp.float32))
        if self.train and not self.is_initializing():
            m, v = last_mean.value, last_var.value  # STALE: read before update
            xf = x.astype(jnp.float32)
            axes = tuple(range(x.ndim - 1))
            bmean = jnp.mean(xf, axes)
            bvar = jnp.mean(jnp.square(xf), axes) - jnp.square(bmean)
            ra_mean.value = (self.momentum * ra_mean.value
                             + (1 - self.momentum) * bmean)
            ra_var.value = (self.momentum * ra_var.value
                            + (1 - self.momentum) * bvar)
            last_mean.value, last_var.value = bmean, bvar
        else:
            m, v = ra_mean.value, ra_var.value  # eval: EMA, like BatchNorm
        inv = scale / jnp.sqrt(v + self.epsilon)
        y = (x.astype(jnp.float32) - m) * inv + bias
        return y.astype(self.dtype)


def make_norm(norm: str, train: bool, dtype):
    """Factory for the block norm layer: 'bn' (reference-parity BatchNorm),
    'affine' (per-channel scale+shift, the fusion floor), 'stalebn'
    (BN with one-step-stale statistics — see StaleBatchNorm)."""
    if norm == "bn":
        return partial(nn.BatchNorm, use_running_average=not train,
                       momentum=0.9, epsilon=1e-5, dtype=dtype)
    if norm == "affine":
        return partial(Affine, dtype=dtype)
    if norm == "stalebn":
        return partial(StaleBatchNorm, train=train, momentum=0.9,
                       epsilon=1e-5, dtype=dtype)
    raise ValueError(f"unknown norm {norm!r}")


class PallasConv(nn.Module):
    """nn.Conv(use_bias=False) stand-in whose VJP runs the Pallas 3x3
    backward kernels (ops/conv_backward.py).  Same param name ("kernel"),
    shape (kh, kw, cin, features) and default init as nn.Conv, so
    checkpoints are interchangeable with the XLA path when call sites pin
    the module name."""

    features: int
    kernel: Tuple[int, int] = (3, 3)
    strides: Any = 1
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        kh, kw = self.kernel
        s = self.strides[0] if isinstance(self.strides, tuple) else self.strides
        w = self.param("kernel", nn.initializers.lecun_normal(),
                       (kh, kw, x.shape[-1], self.features), jnp.float32)
        return conv_backward.conv2d(x.astype(self.dtype),
                                    w.astype(self.dtype), s)


def _conv3x3_factory(conv_impl: str, dtype):
    """The 3x3 conv used inside blocks: XLA end to end, or XLA forward with
    the Pallas traffic-floor backward (conv_impl='pallas')."""
    if conv_impl == "pallas":
        return partial(PallasConv, dtype=dtype)
    return partial(nn.Conv, use_bias=False, dtype=dtype)


class BasicBlock(nn.Module):
    filters: int
    strides: int = 1
    dtype: Any = jnp.bfloat16
    norm: str = "bn"
    conv_impl: str = "xla"

    @nn.compact
    def __call__(self, x, train: bool = True):
        norm = make_norm(self.norm, train, self.dtype)
        conv = partial(nn.Conv, use_bias=False, dtype=self.dtype)
        conv3 = _conv3x3_factory(self.conv_impl, self.dtype)
        residual = x
        y = conv3(self.filters, (3, 3), strides=(self.strides, self.strides),
                  name="Conv_0")(x)
        y = nn.relu(norm()(y))
        y = conv3(self.filters, (3, 3), name="Conv_1")(y)
        # zero-init the last BN scale so each block starts as identity —
        # standard large-batch ResNet trick (Goyal et al.), matters at the
        # batch sizes DP scaling targets
        y = norm(scale_init=nn.initializers.zeros)(y)
        if residual.shape != y.shape:
            residual = conv(self.filters, (1, 1),
                            strides=(self.strides, self.strides),
                            name="conv_proj")(residual)
            residual = norm(name="norm_proj")(residual)
        return nn.relu(residual + y)


class BottleneckBlock(nn.Module):
    filters: int
    strides: int = 1
    dtype: Any = jnp.bfloat16
    norm: str = "bn"
    conv_impl: str = "xla"

    @nn.compact
    def __call__(self, x, train: bool = True):
        norm = make_norm(self.norm, train, self.dtype)
        conv = partial(nn.Conv, use_bias=False, dtype=self.dtype)
        conv3 = _conv3x3_factory(self.conv_impl, self.dtype)
        residual = x
        y = nn.relu(norm()(conv(self.filters, (1, 1), name="Conv_0")(x)))
        # v1.5: stride lives on the 3x3, not the 1x1
        y = nn.relu(norm()(conv3(self.filters, (3, 3),
                                 strides=(self.strides, self.strides),
                                 name="Conv_1")(y)))
        y = norm(scale_init=nn.initializers.zeros)(
            conv(self.filters * 4, (1, 1), name="Conv_2")(y))
        if residual.shape != y.shape:
            residual = conv(self.filters * 4, (1, 1),
                            strides=(self.strides, self.strides),
                            name="conv_proj")(residual)
            residual = norm(name="norm_proj")(residual)
        return nn.relu(residual + y)


class ResNet(nn.Module):
    stage_sizes: Sequence[int]
    block_cls: ModuleDef
    num_classes: int = 1000
    num_filters: int = 64
    dtype: Any = jnp.bfloat16
    stem_strides: int = 2  # small-image variants (CIFAR-style) can use 1
    norm: str = "bn"  # 'bn' | 'stalebn' (fused-epilogue stats) | 'affine'
    conv_impl: str = "xla"  # 'xla' | 'pallas' (traffic-floor 3x3 backward)

    @nn.compact
    def __call__(self, x, train: bool = True):
        x = x.astype(self.dtype)
        x = nn.Conv(self.num_filters, (7, 7),
                    strides=(self.stem_strides, self.stem_strides),
                    padding=[(3, 3), (3, 3)], use_bias=False,
                    dtype=self.dtype, name="conv_init")(x)
        x = make_norm(self.norm, train, self.dtype)(name="bn_init")(x)
        x = nn.relu(x)
        if self.stem_strides == 2:
            x = nn.max_pool(x, (3, 3), strides=(2, 2), padding=[(1, 1), (1, 1)])
        for i, block_count in enumerate(self.stage_sizes):
            for j in range(block_count):
                strides = 2 if i > 0 and j == 0 else 1
                x = self.block_cls(self.num_filters * 2 ** i,
                                   strides=strides, dtype=self.dtype,
                                   norm=self.norm,
                                   conv_impl=self.conv_impl)(x, train)
        x = jnp.mean(x, axis=(1, 2))
        # head in float32: the tiny matmul costs nothing, the logits gain
        # a lot of precision
        x = nn.Dense(self.num_classes, dtype=jnp.float32)(x)
        return x


# --- Normalizer-free ResNets (Brock et al. 2021, NF-ResNet) ---------------
# The BN-free variant: BatchNorm's extra
# activation passes cost 8.4 GB of ResNet-50's 44 GB/step on v5e
# (docs/PERF.md, "the BN traffic measured"), and the zero-norm "affine floor" measures
# +19% step throughput.  NF-ResNets reach that floor with PUBLISHED
# convergence parity on ImageNet: scaled weight standardization (statistics
# over the WEIGHTS — 25 M params, negligible traffic — not the activations),
# analytic variance tracking (alpha/beta), and SkipInit.  Adaptive gradient
# clipping (AGC), which the paper needs only at batch 4096+, is wired via
# optax: compose ``optax.adaptive_grad_clip(0.01)`` ahead of the optimizer
# (imagenet CLI: ``--agc 0.01``; composition with the multi-node optimizer
# is clip-engagement-tested in tests/test_resnet.py).

GAMMA_RELU = 1.7139588594436646  # sqrt(2/(1-1/pi)): restores unit variance


class ScaledWSConv(nn.Module):
    """Conv with scaled weight standardization + learnable per-channel gain.

    W_hat = gain * (W - mean) / sqrt(var * fan_in + eps), statistics taken
    per output channel over (kh, kw, cin).  All the normalization work is
    on the 25 M-param weight tensor — O(params) traffic instead of BN's
    O(activations) — so the activation path is a bare conv the TPU can
    stream at the HBM floor."""

    features: int
    kernel: Tuple[int, int] = (3, 3)
    strides: int = 1
    dtype: Any = jnp.bfloat16
    padding: Any = "SAME"
    conv_impl: str = "xla"

    @nn.compact
    def __call__(self, x):
        kh, kw = self.kernel
        cin = x.shape[-1]
        w = self.param("kernel", nn.initializers.he_normal(),
                       (kh, kw, cin, self.features), jnp.float32)
        gain = self.param("gain", nn.initializers.ones,
                          (self.features,), jnp.float32)
        mu = w.mean((0, 1, 2), keepdims=True)
        var = w.var((0, 1, 2), keepdims=True)
        fan_in = kh * kw * cin
        w_hat = (w - mu) * jax.lax.rsqrt(var * fan_in + 1e-4) * gain
        if self.conv_impl == "pallas" and self.padding == "SAME":
            # Pallas backward for every eligible conv (stride-1 3x3 AND
            # 1x1 on planes >= 14x14 — see _eligible); conv2d falls back
            # to the XLA transpose only for stride-2 / tiny planes, so
            # routing every SAME conv through it is behavior-safe.
            return conv_backward.conv2d(x.astype(self.dtype),
                                        w_hat.astype(self.dtype),
                                        self.strides)
        return jax.lax.conv_general_dilated(
            x.astype(self.dtype), w_hat.astype(self.dtype),
            (self.strides, self.strides), self.padding,
            dimension_numbers=("NHWC", "HWIO", "NHWC"))


class NFBottleneckBlock(nn.Module):
    """Pre-activation normalizer-free bottleneck:
    ``x + alpha * skip_gain * f(relu(x / beta) * gamma)`` with SkipInit
    (skip_gain zero-init) so every block starts as identity."""

    filters: int
    beta: float  # sqrt of the analytically tracked input variance
    strides: int = 1
    alpha: float = 0.2
    dtype: Any = jnp.bfloat16
    conv_impl: str = "xla"

    @nn.compact
    def __call__(self, x):
        conv = partial(ScaledWSConv, dtype=self.dtype,
                       conv_impl=self.conv_impl)
        act = lambda v: nn.relu(v) * GAMMA_RELU  # noqa: E731
        out = act(x / self.beta)
        if self.strides > 1 or x.shape[-1] != self.filters * 4:
            # transition: the shortcut consumes the NORMALIZED activated
            # input, resetting its variance to ~1
            shortcut = conv(self.filters * 4, (1, 1), strides=self.strides,
                            name="conv_shortcut")(out)
        else:
            shortcut = x
        y = act(conv(self.filters, (1, 1))(out))
        y = act(conv(self.filters, (3, 3), strides=self.strides)(y))
        y = conv(self.filters * 4, (1, 1))(y)
        skip_gain = self.param("skip_gain", nn.initializers.zeros,
                               (), jnp.float32)
        # trunk stays in bf16: an fp32 residual path re-inflates HBM traffic
        # past BN's (measured 45 GB vs 36 GB floor); the scalar gain is
        # folded in fp32, the add runs at compute dtype
        return shortcut + ((self.alpha * skip_gain).astype(self.dtype)
                           * y.astype(self.dtype))


class NFResNet(nn.Module):
    """Normalizer-free ResNet-v1.5-shaped network (NF-ResNet-50/101/152).

    Variance bookkeeping follows the NF-ResNet recipe: expected_var starts
    at 1 after the stem, grows by alpha^2 per block, and resets to
    1 + alpha^2 at transitions (their shortcut reads the normalized
    activated input)."""

    stage_sizes: Sequence[int]
    num_classes: int = 1000
    num_filters: int = 64
    alpha: float = 0.2
    dtype: Any = jnp.bfloat16
    stem_strides: int = 2
    conv_impl: str = "xla"

    @nn.compact
    def __call__(self, x, train: bool = True):
        del train  # no normalization layers; kept for ARCHS signature parity
        x = x.astype(self.dtype)
        x = ScaledWSConv(self.num_filters, (7, 7),
                         strides=self.stem_strides,
                         padding=[(3, 3), (3, 3)], dtype=self.dtype,
                         name="conv_init")(x)
        x = nn.relu(x) * GAMMA_RELU
        if self.stem_strides == 2:
            x = nn.max_pool(x, (3, 3), strides=(2, 2), padding=[(1, 1), (1, 1)])
        expected_var = 1.0
        for i, block_count in enumerate(self.stage_sizes):
            for j in range(block_count):
                strides = 2 if i > 0 and j == 0 else 1
                transition = j == 0  # stage entry: width and/or stride jump
                x = NFBottleneckBlock(
                    self.num_filters * 2 ** i,
                    beta=float(expected_var) ** 0.5, strides=strides,
                    alpha=self.alpha, dtype=self.dtype,
                    conv_impl=self.conv_impl)(x)
                expected_var = (1.0 if transition else expected_var) \
                    + self.alpha ** 2
        x = jnp.mean(x, axis=(1, 2))
        x = nn.Dense(self.num_classes, dtype=jnp.float32)(x)
        return x


NFResNet50 = partial(NFResNet, stage_sizes=[3, 4, 6, 3])
NFResNet101 = partial(NFResNet, stage_sizes=[3, 4, 23, 3])
NFResNet152 = partial(NFResNet, stage_sizes=[3, 8, 36, 3])


ResNet18 = partial(ResNet, stage_sizes=[2, 2, 2, 2], block_cls=BasicBlock)
ResNet34 = partial(ResNet, stage_sizes=[3, 4, 6, 3], block_cls=BasicBlock)
ResNet50 = partial(ResNet, stage_sizes=[3, 4, 6, 3], block_cls=BottleneckBlock)
ResNet101 = partial(ResNet, stage_sizes=[3, 4, 23, 3], block_cls=BottleneckBlock)
ResNet152 = partial(ResNet, stage_sizes=[3, 8, 36, 3], block_cls=BottleneckBlock)

ARCHS: dict = {
    "resnet18": ResNet18,
    "resnet34": ResNet34,
    "resnet50": ResNet50,
    "resnet101": ResNet101,
    "resnet152": ResNet152,
    "nf_resnet50": NFResNet50,
    "nf_resnet101": NFResNet101,
    "nf_resnet152": NFResNet152,
}

# The reference's imagenet example shipped a zoo beyond ResNet
# (models/{alex,googlenet,...}.py [uv], SURVEY.md §2.9) — registered here so
# the CLI accepts them; defined in models/convnets.py (import at the bottom
# to avoid a cycle: convnets is standalone, ARCHS is the registry).
from .convnets import AlexNet, GoogLeNet, VGG16  # noqa: E402

ARCHS.update({
    "alex": AlexNet,
    "alexnet": AlexNet,
    "googlenet": GoogLeNet,
    "vgg16": VGG16,
})

from .vit import ViT_B16, ViT_S16, ViT_Ti16  # noqa: E402

ARCHS.update({
    "vit_ti16": ViT_Ti16,
    "vit_s16": ViT_S16,
    "vit_b16": ViT_B16,
})
