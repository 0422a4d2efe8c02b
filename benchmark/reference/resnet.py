"""Plain ResNet-50 v1.5 (He et al. 2015, arXiv:1512.03385; stride on the
3x3): float32 ``jax.numpy`` / ``lax`` convolutions at ``highest`` precision,
training-mode BatchNorm with each shard's own batch statistics, softmax
cross-entropy, SGD with momentum and weight decay by hand.  Imports nothing
of the program and takes nothing the program made: the weights come from
:func:`init_variables` (a pure function of the seed, in the parameter tree
the program's flax model declares), the images and labels from the driver.

Data-parallel semantics are spelled out: the global batch is cut into
``n_shards`` equal shards, each normalised by its own batch statistics, and
the gradient is the mean of the shards' gradients.  A program that does not
average across its chips fails the first-gradient comparison.

``precision`` rounds every convolution's and the head's operands: ``float32``
(the reference), ``bfloat16`` (what the configuration states), ``fp8`` (the
control).  Recomputation per block (``jax.checkpoint``) and one shard at a
time change memory, not mathematics.
"""

import time
from functools import partial

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
BN_EPS = 1e-5
STAGES = (3, 4, 6, 3)

# ---- limits of the comparison that decides ``correct`` (PERF.md section 2
# gives the readings each was set from; None = not set, the cell is not in
# ``workloads``) -------------------------------------------------------------
LIMITS = {
    "loss_gap": None,
    "grad_norm_gap": None,
    "update_norm_gap": None,
}


def _round(x, precision):
    """``x`` rounded to ``precision`` and back, straight through for the
    gradient (a cast's own backward would round the cotangent too, and
    float8 flushes every gradient of this size to zero)."""
    if precision == "float32":
        return x
    if precision == "bfloat16":
        q = x.astype(jnp.bfloat16).astype(x.dtype)
    elif precision == "fp8":
        scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
        q = (x / scale).astype(jnp.float8_e4m3fn).astype(x.dtype) * scale
    else:
        raise ValueError(f"unknown precision {precision!r}")
    return x + jax.lax.stop_gradient(q - x)


def init_variables(key, cfg):
    """Seeded ``{"params", "batch_stats"}`` in the tree the program's flax
    ResNet declares (``conv_init``, ``bn_init``, ``BottleneckBlock_<k>`` with
    ``Conv_0..2``, ``BatchNorm_0..2``, ``conv_proj``, ``norm_proj``,
    ``Dense_0``).  Kernels are He-normal; every BatchNorm starts at scale 1,
    the last of each block too (flax's zero there would switch every
    residual branch off at the seed, and the check would see only the stem
    and the projections); the head is normal(0, 0.01)."""
    width, classes = cfg["num_filters"], cfg["num_classes"]
    n_keys = 2 + 4 * sum(STAGES)
    keys = iter(jax.random.split(key, n_keys))

    def conv(kh, kw, cin, cout):
        std = (2.0 / (kh * kw * cin)) ** 0.5
        return {"kernel": jax.random.normal(
            next(keys), (kh, kw, cin, cout), jnp.float32) * std}

    def bn(c):
        return ({"scale": jnp.ones((c,)), "bias": jnp.zeros((c,))},
                {"mean": jnp.zeros((c,)), "var": jnp.ones((c,))})

    params, stats = {}, {}
    params["conv_init"] = conv(7, 7, 3, width)
    params["bn_init"], stats["bn_init"] = bn(width)
    cin, k = width, 0
    for i, count in enumerate(STAGES):
        f = width * 2 ** i
        for j in range(count):
            p, s = {}, {}
            p["Conv_0"] = conv(1, 1, cin, f)
            p["BatchNorm_0"], s["BatchNorm_0"] = bn(f)
            p["Conv_1"] = conv(3, 3, f, f)
            p["BatchNorm_1"], s["BatchNorm_1"] = bn(f)
            p["Conv_2"] = conv(1, 1, f, 4 * f)
            p["BatchNorm_2"], s["BatchNorm_2"] = bn(4 * f)
            if j == 0:
                p["conv_proj"] = conv(1, 1, cin, 4 * f)
                p["norm_proj"], s["norm_proj"] = bn(4 * f)
            else:
                next(keys)
            params[f"BottleneckBlock_{k}"], stats[f"BottleneckBlock_{k}"] = p, s
            cin, k = 4 * f, k + 1
    params["Dense_0"] = {
        "kernel": jax.random.normal(next(keys), (cin, classes)) * 0.01,
        "bias": jnp.zeros((classes,))}
    return {"params": params, "batch_stats": stats}


def _conv(x, p, stride, precision, padding="SAME"):
    return jax.lax.conv_general_dilated(
        _round(x, precision), _round(p["kernel"], precision),
        (stride, stride), padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST)


def _bn(x, p):
    mean = x.mean((0, 1, 2))
    var = ((x - mean) ** 2).mean((0, 1, 2))
    return (x - mean) / jnp.sqrt(var + BN_EPS) * p["scale"] + p["bias"]


def _bottleneck(x, p, stride, precision):
    y = jax.nn.relu(_bn(_conv(x, p["Conv_0"], 1, precision), p["BatchNorm_0"]))
    y = jax.nn.relu(_bn(_conv(y, p["Conv_1"], stride, precision),
                        p["BatchNorm_1"]))
    y = _bn(_conv(y, p["Conv_2"], 1, precision), p["BatchNorm_2"])
    if "conv_proj" in p:
        x = _bn(_conv(x, p["conv_proj"], stride, precision), p["norm_proj"])
    return jax.nn.relu(x + y)


def logits(params, images_u8, *, precision="float32"):
    """Training-mode forward of one shard of uint8 NHWC images."""
    x = images_u8.astype(params["conv_init"]["kernel"].dtype) / 255.0 - 0.5
    x = _conv(x, params["conv_init"], 2, precision, [(3, 3), (3, 3)])
    x = jax.nn.relu(_bn(x, params["bn_init"]))
    x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 3, 3, 1),
                              (1, 2, 2, 1), [(0, 0), (1, 1), (1, 1), (0, 0)])
    k = 0
    for i, count in enumerate(STAGES):
        for j in range(count):
            blk = jax.checkpoint(partial(
                _bottleneck, stride=2 if i > 0 and j == 0 else 1,
                precision=precision))
            x = blk(x, params[f"BottleneckBlock_{k}"])
            k += 1
    x = x.mean((1, 2))
    head = params["Dense_0"]
    return jnp.einsum("nc,ck->nk", _round(x, precision),
                      _round(head["kernel"], precision),
                      precision=HIGHEST) + head["bias"]


def shard_loss(params, images_u8, labels, *, precision="float32"):
    lg = logits(params, images_u8, precision=precision)
    logp = jax.nn.log_softmax(lg, axis=-1)
    return -jnp.take_along_axis(logp, labels[:, None], axis=-1).mean()


def leaf_norms(tree):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree_util.tree_leaves(tree)])


def worst_leaf_gap(got, want):
    """Gap between the program's norm and the reference's by the worst leaf,
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger."""
    floor = jnp.median(want)
    return float(jnp.max(jnp.abs(got - want) / jnp.maximum(want, floor)))


def train_steps(key, cfg, opt, batches, n_shards, *, precision="float32"):
    """The first ``len(batches)`` data-parallel SGD steps from seeded
    weights.  ``batches``: ``(images uint8 (N, H, W, 3), labels (N,))``
    global batches.  Returns each step's loss (mean over the shards), the
    per-leaf norms of the first gradient as the optimizer's momentum gets it
    (mean over shards, plus weight decay) and of the parameters' change."""
    lr, mom, wd = opt["lr"], opt["momentum"], opt["weight_decay"]
    with jax.default_matmul_precision("highest"):
        init = jax.jit(lambda k: init_variables(k, cfg)["params"])
        grad = jax.jit(jax.value_and_grad(partial(shard_loss,
                                                  precision=precision)))

        @partial(jax.jit, donate_argnums=(0,))
        def accumulate(acc, g):
            return jax.tree_util.tree_map(jnp.add, acc, g)

        @partial(jax.jit, donate_argnums=(0, 1))
        def sgd(p, trace, g):
            g = jax.tree_util.tree_map(lambda g, p: g / n_shards + wd * p, g, p)
            trace = jax.tree_util.tree_map(lambda t, g: mom * t + g, trace, g)
            p = jax.tree_util.tree_map(lambda p, t: p - lr * t, p, trace)
            return p, trace

        p = init(key)
        trace = jax.tree_util.tree_map(jnp.zeros_like, p)
        losses, grad_norms, step_s = [], None, []
        for images, labels in batches:
            t0 = time.perf_counter()
            rows = images.shape[0] // n_shards
            total, grads = 0.0, None
            for s in range(n_shards):
                sl = slice(s * rows, (s + 1) * rows)
                l, g = grad(p, jnp.asarray(images[sl]), jnp.asarray(labels[sl]))
                total = total + l
                grads = g if grads is None else accumulate(grads, g)
                del g
            losses.append(float(total / n_shards))
            p, trace = sgd(p, trace, grads)
            del grads
            if grad_norms is None:      # momentum's first state IS g + wd p
                grad_norms = jax.device_get(jax.jit(leaf_norms)(trace))
            step_s.append(time.perf_counter() - t0)
        update_norms = jax.jit(lambda p, k: leaf_norms(
            jax.tree_util.tree_map(jnp.subtract, p, init(k))))(p, key)
        out = {"losses": losses, "step_s": step_s, "grad_norms": grad_norms,
               "update_norms": jax.device_get(update_norms)}
    del p, trace
    return out
