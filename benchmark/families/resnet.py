"""ResNet family: the program's data-parallel flax train step, fed by its
native prefetcher, from a configuration file's sizes.  Set-up code copied
from ``chip_smoke.py::_resnet_steps`` and ``examples/imagenet/
train_imagenet.py`` (uint8 records, normalised on the chip), not imported.
Weights come from the reference's seeded initialiser."""

import os
import sys
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(_HERE)))

from benchmark.harness import checks as _checks, flops as _flops  # noqa: E402
from benchmark.harness.loader import module as _module   # noqa: E402

ref = _module("reference", "resnet")

#: the configuration states bfloat16; the nearest precision below it
CONTROL_PRECISION = "fp8"


def train_flops_per_sample(config, traffic) -> float:
    """Forward + backward FLOPs of one image: every convolution and the
    head, from the configuration's shapes (v1.5: the stride is on the 3x3)."""
    size, width = config["image_size"], config["num_filters"]
    hw = size // 2
    total = _flops.conv2d(hw, hw, 7, 7, 3, width)
    hw, cin = hw // 2, width                     # max-pool
    for i, count in enumerate(ref.STAGES):
        f = width * 2 ** i
        for j in range(count):
            out = hw // 2 if (i > 0 and j == 0) else hw
            total += _flops.conv2d(hw, hw, 1, 1, cin, f)
            total += _flops.conv2d(out, out, 3, 3, f, f)
            total += _flops.conv2d(out, out, 1, 1, f, 4 * f)
            if j == 0:
                total += _flops.conv2d(out, out, 1, 1, cin, 4 * f)
            hw, cin = out, 4 * f
    total += _flops.matmul(1, cin, config["num_classes"])
    return _flops.train(total)


def _key(seed: int):
    return jax.random.PRNGKey(np.uint32(seed % (2 ** 32)))


def _records(ctx):
    """The synthetic corpus: uint8 NHWC records and labels from the seed
    (what ``scripts/ingest_images.py`` stores), made in bulk."""
    tr, cfg = ctx.traffic, ctx.config
    rng = np.random.default_rng(ctx.seed)
    n, size = tr["records"], cfg["image_size"]
    images = rng.integers(0, 256, (n, size, size, 3), dtype=np.uint8)
    labels = rng.integers(0, cfg["num_classes"], n, dtype=np.int32)
    return images, labels


def _global_batch(ctx) -> int:
    return ctx.traffic["batch_per_chip"] * len(ctx.devices)


def train_reference(ctx, n_steps: int, precision: str = "float32"):
    """The plain reference's first steps on the batches the unshuffled
    feed will deliver: records [0, B), [B, 2B), ... of the corpus."""
    images, labels = _records(ctx)
    b = _global_batch(ctx)
    batches = [(images[i * b:(i + 1) * b], labels[i * b:(i + 1) * b])
               for i in range(n_steps)]
    return ref.train_steps(_key(ctx.seed), ctx.config,
                           ctx.config["assumed"]["optimizer"], batches,
                           len(ctx.devices), precision=precision)


def train_compare(want, got):
    """Each number compared, beside its limit."""
    return _checks.training(ref, want, got)


class Trainer:
    """The compiled data-parallel step with its state and its feed."""

    def __init__(self, ctx):
        import optax

        import chainermn_tpu as mn
        from chainermn_tpu.models.mlp import cross_entropy_loss
        from chainermn_tpu.models.resnet import ARCHS

        cfg, tr = ctx.config, ctx.traffic
        self.ctx, self.opt = ctx, cfg["assumed"]["optimizer"]
        self.samples_per_step = _global_batch(ctx)
        self.flops_per_sample = train_flops_per_sample(cfg, tr)
        if ctx.on_tpu and not mn.runtime.native_available():
            raise RuntimeError("the C++ prefetcher did not build here")
        comm = mn.create_communicator("xla", devices=ctx.devices)
        self.mesh = mesh = comm.mesh
        model = ARCHS[cfg["arch"]](num_classes=cfg["num_classes"],
                                   num_filters=cfg["num_filters"],
                                   stem_strides=2)
        self._init = jax.jit(partial(ref.init_variables, cfg=cfg))
        variables = self._init(_key(ctx.seed))
        declared = jax.eval_shape(lambda: dict(model.init(
            jax.random.PRNGKey(0), jnp.zeros(
                (1, cfg["image_size"], cfg["image_size"], 3)), train=False)))
        same = jax.tree_util.tree_structure(variables) == \
            jax.tree_util.tree_structure(declared) and all(
                a.shape == b.shape for a, b in zip(
                    jax.tree_util.tree_leaves(variables),
                    jax.tree_util.tree_leaves(declared)))
        if not same:
            raise RuntimeError("the reference's parameter tree is not the "
                               "one the program's model declares")
        inner = optax.chain(
            optax.add_decayed_weights(self.opt["weight_decay"]),
            optax.sgd(self.opt["lr"], momentum=self.opt["momentum"]))
        optimizer = mn.create_multi_node_optimizer(inner, comm)

        def loss_and_metrics(lg, batch):
            return cross_entropy_loss(lg, batch[1]), {}

        def normalize_on_chip(batch):
            images, labels = batch
            return images.astype(jnp.float32) / 255.0 - 0.5, labels

        step = mn.make_flax_train_step(model, loss_and_metrics, optimizer,
                                       mesh=mesh, preprocess=normalize_on_chip)
        self.vars = mn.replicate(variables, mesh)
        self.st = mn.replicate(
            jax.jit(optimizer.init)(variables["params"]), mesh)
        del variables
        self.it = mn.PrefetchIterator(_records(ctx),
                                      batch_size=self.samples_per_step,
                                      shuffle=False, copy=True)
        self._shard = partial(mn.shard_batch, mesh=mesh)
        first = self._next_batch()
        self._held = first
        self.compiled = step.lower(self.vars, self.st, first).compile()
        mem = self.compiled.memory_analysis()
        self.info = {
            "tpu_custom_calls": self.compiled.as_text().count(
                "tpu_custom_call"),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "native_prefetcher": mn.runtime.native_available(),
            "chips": len(ctx.devices),
        }

    def _next_batch(self):
        with self.ctx.spans.span("input"):
            return self._shard(self.it.next())

    def step(self):
        """One train step on the feed's next batch; the loss stays on the
        device."""
        batch, self._held = (self._held, None) if self._held is not None \
            else (self._next_batch(), None)
        with self.ctx.spans.span("dispatch"):
            self.vars, self.st, loss, _ = self.compiled(self.vars, self.st,
                                                        batch)
        return loss

    def first_steps(self, n_steps: int):
        import optax

        losses, grad_norms = [], None
        norms = jax.jit(ref.leaf_norms)
        for _ in range(n_steps):
            losses.append(float(self.step()))
            if grad_norms is None:     # momentum's first state IS g + wd p
                trace = optax.tree_utils.tree_get(self.st, "trace")
                grad_norms = jax.device_get(norms(trace))
        change = jax.jit(lambda p, k: ref.leaf_norms(jax.tree_util.tree_map(
            jnp.subtract, p, self._init(k)["params"])))
        update_norms = jax.device_get(
            change(self.vars["params"], _key(self.ctx.seed)))
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in self.ctx.devices]
        if self.ctx.on_tpu and not all(p and p > (64 << 20) for p in peaks):
            raise RuntimeError(f"a chip held no work: peak bytes {peaks}")
        return {"losses": losses, "grad_norms": grad_norms,
                "update_norms": update_norms}


def build_trainer(ctx):
    return Trainer(ctx)
