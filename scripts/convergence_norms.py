#!/usr/bin/env python
# spmd-lint: disable-file=prng-constant-key — fixed seeds are the point:
# profile/probe runs must be bit-reproducible across commits to be comparable
"""Optimization-dynamics parity: BN ResNet-50 vs its traffic-saving variants.

Same data (fixed synthetic labeled set, the no-network stand-in), same
optimizer/seed/steps; only the architecture's normalization strategy
differs.  The claim under test is NOT final accuracy (synthetic data) but
that the variant trains as stably as BN over the measured window.

History this script records (docs/PERF.md "ResNet" section):
  * stalebn with EMA-normalization destabilized after ~50 steps; the
    1-step-stale rework NaN'd by step 5 at lr 0.05
    (docs/evidence_stalebn_divergence.json) — stale activation statistics
    are an undamped feedback loop, so the knob stays experimental.
  * nf_resnet50 (scaled weight standardization + SkipInit, Brock et al.) is
    the shipped BN-free path: stats live on the weights, activations run at
    the measured zero-norm HBM floor.

Usage: python scripts/convergence_norms.py [variant ...]
Variants: bn (default baseline), stalebn, affine, nf (default comparison).
"""

import json
import sys

sys.path.insert(0, ".")

import jax
import jax.numpy as jnp
import numpy as np
import optax

import chainermn_tpu as mn
from chainermn_tpu.models.mlp import cross_entropy_loss
from chainermn_tpu.models.resnet import ARCHS

B, IMG, CLASSES, STEPS, LOG_EVERY = 256, 32, 10, 300, 20

VARIANTS = {
    "bn": ("resnet50", {}),
    "stalebn": ("resnet50", {"norm": "stalebn"}),
    "affine": ("resnet50", {"norm": "affine"}),
    "nf": ("nf_resnet50", {}),
}


def run(variant: str):
    arch, kw = VARIANTS[variant]
    model = ARCHS[arch](num_classes=CLASSES, stem_strides=1, **kw)
    comm = mn.create_communicator("xla")
    mesh = comm.mesh
    variables = dict(model.init(jax.random.PRNGKey(0),
                                jnp.zeros((1, IMG, IMG, 3)), train=False))
    variables.setdefault("batch_stats", {})
    opt = optax.chain(optax.add_decayed_weights(1e-4),
                      optax.sgd(0.05, momentum=0.9))
    step = mn.make_flax_train_step(
        model, lambda logits, b: (cross_entropy_loss(logits, b[1]), {}),
        opt, mesh=mesh)
    variables = mn.replicate(variables, mesh)
    opt_state = mn.replicate(opt.init(variables["params"]), mesh)

    # fixed learnable dataset: class-dependent mean shift + noise
    rs = np.random.RandomState(0)
    labels = rs.randint(0, CLASSES, B).astype(np.int32)
    protos = rs.randn(CLASSES, IMG, IMG, 3).astype(np.float32)
    images = protos[labels] * 0.5 + rs.randn(B, IMG, IMG, 3).astype(
        np.float32) * 0.5
    batch = mn.shard_batch((images, labels), mesh)

    losses = []
    for i in range(STEPS):
        variables, opt_state, loss, _ = step(variables, opt_state, batch)
        if (i + 1) % LOG_EVERY == 0:
            losses.append(round(float(loss), 4))
    return losses


def main():
    variants = sys.argv[1:] or ["bn", "nf"]
    out = {}
    for v in variants:
        out[f"loss_{v}"] = run(v)
        print(f"{v}: {out[f'loss_{v}']}", file=sys.stderr, flush=True)
    if "loss_bn" in out and "loss_nf" in out:
        # parity criterion: nf's final logged loss within 15% of bn's,
        # or below it
        out["parity_ok"] = bool(
            out["loss_nf"][-1] <= out["loss_bn"][-1] * 1.15)
    print(json.dumps({"steps": STEPS, "log_every": LOG_EVERY, **out}))


if __name__ == "__main__":
    main()
