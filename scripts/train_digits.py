#!/usr/bin/env python
# spmd-lint: disable-file=prng-constant-key — fixed seeds are the point:
# profile/probe runs must be bit-reproducible across commits to be comparable
"""Real-data convergence proof: FileDataset → prefetch ring → chip → metric.

An earlier review asked for one committed convergence artifact where the
file-backed data path ingests a NON-synthetic corpus and trains to a
target metric.  The corpus is scikit-learn's 1,797 real 8×8 handwritten
digit scans (the one genuine dataset reachable with zero egress),
ingested by ``scripts/ingest_images.py --source sklearn-digits`` into the
C++ prefetcher's record layout, then streamed through
``FileDataset → PrefetchIterator → shard_batch → jit step`` — the exact
path the ImageNet CLI's ``--data-dir`` uses — into a ResNet-18.

Artifact: docs/evidence_digits_convergence.json (loss curve + held-out
accuracy).  Pass/fail bar: val top-1 ≥ 0.95 (simple baselines reach ~0.9x
on digits; a broken data path or training loop lands far below).

Usage: python scripts/train_digits.py
"""

import json
import os
import subprocess
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp
import numpy as np
import optax

import chainermn_tpu as mn
from chainermn_tpu.models.mlp import cross_entropy_loss
from chainermn_tpu.models.resnet import ARCHS

B, STEPS, LOG_EVERY = 128, 400, 25


def main():
    root = tempfile.mkdtemp(prefix="digits_")
    subprocess.run(
        [sys.executable,
         os.path.join(os.path.dirname(__file__), "ingest_images.py"),
         "--source", "sklearn-digits", "--out", root],
        check=True)
    train = mn.FileDataset(os.path.join(root, "train"))
    val = mn.FileDataset(os.path.join(root, "val"))

    comm = mn.create_communicator("xla")
    mesh = comm.mesh
    model = ARCHS["resnet18"](num_classes=10, stem_strides=1)
    variables = dict(model.init(jax.random.PRNGKey(0),
                                jnp.zeros((1, 8, 8, 3)), train=False))
    opt = optax.chain(optax.add_decayed_weights(1e-4),
                      optax.sgd(0.05, momentum=0.9))
    step = mn.make_flax_train_step(
        model, lambda logits, b: (cross_entropy_loss(logits, b[1]), {}),
        opt, mesh=mesh)
    variables = mn.replicate(variables, mesh)
    opt_state = mn.replicate(opt.init(variables["params"]), mesh)

    it = mn.PrefetchIterator(train, batch_size=B, seed=0)
    losses = []
    for i in range(STEPS):
        batch = mn.shard_batch(next(it), mesh)
        variables, opt_state, loss, _ = step(variables, opt_state, batch)
        if (i + 1) % LOG_EVERY == 0:
            losses.append(round(float(loss), 4))
            print(f"step {i + 1}: loss {losses[-1]}", file=sys.stderr,
                  flush=True)
    it.close()

    # held-out accuracy, full val set in one batch (359 records)
    xs, ys = val.unpack(np.asarray(val.packed))
    host_vars = jax.device_get(variables)
    logits = model.apply(
        {"params": host_vars["params"],
         "batch_stats": host_vars["batch_stats"]},
        jnp.asarray(xs), train=False)
    acc = float((np.asarray(logits).argmax(-1) == ys).mean())
    out = {
        "corpus": "sklearn load_digits (1797 real 8x8 handwritten scans)",
        "path": "ingest_images.py -> write_file_dataset -> FileDataset -> "
                "PrefetchIterator (C++ pread ring) -> shard_batch -> chip",
        "train_records": len(train), "val_records": len(val),
        "steps": STEPS, "batch": B, "loss_curve": losses,
        "val_top1": round(acc, 4), "target": 0.95,
        "converged": bool(acc >= 0.95),
    }
    print(json.dumps(out))


if __name__ == "__main__":
    main()
