#!/usr/bin/env python
"""Data-parallel MNIST MLP — BASELINE config #1.

Reference parity: ``examples/mnist/train_mnist.py`` [uv] (SURVEY.md §2.9):
create_communicator → scatter_dataset → multi-node optimizer → train →
multi-node evaluator.  The reference ran one MPI process per GPU under
``mpiexec``; here one process drives every chip of the slice through a
single jitted SPMD step.

With no dataset on disk a synthetic, *learnable* MNIST stand-in is
generated (labels are a linear function of the image), so loss/accuracy
trends demonstrate end-to-end correctness without network access.
Run:  python examples/mnist/train_mnist.py --devices 8   (virtual CPU mesh)
      python examples/mnist/train_mnist.py               (real chips)
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))


def make_synthetic_mnist(n, seed=0):
    """Learnable stand-in: zero-mean images, labels from one fixed linear
    map shared by every split (so train/val measure the same task)."""
    import numpy as np
    w_true = np.random.RandomState(42).randn(784, 10).astype(np.float32)
    xs = np.random.RandomState(seed).randn(n, 784).astype(np.float32)
    ys = (xs @ w_true).argmax(-1).astype(np.int32)
    return list(zip(xs, ys))


def main():
    parser = argparse.ArgumentParser(description="ChainerMN-TPU example: MNIST")
    parser.add_argument("--communicator", type=str, default="xla",
                        help="xla | pure_nccl | hierarchical | ... | naive")
    parser.add_argument("--devices", type=int, default=0,
                        help="fake an N-device CPU mesh (0 = use real chips)")
    parser.add_argument("--batchsize", type=int, default=128, help="per-rank batch")
    parser.add_argument("--epoch", type=int, default=3)
    parser.add_argument("--unit", type=int, default=256)
    parser.add_argument("--lr", type=float, default=1e-3)
    parser.add_argument("--n-train", type=int, default=8192)
    parser.add_argument("--n-val", type=int, default=1024)
    parser.add_argument("--double-buffering", action="store_true")
    args = parser.parse_args()

    if args.devices:
        import jax
        jax.config.update("jax_platforms", "cpu")
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.devices}")

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import chainermn_tpu as mn
    from chainermn_tpu.models import MLP, accuracy, cross_entropy_loss
    from chainermn_tpu.topology import enable_compile_cache

    enable_compile_cache()

    mn.init_distributed()
    comm = mn.create_communicator(args.communicator)
    if comm.rank == 0:
        print(f"communicator: {type(comm).__name__}  size: {comm.size}")

    train = make_synthetic_mnist(args.n_train, seed=0)
    val = make_synthetic_mnist(args.n_val, seed=1)
    scattered = mn.scatter_dataset(train, comm, shuffle=True, seed=0)

    model = MLP(n_units=args.unit)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 784)))
    optimizer = mn.create_multi_node_optimizer(
        optax.adam(args.lr), comm, double_buffering=args.double_buffering)

    mesh = getattr(comm, "mesh", None) or mn.make_mesh()

    def loss_fn(params, batch):
        xs, ys = batch
        logits = model.apply(params, xs)
        return cross_entropy_loss(logits, ys), accuracy(logits, ys)

    step = mn.make_train_step(loss_fn, optimizer, mesh=mesh, has_aux=True)
    params = mn.replicate(params, mesh)
    opt_state = mn.replicate(optimizer.init(params), mesh)

    shard_len = len(scattered.shard(0))
    steps_per_epoch = max(shard_len // args.batchsize, 1)
    t0 = time.time()
    for epoch in range(args.epoch):
        for it in range(steps_per_epoch):
            # global batch = concatenation of each rank's local batch
            xs, ys = [], []
            for r in range(comm.size):
                shard = scattered.shard(r)
                idx = [(it * args.batchsize + j) % len(shard)
                       for j in range(args.batchsize)]
                items = [shard[i] for i in idx]
                xs.append(np.stack([x for x, _ in items]))
                ys.append(np.asarray([y for _, y in items]))
            batch = mn.shard_batch(
                (np.concatenate(xs), np.concatenate(ys)), mesh)
            params, opt_state, loss, acc = step(params, opt_state, batch)
            # keep virtual devices in lockstep on thin hosts (see tests)
            loss.block_until_ready()
        if comm.rank == 0:
            print(f"epoch {epoch}  loss {float(loss):.4f}  acc {float(acc):.3f}  "
                  f"({time.time() - t0:.1f}s)")

    evaluator = mn.create_multi_node_evaluator(
        mn.accuracy_evaluator(lambda xs: model.apply(params, jnp.asarray(xs))), comm)
    # eval shards stay unequal (no wrap padding) — the evaluator's
    # example-weighted mean handles that; padding would double-count
    metrics = evaluator(mn.scatter_dataset(val, comm, force_equal_length=False))
    if comm.rank == 0:
        print({k: round(v, 4) for k, v in metrics.items()})


if __name__ == "__main__":
    main()
