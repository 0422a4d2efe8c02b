#!/usr/bin/env python
"""MNIST with fault-tolerant checkpoint/auto-resume.

Reference parity: ``examples/mnist/train_mnist_checkpoint.py`` [uv]
(SURVEY.md §2.9) — the checkpointer-exercising MNIST variant: snapshots
every epoch, and a SIGKILL'd/restarted job resumes from the newest
gang-consistent generation with identical training state (params, optimizer
momentum, data order).

Demo the resume end-to-end in one command with ``--kill-at-epoch``: the
run "crashes" mid-training, then a fresh process resumes and finishes:

    python examples/mnist/train_mnist_checkpoint.py --devices 8 --kill-at-epoch 2
    python examples/mnist/train_mnist_checkpoint.py --devices 8   # resumes
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

from train_mnist import make_synthetic_mnist  # noqa: E402


def main():
    parser = argparse.ArgumentParser(
        description="ChainerMN-TPU example: MNIST with checkpoint/resume")
    parser.add_argument("--devices", type=int, default=0)
    parser.add_argument("--batchsize", type=int, default=128)
    parser.add_argument("--epoch", type=int, default=4)
    parser.add_argument("--unit", type=int, default=256)
    parser.add_argument("--lr", type=float, default=1e-3)
    parser.add_argument("--out", default="result_mnist_ckpt")
    parser.add_argument("--kill-at-epoch", type=int, default=0,
                        help="simulate a crash after this many epochs (0=off)")
    args = parser.parse_args()

    if args.devices:
        import jax
        jax.config.update("jax_platforms", "cpu")
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.devices}")

    import jax
    import jax.numpy as jnp
    import optax

    import chainermn_tpu as mn
    from chainermn_tpu.models import MLP, accuracy, cross_entropy_loss
    from chainermn_tpu.training import StandardUpdater, Trainer, extensions
    from chainermn_tpu.topology import enable_compile_cache

    enable_compile_cache()

    mn.init_distributed()
    comm = mn.create_communicator("xla")
    mesh = comm.mesh

    # The updater shards each global batch across the mesh itself, so the
    # iterator runs over the full dataset (scatter_dataset is exercised by
    # the base train_mnist.py); shuffle order across restarts comes from
    # the iterator's CHECKPOINTED rng state, not the seed alone.
    train = make_synthetic_mnist(4096, seed=0)
    it = mn.SerialIterator(train, args.batchsize * comm.size,
                           shuffle=True, seed=1)

    model = MLP(n_units=args.unit)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 784)))
    optimizer = mn.create_multi_node_optimizer(optax.adam(args.lr), comm)

    def loss_fn(p, batch):
        xs, ys = batch
        logits = model.apply(p, xs)
        return cross_entropy_loss(logits, ys), accuracy(logits, ys)

    raw_step = mn.make_train_step(loss_fn, optimizer, mesh=mesh,
                                  has_aux=True, donate=False)

    def step_fn(state, batch):
        p, st = state
        p, st, loss, acc = raw_step(p, st, batch)
        return (p, st), {"main/loss": loss, "main/acc": acc}

    state = (mn.replicate(params, mesh),
             mn.replicate(optimizer.init(params), mesh))
    trainer = Trainer(StandardUpdater(it, step_fn, state),
                      (args.epoch, "epoch"), out=args.out)
    log = extensions.LogReport(trigger=(1, "epoch"))
    trainer.extend(log)
    trainer.extend(extensions.PrintReport(
        ["epoch", "iteration", "main/loss", "main/acc"], log))

    ckpt = mn.create_multi_node_checkpointer(
        "mnist", comm, path=os.path.join(args.out, "checkpoints"), keep=2)
    trainer.extend(ckpt, trigger=(1, "epoch"))

    # ---- auto-resume (reference: maybe_load after restart [uv]) ----
    snap, resumed_iter = ckpt.maybe_load()
    if resumed_iter is not None:
        trainer.load_checkpoint_state(snap)
        if comm.rank == 0:
            print(f"resumed from iteration {resumed_iter} "
                  f"(epoch {trainer.epoch})")

    if args.kill_at_epoch:
        class _Killer:
            trigger = (args.kill_at_epoch, "epoch")

            def __call__(self, trainer):
                print(f"simulating crash at epoch {trainer.epoch} "
                      f"(checkpoints retained)", flush=True)
                os._exit(99)

        trainer.extend(_Killer(), name="killer")

    trainer.run()
    if comm.rank == 0:
        print(f"done: epoch {trainer.epoch}, "
              f"final loss {log.log[-1]['main/loss']:.4f}")


if __name__ == "__main__":
    main()
