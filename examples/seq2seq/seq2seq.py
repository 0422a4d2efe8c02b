#!/usr/bin/env python
"""Distributed seq2seq translation — BASELINE config #3.

Reference parity: ``examples/seq2seq/seq2seq.py`` [uv] (SURVEY.md §2.9):
rank 0 loads the corpus and vocabularies → ``bcast_obj`` the vocab →
``scatter_dataset`` the pairs → multi-node optimizer → per-epoch multi-node
evaluation → greedy translation samples.  The reference trained En→Fr
WMT under mpiexec; with no corpus on disk a synthetic reversal
"translation" corpus exercises the identical pipeline (ragged pairs,
object broadcast, scatter, padded buckets).

Run:  python examples/seq2seq/seq2seq.py --devices 8     (virtual CPU mesh)
      python examples/seq2seq/seq2seq.py                 (real chips)
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))


def make_corpus(n, vocab, seed, min_len=2, max_len=10):
    """Ragged (source, reversed-source) token pairs, ids >= N_SPECIAL."""
    import numpy as np
    from chainermn_tpu.models.seq2seq import N_SPECIAL

    rng = np.random.RandomState(seed)
    pairs = []
    for _ in range(n):
        k = rng.randint(min_len, max_len + 1)
        s = rng.randint(N_SPECIAL, vocab, size=k).tolist()
        pairs.append((s, s[::-1]))
    return pairs


def main():
    parser = argparse.ArgumentParser(description="ChainerMN-TPU example: seq2seq")
    parser.add_argument("--communicator", type=str, default="xla")
    parser.add_argument("--devices", type=int, default=0,
                        help="fake an N-device CPU mesh (0 = real chips)")
    parser.add_argument("--batchsize", type=int, default=64, help="global batch")
    parser.add_argument("--epoch", type=int, default=8)
    parser.add_argument("--unit", type=int, default=128)
    parser.add_argument("--layer", type=int, default=2)
    parser.add_argument("--lr", type=float, default=3e-3)
    parser.add_argument("--vocab", type=int, default=32)
    parser.add_argument("--n-train", type=int, default=4096)
    parser.add_argument("--n-val", type=int, default=256)
    parser.add_argument("--bucket", type=int, default=12, help="padded length")
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
    from chainermn_tpu.iterators import SerialIterator
    from chainermn_tpu.models.seq2seq import (
        PAD, EOS, Seq2seq, encode_pairs, masked_cross_entropy, token_accuracy)
    from chainermn_tpu.training import StandardUpdater, Trainer, extensions
    from chainermn_tpu.topology import enable_compile_cache

    enable_compile_cache()

    comm = mn.create_communicator(args.communicator)
    print(f"communicator={args.communicator} size={comm.size} "
          f"backend={jax.default_backend()}")

    # Rank 0 owns the corpus + vocab; everyone else receives them over the
    # object lane (reference: bcast of the vocabularies [uv]).
    if comm.owns_rank(0):
        vocab = {"size": args.vocab}
        train_pairs = make_corpus(args.n_train, args.vocab, seed=1)
        val_pairs = make_corpus(args.n_val, args.vocab, seed=2)
    else:
        vocab, train_pairs, val_pairs = None, None, None
    vocab = comm.bcast_obj(vocab, root=0)
    train_scattered = mn.scatter_dataset(
        comm.bcast_obj(train_pairs, root=0), comm, shuffle=True, seed=0)
    val_pairs = comm.bcast_obj(val_pairs, root=0)

    model = Seq2seq(vocab["size"], vocab["size"], n_units=args.unit,
                    n_layers=args.layer,
                    dtype=jnp.bfloat16 if jax.default_backend() == "tpu"
                    else jnp.float32)
    src0, tin0, _ = encode_pairs(train_pairs[:2] if train_pairs else
                                 make_corpus(2, vocab["size"], 9),
                                 args.bucket, args.bucket)
    params = model.init(jax.random.PRNGKey(0), src0, tin0)
    opt = mn.create_multi_node_optimizer(optax.adam(args.lr), comm)

    def loss_fn(p, batch):
        src, tin, tout = batch
        logits = model.apply(p, src, tin)
        return masked_cross_entropy(logits, tout), token_accuracy(logits, tout)

    raw_step = mn.make_train_step(loss_fn, opt, has_aux=True, donate=False)

    def step_fn(state, batch):
        p, s = state
        p, s, loss, acc = raw_step(p, s, batch)
        return (p, s), {"main/loss": loss, "main/accuracy": acc}

    def converter(batch):
        return encode_pairs(batch, args.bucket, args.bucket)

    # Global-batch iterator over the union of shards: single-controller owns
    # all ranks, so iterate the whole (scattered) dataset and let shard_batch
    # split it across the mesh — each chip sees exactly its scattered shard's
    # share of every global batch.
    flat = [shard[i] for r in range(comm.size)
            for shard in [train_scattered.shard(r)]
            for i in range(len(shard))]
    it = SerialIterator(flat, args.batchsize, shuffle=True, seed=0)
    state = (mn.replicate(params), mn.replicate(opt.init(params)))
    updater = StandardUpdater(it, step_fn, state, converter=converter)
    trainer = Trainer(updater, (args.epoch, "epoch"), out="result_seq2seq")

    vsrc, vtin, vtout = encode_pairs(val_pairs, args.bucket, args.bucket)

    @jax.jit
    def eval_batch(p, src, tin, tout):
        logits = model.apply(p, src, tin)
        return masked_cross_entropy(logits, tout), token_accuracy(logits, tout)

    def evaluate(_):
        p = updater.state[0]
        loss, acc = eval_batch(p, vsrc, vtin, vtout)
        return {"loss": float(loss), "accuracy": float(acc)}

    log = extensions.LogReport(trigger=(1, "epoch"))
    trainer.extend(extensions.EvaluatorExtension(evaluate, None, trigger=(1, "epoch")))
    trainer.extend(log)
    trainer.extend(extensions.PrintReport(
        ["epoch", "iteration", "main/loss", "main/accuracy",
         "validation/loss", "validation/accuracy", "elapsed_time"], log))
    trainer.run()

    # Greedy translation samples (reference printed example translations).
    toks = np.asarray(model.apply(
        updater.state[0], vsrc[:4], max_len=args.bucket,
        method=Seq2seq.translate))
    for i in range(4):
        src_toks = [int(t) for t in vsrc[i] if t != PAD]
        out_toks = [int(t) for t in toks[i] if t not in (PAD, EOS)]
        ok = out_toks == src_toks[::-1]
        print(f"src={src_toks} → out={out_toks} {'✓' if ok else '✗'}")

    # Corpus BLEU over the whole validation set (reference parity: the
    # reference's seq2seq scored its translations with BLEU).
    def translate_fn(srcs):
        src_arr, _, _ = encode_pairs(
            [(list(s), list(s)) for s in srcs], args.bucket, args.bucket)
        out = np.asarray(model.apply(
            updater.state[0], src_arr, max_len=args.bucket,
            method=Seq2seq.translate))
        return [[int(t) for t in row if t not in (PAD, EOS)] for row in out]

    # val_pairs already holds the ragged (source, reversed-source) examples.
    # Multi-controller: each process scores only its strided slice (plain
    # lists are treated as LOCAL shards; the evaluator pools the counts),
    # so BLEU is identical for any host count and nothing decodes P times.
    if comm.inter_size > 1:
        owned = [r for r in range(comm.size) if comm.owns_rank(r)]
        local_pairs = [ex for i, ex in enumerate(val_pairs)
                       if i % comm.size in owned]
    else:
        local_pairs = val_pairs
    bleu_eval = mn.bleu_evaluator(translate_fn, comm)
    print(f"validation BLEU: {bleu_eval([local_pairs])['bleu']:.4f}")


if __name__ == "__main__":
    main()
