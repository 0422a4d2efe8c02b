#!/usr/bin/env python
"""Train a tiny LM on a toy corpus, then decode from it with the KV cache.

Beyond-reference workload: the reference's only generation was seq2seq
greedy translate; this demonstrates the decoding stack end-to-end —
DP×TP training (make_hybrid_shard_map_step) into TP-sharded KV-cache
incremental decoding (make_lm_generator), with RoPE/GQA options.

The toy corpus is deterministic arithmetic-progression sequences, so a
properly trained model + a CORRECT cache produce visibly right
continuations (each token = previous + step mod V) — an eyeball check on
top of the exactness tests.

Run:  python examples/generate/generate.py --devices 8 --tp 2
      python examples/generate/generate.py --devices 8 --tp 2 --pos-impl rope --kv-heads 2 --temperature 0.7
"""

import argparse
import os
import sys
from functools import partial

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))


def make_corpus(rng, n, seq_len, vocab):
    """Arithmetic progressions mod vocab: fully learnable structure."""
    import numpy as np

    starts = rng.randint(0, vocab, n)
    steps = rng.randint(1, 4, n)
    pos = np.arange(seq_len + 1)
    return ((starts[:, None] + steps[:, None] * pos[None]) % vocab
            ).astype("int32")


def main():
    parser = argparse.ArgumentParser(
        description="ChainerMN-TPU example: LM training + KV-cache decoding")
    parser.add_argument("--devices", type=int, default=0)
    parser.add_argument("--tp", type=int, default=2)
    parser.add_argument("--vocab", type=int, default=64)
    parser.add_argument("--d-model", type=int, default=64)
    parser.add_argument("--n-heads", type=int, default=4)
    parser.add_argument("--kv-heads", type=int, default=None)
    parser.add_argument("--n-layers", type=int, default=2)
    parser.add_argument("--seq-len", type=int, default=24)
    parser.add_argument("--pos-impl", default="learned",
                        choices=["learned", "rope"])
    parser.add_argument("--steps", type=int, default=150)
    parser.add_argument("--lr", type=float, default=1e-2)
    parser.add_argument("--prompt-len", type=int, default=6)
    parser.add_argument("--max-new-tokens", type=int, default=10)
    parser.add_argument("--temperature", type=float, default=0.0)
    args = parser.parse_args()

    if args.devices:
        import jax
        jax.config.update("jax_platforms", "cpu")
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.devices}")

    import jax
    import numpy as np
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    import chainermn_tpu as mn
    from chainermn_tpu.parallel import (
        init_tp_transformer_lm, make_hybrid_shard_map_step, make_lm_generator,
        shard_pytree, state_specs_like, tp_transformer_lm_loss,
        transformer_lm_specs)
    from chainermn_tpu.topology import enable_compile_cache

    enable_compile_cache()

    n = len(jax.devices())
    dp = n // args.tp
    mesh = mn.make_nd_mesh(("data", "model"), (dp, args.tp))
    head_dim = args.d_model // args.n_heads

    params = init_tp_transformer_lm(
        jax.random.PRNGKey(0), args.vocab, args.d_model, args.n_heads,
        args.n_layers, max_len=max(args.seq_len,
                                   args.prompt_len + args.max_new_tokens),
        pos_impl=args.pos_impl, n_kv_heads=args.kv_heads)
    specs = transformer_lm_specs(params, "model")
    optimizer = optax.adam(args.lr)
    loss_fn = partial(tp_transformer_lm_loss, head_dim=head_dim,
                      axis_name="model")
    step = make_hybrid_shard_map_step(loss_fn, optimizer, mesh, params, specs,
                                      donate=False)
    p = shard_pytree(params, mesh, specs)
    st = shard_pytree(optimizer.init(params), mesh,
                      state_specs_like(optimizer, params, specs))

    rng = np.random.RandomState(0)
    for i in range(args.steps):
        tokens = make_corpus(rng, 8 * dp, args.seq_len, args.vocab)
        batch = (jax.device_put(tokens, NamedSharding(mesh, P("data"))),)
        p, st, loss = step(p, st, batch)
        if i % 30 == 0 or i == args.steps - 1:
            print(f"step {i:3d}  loss {float(loss):.4f}")

    gen = make_lm_generator(mesh, "model", head_dim=head_dim,
                            max_new_tokens=args.max_new_tokens,
                            temperature=args.temperature)
    # Eval corpus long enough to hold prompt + continuation (the model's
    # max_len already covers it).
    eval_len = max(args.seq_len, args.prompt_len + args.max_new_tokens)
    test = make_corpus(np.random.RandomState(99), 4, eval_len, args.vocab)
    prompts = test[:, : args.prompt_len]
    want = test[:, args.prompt_len: args.prompt_len + args.max_new_tokens]
    out = np.asarray(gen(p, prompts, jax.random.PRNGKey(1)))
    correct = (out == want).mean()
    for i in range(len(prompts)):
        print(f"prompt {prompts[i].tolist()} -> {out[i].tolist()} "
              f"(true continuation {want[i].tolist()})")
    print(f"continuation accuracy: {correct:.2f}"
          + ("  (sampled; exactness not expected)" if args.temperature > 0
             else ""))


if __name__ == "__main__":
    main()
