#!/usr/bin/env python
"""Decoder-only transformer LM with DP×TP over a ('data','model') mesh.

Beyond-reference workload (SURVEY.md §2.8: the reference could only express
TP "manually"; it had no transformer): Megatron-style sharding — heads and
MLP columns over the model axis, vocab-parallel embedding + loss (the full
logits never materialize), flash attention optional — composed with data
parallelism in ONE jitted step via make_hybrid_shard_map_step.

Run:  python examples/transformer/train_transformer.py --devices 8 --tp 2
      python examples/transformer/train_transformer.py --devices 8 --tp 4 --attn-impl flash
"""

import argparse
import os
import sys
import time
from functools import partial

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))


def main(argv=None):
    parser = argparse.ArgumentParser(
        description="ChainerMN-TPU example: DP x TP transformer LM")
    parser.add_argument("--devices", type=int, default=0,
                        help="fake an N-device CPU mesh (0 = real chips)")
    parser.add_argument("--tp", type=int, default=2, help="model-axis size")
    parser.add_argument("--vocab", type=int, default=256)
    parser.add_argument("--d-model", type=int, default=64)
    parser.add_argument("--n-heads", type=int, default=4)
    parser.add_argument("--kv-heads", type=int, default=None,
                        help="GQA: fewer KV heads than Q heads (must stay "
                             "divisible by --tp)")
    parser.add_argument("--n-layers", type=int, default=2)
    parser.add_argument("--seq-len", type=int, default=32)
    parser.add_argument("--batchsize", type=int, default=32, help="global batch")
    parser.add_argument("--steps", type=int, default=60)
    parser.add_argument("--lr", type=float, default=1e-2)
    parser.add_argument("--attn-impl", default="auto",
                        choices=["auto", "xla", "flash"])
    parser.add_argument("--ce-impl", default="auto",
                        choices=["auto", "xla", "fused"],
                        help="LM-head loss path; 'fused' = the Pallas "
                             "online-softmax kernels (big-vocab heads)")
    args = parser.parse_args(argv)

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
        init_tp_transformer_lm, make_hybrid_shard_map_step, shard_pytree,
        state_specs_like, tp_transformer_lm_loss, transformer_lm_specs)
    from chainermn_tpu.topology import enable_compile_cache

    enable_compile_cache()
    n = len(jax.devices())
    if n % args.tp:
        raise SystemExit(f"device count {n} not divisible by --tp {args.tp}")
    dp = n // args.tp
    mesh = mn.make_nd_mesh(("data", "model"), (dp, args.tp))
    print(f"mesh {dp}x{args.tp} (data x model)  "
          f"LM: V={args.vocab} D={args.d_model} H={args.n_heads} "
          f"L={args.n_layers} S={args.seq_len}  attn={args.attn_impl}")

    params = init_tp_transformer_lm(
        jax.random.PRNGKey(0), args.vocab, args.d_model, args.n_heads,
        args.n_layers, max_len=args.seq_len, n_kv_heads=args.kv_heads)
    specs = transformer_lm_specs(params, "model")
    optimizer = optax.adam(args.lr)
    loss_fn = partial(tp_transformer_lm_loss,
                      head_dim=args.d_model // args.n_heads,
                      axis_name="model", attn_impl=args.attn_impl,
                      ce_impl=args.ce_impl)

    step = make_hybrid_shard_map_step(loss_fn, optimizer, mesh, params, specs)
    p = shard_pytree(params, mesh, specs)
    st = shard_pytree(optimizer.init(params), mesh,
                      state_specs_like(optimizer, params, specs))

    # tiny synthetic corpus: fixed random token sequences to memorize
    rng = np.random.RandomState(0)
    tokens = rng.randint(0, args.vocab,
                         (args.batchsize, args.seq_len + 1)).astype(np.int32)
    batch = (jax.device_put(tokens, NamedSharding(mesh, P("data"))),)

    p, st, loss = step(p, st, batch)  # compile
    print(f"initial loss {float(loss):.4f}  (log V = {np.log(args.vocab):.4f})")
    t0 = time.time()
    for i in range(args.steps):
        p, st, loss = step(p, st, batch)
        if (i + 1) % 20 == 0:
            print(f"step {i + 1}  loss {float(loss):.4f}")
    dt = time.time() - t0
    tok_s = args.steps * args.batchsize * args.seq_len / dt
    dev = jax.devices()[0]
    print(f"{tok_s:,.0f} tokens/sec on {n} x {dev.platform} "
          f"({dev.device_kind})  final loss {float(loss):.4f}")


if __name__ == "__main__":
    main()
