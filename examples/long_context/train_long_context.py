#!/usr/bin/env python
"""Long-context LM training with sequence parallelism (ring attention).

The rebuild brief's long-context pillar, end-to-end (the 2017 reference
predates all of this — SURVEY.md §5): the SEQUENCE is sharded across the
mesh, each chip holds ``S/P`` tokens of every layer's activations and
``S/P`` keys/values, and K/V blocks rotate the ICI ring inside one jitted
step (``parallel.ring_attention``, flash local blocks on TPU).  Params are
replicated; gradient sync is the same AD-inserted psum as data parallelism.
Max trainable context grows LINEARLY with chips at constant per-chip HBM.

Run:  python examples/long_context/train_long_context.py --devices 8 --seq-len 512
      python examples/long_context/train_long_context.py --devices 8 --seq-len 2048 --attn-impl xla
"""

import argparse
import os
import sys
import time
from functools import partial

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))


def main():
    parser = argparse.ArgumentParser(
        description="ChainerMN-TPU example: sequence-parallel long-context LM")
    parser.add_argument("--devices", type=int, default=0,
                        help="fake an N-device CPU mesh (0 = real chips)")
    parser.add_argument("--vocab", type=int, default=256)
    parser.add_argument("--d-model", type=int, default=64)
    parser.add_argument("--n-heads", type=int, default=4)
    parser.add_argument("--n-layers", type=int, default=2)
    parser.add_argument("--seq-len", type=int, default=512)
    parser.add_argument("--batchsize", type=int, default=2)
    parser.add_argument("--steps", type=int, default=30)
    parser.add_argument("--lr", type=float, default=1e-2)
    parser.add_argument("--attn-impl", default="xla", choices=["xla", "flash"],
                        help="flash = Pallas kernel (TPU); xla is exact too")
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
    from chainermn_tpu._compat import shard_map
    from chainermn_tpu.parallel import (
        init_tp_transformer_lm, sp_transformer_lm_loss)
    from chainermn_tpu.topology import enable_compile_cache

    enable_compile_cache()

    n = len(jax.devices())
    if args.seq_len % n:
        raise SystemExit(f"--seq-len {args.seq_len} not divisible by {n} chips")
    mesh = mn.make_mesh(axis_name="sp")
    print(f"{n} chips, {args.seq_len} tokens → {args.seq_len // n} "
          f"tokens/chip  attn={args.attn_impl}")

    params = init_tp_transformer_lm(
        jax.random.PRNGKey(0), args.vocab, args.d_model, args.n_heads,
        args.n_layers, max_len=args.seq_len)
    optimizer = optax.adam(args.lr)
    loss_fn = partial(sp_transformer_lm_loss,
                      head_dim=args.d_model // args.n_heads,
                      axis_name="sp", attn_impl=args.attn_impl)

    def spmd(p, opt_state, batch):
        def global_loss(pp):
            return jax.lax.pmean(loss_fn(pp, batch), "sp")

        loss, grads = jax.value_and_grad(global_loss)(p)
        updates, opt_state = optimizer.update(grads, opt_state, p)
        return optax.apply_updates(p, updates), opt_state, loss

    seq_spec = (P(None, "sp"), P(None, "sp"))
    # Interpreted (off-TPU) Pallas flash can't propagate varying-axes;
    # the compiled TPU path keeps the check (same policy as the factories).
    interpreted_flash = (args.attn_impl == "flash"
                         and jax.default_backend() != "tpu")
    step = jax.jit(shard_map(
        spmd, mesh=mesh,
        in_specs=(P(), P(), seq_spec), out_specs=(P(), P(), P()),
        check_vma=not interpreted_flash))

    p = mn.replicate(params, mesh)
    st = mn.replicate(optimizer.init(params), mesh)

    rng = np.random.RandomState(0)
    tokens = rng.randint(0, args.vocab,
                         (args.batchsize, args.seq_len + 1)).astype(np.int32)
    inputs, targets = tokens[:, :-1], tokens[:, 1:]  # shift BEFORE sharding
    batch = tuple(jax.device_put(t, NamedSharding(mesh, P(None, "sp")))
                  for t in (inputs, targets))

    p, st, loss = step(p, st, batch)  # compile
    print(f"initial loss {float(loss):.4f}  (log V = {np.log(args.vocab):.4f})")
    t0 = time.time()
    for i in range(args.steps):
        p, st, loss = step(p, st, batch)
        if (i + 1) % 10 == 0:
            print(f"step {i + 1}  loss {float(loss):.4f}")
    dt = time.time() - t0
    tok_s = args.steps * args.batchsize * args.seq_len / dt
    print(f"{tok_s:,.0f} tokens/sec  final loss {float(loss):.4f}")


if __name__ == "__main__":
    main()
