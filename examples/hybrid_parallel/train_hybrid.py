#!/usr/bin/env python
"""Hybrid DP×TP training over a 2-D ('data','model') mesh.

Reference parity: SURVEY.md §2.8 "Hybrid DP×MP" — the reference composed
2-D layouts by hand with ``CommunicatorBase.split`` sub-communicators [uv].
TPU-native the layout is one mesh and ONE jitted step: the model dimension
of the MLP weights is sharded over 'model' (tensor parallelism, psum over
ICI inside the layer), the batch over 'data' (gradient mean inserted by
autodiff), and XLA schedules both collectives inside the step.

Run:  python examples/hybrid_parallel/train_hybrid.py --devices 8 --tp 2
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))


def main():
    parser = argparse.ArgumentParser(description="ChainerMN-TPU example: hybrid DP x TP")
    parser.add_argument("--devices", type=int, default=0,
                        help="fake an N-device CPU mesh (0 = real chips)")
    parser.add_argument("--tp", type=int, default=2, help="model-axis size")
    parser.add_argument("--d-model", type=int, default=256)
    parser.add_argument("--d-hidden", type=int, default=1024)
    parser.add_argument("--batchsize", type=int, default=64, help="global batch")
    parser.add_argument("--steps", type=int, default=30)
    parser.add_argument("--lr", type=float, default=1e-2)
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
    from jax.sharding import NamedSharding, PartitionSpec as P

    import chainermn_tpu as mn
    from chainermn_tpu.parallel import (
        init_tp_mlp_params, make_hybrid_shard_map_step, shard_pytree,
        state_specs_like, tp_mlp, tp_mlp_specs)
    from chainermn_tpu.topology import enable_compile_cache

    enable_compile_cache()

    n = len(jax.devices())
    if n % args.tp:
        raise SystemExit(f"device count {n} not divisible by --tp {args.tp}")
    dp = n // args.tp
    mesh = mn.make_nd_mesh(("data", "model"), (dp, args.tp))
    print(f"mesh {dp}x{args.tp} (data x model)  global_batch={args.batchsize}")

    params = init_tp_mlp_params(
        jax.random.PRNGKey(0), args.d_model, args.d_hidden)
    specs = tp_mlp_specs("model")
    optimizer = optax.adam(args.lr)

    def loss_fn(p, batch):
        y = tp_mlp(batch[0], p, axis_name="model")
        return jnp.mean((y - batch[1]) ** 2)

    step = make_hybrid_shard_map_step(
        loss_fn, optimizer, mesh, params, specs)
    p = shard_pytree(params, mesh, specs)
    st = shard_pytree(optimizer.init(params), mesh,
                      state_specs_like(optimizer, params, specs))

    rng = np.random.RandomState(0)
    xs = rng.randn(args.batchsize, args.d_model).astype(np.float32)
    w_true = rng.randn(args.d_model, args.d_model).astype(np.float32) / args.d_model
    batch = (jax.device_put(xs, NamedSharding(mesh, P("data"))),
             jax.device_put(xs @ w_true, NamedSharding(mesh, P("data"))))

    p, st, loss = step(p, st, batch)  # compile
    t0 = time.time()
    for i in range(args.steps):
        p, st, loss = step(p, st, batch)
        if (i + 1) % 10 == 0:
            print(f"step {i + 1}  loss {float(loss):.6f}")
    dt = time.time() - t0
    print(f"{args.steps / dt:.1f} steps/sec  final loss {float(loss):.6f}")


if __name__ == "__main__":
    main()
