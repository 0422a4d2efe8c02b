#!/usr/bin/env python
"""Model-parallel MLP split across chips — BASELINE config #5.

Reference parity: ``examples/model_parallel/`` [uv] (SURVEY.md §2.9): an
MLP split over two ranks with ``chainermn.functions.send/recv`` inside
``MultiNodeChainList``, plus ``create_empty_dataset`` feeding the
non-input rank.

Two faces are demonstrated:
1. MultiNodeChainList — the reference-shaped graph container (one jitted
   differentiable program).
2. Raw SPMD send/recv — the same split written with
   ``chainermn_tpu.functions`` inside shard_map, activations crossing chips
   over ICI with autodiff routing gradients back (reference §3.5 semantics).

Run:  python examples/model_parallel/train_model_parallel.py --devices 8
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))


def main():
    parser = argparse.ArgumentParser(description="ChainerMN-TPU: model parallel")
    parser.add_argument("--devices", type=int, default=0)
    parser.add_argument("--steps", type=int, default=40)
    parser.add_argument("--hidden", type=int, default=32)
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
    from jax.sharding import PartitionSpec as P

    import chainermn_tpu as mn
    from chainermn_tpu import functions as F
    from chainermn_tpu.links import MultiNodeChainList
    from chainermn_tpu.topology import enable_compile_cache

    enable_compile_cache()

    comm = mn.create_communicator("xla")
    mesh = comm.mesh
    print(f"chips: {comm.size}")
    if comm.size < 2:
        raise SystemExit(
            "model parallelism needs at least 2 ranks to place stages on; "
            "run with --devices 2 (or more) to fake a multi-chip mesh on "
            "one host")

    rng = np.random.RandomState(0)
    xs = rng.randn(64, 16).astype(np.float32)
    ys = (np.sin(xs.sum(axis=1, keepdims=True)) > 0).astype(np.float32)
    # non-input ranks iterate a placeholder of the same length (reference:
    # create_empty_dataset feeding rank 1)
    empty = mn.create_empty_dataset(list(range(len(xs))))
    assert len(empty) == len(xs)

    def dense(key, n_in, n_out):
        k = jax.random.PRNGKey(key)
        return {"w": jax.random.normal(k, (n_in, n_out)) * 0.3,
                "b": jnp.zeros((n_out,))}

    def stage0(p, x):
        return jnp.tanh(x @ p["w"] + p["b"])

    def stage1(p, h):
        return h @ p["w"] + p["b"]

    # ---- face 1: MultiNodeChainList ----
    mnc = MultiNodeChainList(comm)
    mnc.add_link(stage0, dense(0, 16, args.hidden), rank=0,
                 rank_in=None, rank_out=1)
    mnc.add_link(stage1, dense(1, args.hidden, 1), rank=1,
                 rank_in=0, rank_out=None)

    def loss_chain(plist):
        logits = mnc(jnp.asarray(xs), params=plist)
        return jnp.mean(optax.sigmoid_binary_cross_entropy(logits, ys))

    opt = optax.adam(1e-2)
    # Fused-jit face: one jit argument → the default uncommitted params()
    # (params(placed=True) would pin each stage's pytree to its chip).
    plist = mnc.params()
    state = opt.init(plist)

    @jax.jit
    def step_chain(pl, st):
        l, g = jax.value_and_grad(loss_chain)(pl)
        up, st = opt.update(g, st, pl)
        return optax.apply_updates(pl, up), st, l

    for i in range(args.steps):
        plist, state, loss = step_chain(plist, state)
        loss.block_until_ready()
        if i in (0, args.steps - 1):
            print(f"[chain-list] step {i}  loss {float(loss):.4f}")

    # ---- face 2: raw SPMD send/recv over ICI ----
    # Stage parameters are stacked over the mesh axis: rank 0's slab holds
    # stage-0 weights, rank 1's slab stage-1 weights (padded), other ranks
    # idle — the minimal faithful port of the reference's 2-process MLP.
    w0, w1 = dense(0, 16, args.hidden), dense(1, args.hidden, 1)

    def spmd_fwd(w0_, b0_, w1_, b1_, x):
        h = jnp.tanh(x @ w0_[0] + b0_[0])          # rank 0 computes...
        h = F.send(h, dest=1, source=0)            # ...ships over ICI...
        logits = h @ w1_[0] + b1_[0]               # ...rank 1 finishes
        out = F.send(logits, dest=0, source=1)     # result home to rank 0
        return out

    def spmd_loss(w0_, b0_, w1_, b1_, x, y):
        out = spmd_fwd(w0_, b0_, w1_, b1_, x)
        per = optax.sigmoid_binary_cross_entropy(out, y)
        idx = jax.lax.axis_index("mn")
        valid = jnp.where(idx == 0, per.mean(), 0.0)
        return jax.lax.psum(valid, "mn")

    smapped = jax.jit(jax.shard_map(
        jax.value_and_grad(spmd_loss, argnums=(0, 1, 2, 3)),
        mesh=mesh,
        in_specs=(P("mn"), P("mn"), P("mn"), P("mn"), P(), P()),
        out_specs=(P(), (P("mn"), P("mn"), P("mn"), P("mn")))))

    n = comm.size
    stack = lambda a: jnp.broadcast_to(a[None], (n,) + a.shape)
    w0s, b0s = stack(w0["w"]), stack(w0["b"])
    w1s, b1s = stack(w1["w"]), stack(w1["b"])
    for i in range(args.steps):
        loss, grads = smapped(w0s, b0s, w1s, b1s, jnp.asarray(xs), jnp.asarray(ys))
        w0s, b0s, w1s, b1s = (
            a - 0.05 * g for a, g in zip((w0s, b0s, w1s, b1s), grads))
        float(loss)
        if i in (0, args.steps - 1):
            print(f"[spmd p2p]   step {i}  loss {float(loss):.4f}")


if __name__ == "__main__":
    main()
