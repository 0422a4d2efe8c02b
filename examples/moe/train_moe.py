#!/usr/bin/env python
"""Expert-parallel (MoE) training end-to-end: Switch-style top-1 routing.

Beyond-reference workload (SURVEY.md §2.8: EP "absent" — the reference only
shipped the ``alltoall`` substrate): a classifier whose middle layer is a
top-1 mixture-of-experts MLP, experts sharded one-per-device, tokens riding
TWO ``all_to_all`` collectives per step, trained in ONE jitted SPMD step.

The same mesh axis carries data parallelism (tokens sharded) AND expert
parallelism (expert weights sharded) — the composition falls out of
``make_hybrid_shard_map_step``: expert-sharded params are axis-varying so
autodiff leaves their gradients local (each device owns its experts), while
replicated params get the AD-inserted cross-rank psum.

The load-balance auxiliary loss (Switch eq. 4) is what keeps routing from
collapsing onto one expert — run with ``--aux-weight 0`` to watch it
collapse (max expert fraction → 1), the failure mode the loss exists for.

Run:  python examples/moe/train_moe.py --devices 8
      python examples/moe/train_moe.py --devices 8 --aux-weight 0
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))


def make_dataset(rng, n, d_in, num_classes):
    """Clustered synthetic data: class = nearest of C random centroids, so
    a router has real structure to specialize experts on."""
    centroids = rng.randn(num_classes, d_in).astype("float32") * 2.0
    labels = rng.randint(0, num_classes, n)
    xs = centroids[labels] + rng.randn(n, d_in).astype("float32")
    return xs.astype("float32"), labels.astype("int32")


def main():
    parser = argparse.ArgumentParser(
        description="ChainerMN-TPU example: expert-parallel MoE training")
    parser.add_argument("--devices", type=int, default=0,
                        help="fake an N-device CPU mesh (0 = real chips)")
    parser.add_argument("--d-in", type=int, default=16)
    parser.add_argument("--d-model", type=int, default=32)
    parser.add_argument("--d-hidden", type=int, default=64)
    parser.add_argument("--num-classes", type=int, default=8)
    parser.add_argument("--experts-per-device", type=int, default=1)
    parser.add_argument("--batchsize", type=int, default=256,
                        help="global tokens per step")
    parser.add_argument("--steps", type=int, default=60)
    parser.add_argument("--lr", type=float, default=3e-2)
    parser.add_argument("--aux-weight", type=float, default=0.01)
    parser.add_argument("--capacity-factor", type=float, default=1.5)
    parser.add_argument("--router-topk", type=int, default=1,
                        choices=[1, 2],
                        help="1 = Switch top-1, 2 = GShard top-2 routing")
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
        init_moe_mlp_params, make_hybrid_shard_map_step, moe_mlp,
        moe_mlp_specs, shard_pytree, state_specs_like)
    from chainermn_tpu.topology import enable_compile_cache

    enable_compile_cache()

    comm = mn.create_communicator("xla")
    mesh, ax = comm.mesh, comm.axis_name
    n_dev = comm.size
    e = args.experts_per_device * n_dev

    rng = jax.random.PRNGKey(0)
    k_in, k_moe, k_head = jax.random.split(rng, 3)
    params = {
        "w_in": jax.random.normal(k_in, (args.d_in, args.d_model)) * 0.3,
        "moe": init_moe_mlp_params(k_moe, args.d_model, args.d_hidden, e),
        "w_head": jax.random.normal(k_head, (args.d_model, args.num_classes))
                  * 0.3,
    }
    specs = {"w_in": P(), "moe": moe_mlp_specs(ax), "w_head": P()}

    def loss_fn(p, batch):
        xs, ys = batch
        h = jnp.tanh(xs @ p["w_in"])
        y, aux = moe_mlp(h, p["moe"], axis_name=ax, num_experts=e,
                         capacity_factor=args.capacity_factor,
                         router_topk=args.router_topk)
        logits = y @ p["w_head"]
        logp = jax.nn.log_softmax(logits.astype(jnp.float32))
        ce = -jnp.mean(jnp.take_along_axis(logp, ys[:, None], 1))
        acc = (logits.argmax(-1) == ys).mean()
        # routing fractions for observability (max fraction → collapse)
        probs = jax.nn.softmax(
            (h @ p["moe"]["router"]).astype(jnp.float32), -1)
        frac = jax.lax.pmean(
            jnp.mean(jax.nn.one_hot(probs.argmax(-1), e), 0), ax)
        return ce + args.aux_weight * aux, {
            "ce": ce, "aux": aux, "accuracy": acc, "max_frac": frac.max()}

    optimizer = optax.adam(args.lr)
    step = make_hybrid_shard_map_step(
        loss_fn, optimizer, mesh, params, specs, data_axis=ax,
        batch_spec=P(ax), has_aux=True, donate=False)

    p = shard_pytree(params, mesh, specs)
    st = shard_pytree(optimizer.init(params),
                      mesh, state_specs_like(optimizer, params, specs))

    data_rng = np.random.RandomState(0)
    xs, ys = make_dataset(data_rng, args.batchsize * 4, args.d_in,
                          args.num_classes)
    t0 = time.time()
    for i in range(args.steps):
        lo = (i * args.batchsize) % (len(xs) - args.batchsize + 1)
        batch = tuple(
            jax.device_put(a[lo:lo + args.batchsize],
                           NamedSharding(mesh, P(ax)))
            for a in (xs, ys))
        p, st, loss, aux = step(p, st, batch)
        if comm.rank == 0 and (i % 10 == 0 or i == args.steps - 1):
            print(f"step {i:3d}  loss {float(loss):.4f}  "
                  f"ce {float(aux['ce']):.4f}  acc {float(aux['accuracy']):.3f}  "
                  f"aux {float(aux['aux']):.3f}  "
                  f"max_expert_frac {float(aux['max_frac']):.3f}")
    if comm.rank == 0:
        print(f"{e} experts on {n_dev} devices, "
              f"{args.steps} steps in {time.time() - t0:.1f}s")


if __name__ == "__main__":
    main()
