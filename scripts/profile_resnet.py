#!/usr/bin/env python
# spmd-lint: disable-file=prng-constant-key — fixed seeds are the point:
# profile/probe runs must be bit-reproducible across commits to be comparable
"""Where does ResNet-50's step time go on the real chip?

Scan-chained single-dispatch timings (the recipe of
scripts/micro_lm.py): full step, fwd, fwd+bwd, the 3-channel stem conv in
isolation, and the stem replaced by a 64-channel-input equivalent — the
difference quantifies how much the MXU-hostile 3-channel contraction costs.
"""

import json
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import optax

import chainermn_tpu as mn
from chainermn_tpu.models.mlp import cross_entropy_loss
from chainermn_tpu.models.resnet import ARCHS

B, IMG = 128, 224
PEAK = 197e12
N = 40


def chain_step(step_fn, variables, opt_state, batch):
    """One jit: scan N train steps, thread state, return final loss."""
    @jax.jit
    def run(v, o, b):
        def body(carry, _):
            vv, oo = carry
            vv, oo, loss, _ = step_fn(vv, oo, b)
            return (vv, oo), loss
        (_, _), losses = jax.lax.scan(body, (v, o), None, length=N)
        return losses[-1]
    return run


def bench(tag, fn, args, flops=None):
    from chainermn_tpu.observability import set_gauge, span

    with span(f"profile/{tag}", cat="bench"):  # no-op unless tracing on
        out = fn(*args)
        float(out)
        best = 1e9
        for _ in range(3):
            t0 = time.perf_counter()
            float(fn(*args))
            best = min(best, (time.perf_counter() - t0 - 0.1) / N)
    ms = best * 1e3
    line = {"ms": round(ms, 3)}
    if flops:
        line["mfu"] = round(flops / best / PEAK, 3)
    set_gauge(f"profile_resnet/{tag}_ms", ms)
    print(f"{tag}: {json.dumps(line)}", flush=True)
    return ms


def main():
    import argparse

    parser = argparse.ArgumentParser(
        description="ResNet-50 step-time component breakdown")
    parser.add_argument("--trace-out", default=None,
                        help="enable the observability tracer; write a "
                             "Chrome-trace/Perfetto JSON here")
    parser.add_argument("--metrics-out", default=None,
                        help="append the component timings as one record "
                             "of the versioned JSONL metrics stream")
    args = parser.parse_args()
    obs = None
    if args.trace_out or args.metrics_out:
        from chainermn_tpu import observability as obs
        obs.enable()

    comm = mn.create_communicator("xla")
    mesh = comm.mesh
    model = ARCHS["resnet50"](stem_strides=2)
    variables = dict(model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, IMG, IMG, 3)), train=False))
    optimizer = mn.create_multi_node_optimizer(
        optax.chain(optax.add_decayed_weights(1e-4),
                    optax.sgd(0.1, momentum=0.9)), comm)

    def loss_and_metrics(logits, batch):
        return cross_entropy_loss(logits, batch[1]), {}

    # the UNJITTED spmd body so we can scan it — rebuild by calling the
    # factory pieces ourselves via make_flax_train_step's returned fn is
    # jitted; scanning a jitted fn inside jit is fine (inlined).
    step = mn.make_flax_train_step(model, loss_and_metrics, optimizer,
                                   mesh=mesh, donate=False)
    variables = mn.replicate(variables, mesh)
    opt_state = mn.replicate(optimizer.init(variables["params"]), mesh)
    rng = np.random.RandomState(0)
    batch = mn.shard_batch(
        (rng.randn(B, IMG, IMG, 3).astype(np.float32),
         rng.randint(0, 1000, B).astype(np.int32)), mesh)

    train_flops = 3 * 4.1e9 * B  # analytic: fwd 4.1 GFLOP/img, train ~3x
    bench("full_step", chain_step(step, variables, opt_state, batch),
          (variables, opt_state, batch), train_flops)

    # fwd-only
    params = variables["params"]
    stats = variables["batch_stats"]

    def fwd_loss(p, b):
        out, _ = model.apply({"params": p, "batch_stats": stats},
                             b[0], train=True, mutable=["batch_stats"])
        return cross_entropy_loss(out, b[1])

    @jax.jit
    def fwd_chain(p, b):
        def body(acc, _):
            # acc*0 into the image defeats loop-invariant hoisting
            bb = (b[0] + acc * 0.0, b[1])
            return acc + fwd_loss(p, bb) * 1e-6, None
        out, _ = jax.lax.scan(body, jnp.float32(0.0), None, length=N)
        return out
    bench("fwd_only", fwd_chain, (params, batch), 4.1e9 * B)

    @jax.jit
    def grad_chain(p, b):
        def body(c, _):
            l, g = jax.value_and_grad(fwd_loss)(c, b)
            c2 = jax.tree_util.tree_map(lambda a, d: a - 0.0 * d, c, g)
            return c2, l
        _, ls = jax.lax.scan(body, p, None, length=N)
        return ls[-1]
    bench("fwd_bwd", grad_chain, (params, batch), 3 * 4.1e9 * B)

    # stem in isolation: 7x7 s2 conv on 3 channels + the same conv on a
    # 64-channel input (MXU-friendly contraction) for contrast
    import flax.linen as nn
    x3 = jax.device_put(rng.randn(B, IMG, IMG, 3).astype(jnp.bfloat16))
    x48 = jax.device_put(
        rng.randn(B, IMG // 4, IMG // 4, 48).astype(jnp.bfloat16))

    stem3 = nn.Conv(64, (7, 7), strides=(2, 2), use_bias=False,
                    dtype=jnp.bfloat16)
    v3 = stem3.init(jax.random.PRNGKey(1), x3[:1])
    stem48 = nn.Conv(64, (2, 2), strides=(1, 1), use_bias=False,
                     dtype=jnp.bfloat16)
    v48 = stem48.init(jax.random.PRNGKey(1), x48[:1])

    def conv_chain(mod, v, x):
        @jax.jit
        def run(v, x):
            def body(acc, _):
                y = mod.apply(v, x + acc * 0.0)
                return acc + jnp.mean(y.astype(jnp.float32)) * 1e-6, None
            out, _ = jax.lax.scan(body, jnp.float32(0.0), None, length=N)
            return out
        return run

    stem_flops = 2 * B * 112 * 112 * 64 * 49 * 3
    bench("stem_conv_7x7s2_3ch_fwd", conv_chain(stem3, v3, x3), (v3, x3),
          stem_flops)
    s2d_flops = 2 * B * 56 * 56 * 64 * 4 * 48
    bench("conv_2x2_48ch_fwd(s2d-like)", conv_chain(stem48, v48, x48),
          (v48, x48), s2d_flops)

    if obs is not None:
        if args.trace_out:
            obs.export_chrome_trace(args.trace_out)
            print(f"profile_resnet: trace written to {args.trace_out}",
                  flush=True)
        if args.metrics_out:
            # every bench() above published a profile_resnet/<tag>_ms gauge
            gauges = {k: v for k, v in obs.get_tracer().gauges().items()
                      if k.startswith("profile_resnet/")}
            w = obs.MetricsWriter(args.metrics_out)
            w.write(gauges, kind="profile_resnet")
            w.close()
            print(f"profile_resnet: metrics appended to {args.metrics_out}",
                  flush=True)


if __name__ == "__main__":
    main()
