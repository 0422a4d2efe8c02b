#!/usr/bin/env python
# spmd-lint: disable-file=prng-constant-key — fixed seeds are the point:
# profile/probe runs must be bit-reproducible across commits to be comparable
"""Component-level timing breakdown of the transformer-LM train step.

Answers "where does the non-MXU time go" for the 135M LM config
(d1024 L8 h16 S1024 V32768 b8, bf16, flash) by timing nested subsets:

  full step  =  fwd + bwd + optimizer + dispatch
  grad       =  fwd + bwd
  fwd        =  forward loss only
  body-only  =  same minus the vocab-parallel cross entropy (mean(h) loss)
  attn micro =  flash fwd / fwd+bwd at that shape, isolated
  vocab  CE  =  logits+CE fwd / fwd+bwd, isolated

Timing barrier: HOST READBACK of a scalar that data-depends on the work
(float() of that scalar cannot return before the work is done).
All results go to stdout as one JSON dict.
"""

import json
import sys
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import optax

import chainermn_tpu as mn
from chainermn_tpu.parallel import (
    init_tp_transformer_lm, make_hybrid_shard_map_step, shard_pytree,
    state_specs_like, tp_transformer_lm_loss, transformer_lm_specs)
from chainermn_tpu.parallel.transformer import (
    _layer_norm, tp_block, vocab_parallel_logits_loss)
from jax.sharding import NamedSharding, PartitionSpec as P

VOCAB, D, H, L, S = 32768, 1024, 16, 8, 1024
B = 8
STEPS = 10


def timeit(fn, *args, steps=STEPS, scalarize=lambda out: out):
    """Dispatch `steps` executions, barrier on a host readback of the last.

    TPU executes dispatches FIFO per device, so reading back a scalar from
    the final dispatch bounds the wall-clock of all of them.
    """
    out = fn(*args)
    float(scalarize(out))  # warmup + compile barrier
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(steps):
            out = fn(*args)
        float(scalarize(out))
        best = min(best, (time.perf_counter() - t0) / steps)
    return best * 1e3  # ms


def main():
    import argparse

    parser = argparse.ArgumentParser(
        description="transformer-LM train-step component breakdown")
    parser.add_argument("--trace-out", default=None,
                        help="enable the observability tracer; write a "
                             "Chrome-trace/Perfetto JSON here")
    parser.add_argument("--metrics-out", default=None,
                        help="append the report as one record of the "
                             "versioned JSONL metrics stream")
    args = parser.parse_args()
    obs = None
    if args.trace_out or args.metrics_out:
        from chainermn_tpu import observability as obs
        obs.enable()

    dev = jax.devices()[0]
    report = {"device": dev.device_kind, "config": f"d{D} L{L} h{H} S{S} "
              f"V{VOCAB} b{B} bf16"}
    n_chips = len(jax.devices())
    mesh = mn.make_nd_mesh(("data", "model"), (n_chips, 1))
    params = init_tp_transformer_lm(
        jax.random.PRNGKey(0), VOCAB, D, H, L, max_len=S, dtype=jnp.bfloat16)
    # Host copies: device_put can alias on-device leaves, so donation in the
    # full-step loop would otherwise delete `params` itself.
    params = jax.tree_util.tree_map(np.asarray, params)
    specs = transformer_lm_specs(params, "model")
    loss_fn = partial(tp_transformer_lm_loss, head_dim=D // H,
                      axis_name="model", attn_impl="flash")
    optimizer = optax.sgd(1e-2)
    step = make_hybrid_shard_map_step(
        loss_fn, optimizer, mesh, params, specs, data_axis="data",
        batch_spec=P("data"))
    p = shard_pytree(params, mesh, specs)
    st = shard_pytree(optimizer.init(params), mesh,
                     state_specs_like(optimizer, params, specs))
    tokens = np.random.RandomState(0).randint(
        0, VOCAB, (B * n_chips, S + 1)).astype(np.int32)
    batch = (jax.device_put(tokens, NamedSharding(mesh, P("data"))),)

    # --- full step (threads the donated state from call to call) -----------
    pp, sst = p, st
    pp, sst, loss, *_ = step(pp, sst, batch)
    float(loss)
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(STEPS):
            pp, sst, loss, *_ = step(pp, sst, batch)
        float(loss)
        best = min(best, (time.perf_counter() - t0) / STEPS)
    report["full_step_ms"] = best * 1e3
    p = shard_pytree(params, mesh, specs)  # donated p/st are gone; rebuild
    st = shard_pytree(optimizer.init(params), mesh,
                      state_specs_like(optimizer, params, specs))

    # --- fwd+bwd only (no optimizer/dispatch of update) --------------------
    grad_fn = jax.jit(jax.value_and_grad(lambda pp: loss_fn(pp, batch_local))
                      if False else jax.value_and_grad(
                          lambda pp, b: loss_fn(pp, b)))
    # loss_fn references axis_name="model": must run under shard_map/jit with
    # mesh axes. Use a 1-device-model trick: wrap with jax.jit over the mesh.
    from jax import shard_map
    smapped = shard_map(
        jax.value_and_grad(lambda pp, b: loss_fn(pp, b)),
        mesh=mesh, in_specs=(specs, (P("data"),)),
        out_specs=(P(), specs), check_vma=False)
    gfn = jax.jit(smapped)
    report["fwd_bwd_ms"] = timeit(gfn, p, batch,
                                  scalarize=lambda o: o[0])

    # --- fwd only ----------------------------------------------------------
    fwd = jax.jit(shard_map(loss_fn, mesh=mesh,
                            in_specs=(specs, (P("data"),)), out_specs=P(),
                            check_vma=False))
    report["fwd_ms"] = timeit(fwd, p, batch)

    # --- body only: transformer blocks without the vocab CE ----------------
    def body_loss(pp, b):
        tokens = b[0]
        inputs = tokens[:, :-1]
        from chainermn_tpu.parallel.tensor_parallel import (
            vocab_parallel_embedding)
        x = vocab_parallel_embedding(inputs, pp["embed"], axis_name="model")
        x = x * (pp["embed"].shape[1] ** 0.5)
        x = x + pp["pos_embed"][: x.shape[1]][None]
        for blk in pp["blocks"]:
            x = tp_block(x, blk, head_dim=D // H, axis_name="model",
                         causal=True, attn_impl="flash")
        x = _layer_norm(x, pp["lnf_scale"], pp["lnf_bias"])
        return jnp.mean(x.astype(jnp.float32))

    bfwd = jax.jit(shard_map(body_loss, mesh=mesh,
                             in_specs=(specs, (P("data"),)), out_specs=P(),
                             check_vma=False))
    report["body_fwd_ms"] = timeit(bfwd, p, batch)
    bgrad = jax.jit(shard_map(jax.value_and_grad(body_loss), mesh=mesh,
                              in_specs=(specs, (P("data"),)),
                              out_specs=(P(), specs), check_vma=False))
    report["body_fwd_bwd_ms"] = timeit(bgrad, p, batch,
                                       scalarize=lambda o: o[0])

    # --- vocab CE micro: h -> logits -> loss -------------------------------
    h = jax.device_put(
        np.random.RandomState(1).randn(B, S, D).astype(jnp.bfloat16))
    tgt = jax.device_put(tokens[:B, 1:])
    table = jax.device_put(np.asarray(params["embed"], dtype=jnp.bfloat16))

    def ce(hh, tab):
        logits = jnp.einsum("bsd,vd->bsv", hh, tab,
                            preferred_element_type=jnp.float32)
        m = jax.lax.stop_gradient(logits).max(-1)
        sumexp = jnp.exp(logits - m[..., None]).sum(-1)
        picked = jnp.take_along_axis(logits, tgt[..., None], axis=-1)[..., 0]
        return jnp.mean(m + jnp.log(sumexp) - picked)

    cefwd = jax.jit(ce)
    report["vocab_ce_fwd_ms"] = timeit(cefwd, h, table)
    cegrad = jax.jit(jax.value_and_grad(ce, argnums=(0, 1)))
    report["vocab_ce_fwd_bwd_ms"] = timeit(cegrad, h, table,
                                           scalarize=lambda o: o[0])

    # --- attention micro: flash fwd / fwd+bwd ------------------------------
    from chainermn_tpu.ops.flash_attention import flash_attention
    rs = np.random.RandomState(2)
    q = jax.device_put(rs.randn(B, S, H, D // H).astype(jnp.bfloat16))
    k = jax.device_put(rs.randn(B, S, H, D // H).astype(jnp.bfloat16))
    v = jax.device_put(rs.randn(B, S, H, D // H).astype(jnp.bfloat16))

    def attn_all_layers(qq, kk, vv):  # L layers' worth of attention
        out = 0.0
        for i in range(L):
            out = out + flash_attention(qq + i * 0.0, kk, vv, causal=True)
        return jnp.mean(out.astype(jnp.float32))

    afwd = jax.jit(attn_all_layers)
    report["attn_x8_flash_fwd_ms"] = timeit(afwd, q, k, v)
    agrad = jax.jit(jax.value_and_grad(attn_all_layers, argnums=(0, 1, 2)))
    report["attn_x8_flash_fwd_bwd_ms"] = timeit(agrad, q, k, v,
                                                scalarize=lambda o: o[0])

    def attn_all_layers_xla(qq, kk, vv):
        out = 0.0
        for i in range(L):
            s = jnp.einsum("bqhd,bkhd->bhqk", qq + i * 0.0, kk,
                           preferred_element_type=jnp.float32) / ((D // H) ** 0.5)
        mask = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
        s = jnp.where(mask[None, None], s, -1e30)
        pr = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bhqk,bkhd->bqhd", pr.astype(vv.dtype), vv)
        return jnp.mean(out.astype(jnp.float32))

    report["attn_x1_xla_fwd_ms"] = timeit(jax.jit(attn_all_layers_xla), q, k, v)

    # --- derived -----------------------------------------------------------
    report["optimizer_dispatch_ms"] = round(
        report["full_step_ms"] - report["fwd_bwd_ms"], 2)
    report["ce_share_of_grad_ms"] = round(
        report["fwd_bwd_ms"] - report["body_fwd_bwd_ms"], 2)
    for k_ in list(report):
        if isinstance(report[k_], float):
            report[k_] = round(report[k_], 2)
    if obs is not None:
        for k_, v in report.items():
            if isinstance(v, (int, float)):
                obs.set_gauge(f"profile_lm/{k_}", float(v))
        if args.trace_out:
            obs.export_chrome_trace(args.trace_out)
            print(f"profile_lm: trace written to {args.trace_out}",
                  file=sys.stderr)
        if args.metrics_out:
            w = obs.MetricsWriter(args.metrics_out)
            w.write(dict(report), kind="profile_lm")
            w.close()
            print(f"profile_lm: metrics appended to {args.metrics_out}",
                  file=sys.stderr)
    print(json.dumps(report, indent=2))


if __name__ == "__main__":
    main()
