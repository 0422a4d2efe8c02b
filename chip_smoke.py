#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process, one TPU v5e chip by default.  Drives the training and serving
main paths once through the entry points a user would call, at the full
width of the repo's headline configs (depth is what it is there: ResNet-50,
and the 8-layer 135M LM), with weights made from ``--seed``:

* ``kernel-parity``   compiled Pallas kernels (flash fwd+bwd, flash-decode,
                      3x3 conv backward) against their dense oracles at
                      small shapes — what ``tests_tpu/`` used to hold;
* ``train-resnet50``  ``examples/imagenet/train_imagenet.py`` in-process:
                      ``init_distributed`` → ``create_communicator("xla")``
                      → the flax train step, 224², per-chip batch 128, bf16;
* ``train-lm``        the TP transformer LM (V32768 d1024 L8 h8×128 S1024
                      b8, bf16) through ``make_hybrid_shard_map_step`` on a
                      (1, 1) mesh with flash attention and the fused CE;
* ``serve``           ``ServingEngine`` at the same width: 4 slots, prompt
                      512, 64 new tokens, 8 staggered requests, one checked
                      token-exact against ``make_lm_generator`` greedy.

``--chips 4`` runs ONLY the cross-chip phase and what it is compared with:
eager collectives against the numpy oracle, the 4-chip DP ResNet step
against the same global batch on a 1-device mesh, one 2×2 DP×TP LM step
against the 1×1 step — with placement asserted, not just results.

Every phase prints one JSON line; any failure is a non-zero exit (nothing
is caught and carried past).  The last line of stdout is exactly

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

Without an accelerator the script exits non-zero and prints no result:
there is no CPU path here (``main(..., _allow_cpu=True, _sizes=...)`` is
the test-only rehearsal hook of tests/test_chip_smoke.py — not a flag,
not an environment variable).
"""

import argparse
import contextlib
import importlib.util
import json
import os
import sys
import time
from functools import partial

REPO = os.path.dirname(os.path.abspath(__file__))

#: The real sizes: the repo's own headline configs (ResNet-50 and the
#: 135M LM), never cut in width.
SIZES = {
    "parity": {"flash": (2, 256, 4, 64), "decode": (2, 256, 4, 128),
               "conv": (8, 28, 28, 128)},
    "resnet": {"arch": "resnet50", "image": 224, "batch": 128, "steps": 5,
               "classes": 1000},
    "lm": {"vocab": 32768, "d_model": 1024, "n_layers": 8, "n_heads": 8,
           "seq": 1024, "batch": 8, "steps": 5},
    "serve": {"n_slots": 4, "prompt": 512, "new": 64, "requests": 8,
              "train_steps": 3},
    # --chips 4: the same widths; the DP comparison holds the GLOBAL batch
    # at one chip's 128 so its 1-device reference fits one chip's memory
    "cross": {"arch": "resnet50", "image": 224, "global_batch": 128,
              "steps": 5, "classes": 1000, "lm_steps": 2},
}


def emit(**row):
    print(json.dumps(row), flush=True)


def require(ok, *info):
    """A check that survives ``python -O``; a failed one ends the run."""
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {info}")


def _peak_bytes(dev):
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


class CacheEvents:
    """Counts JAX's persistent-compilation-cache hits and misses."""

    def __init__(self):
        import jax

        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


# --------------------------------------------------------------------------
# kernel parity (small shapes, the compiled kernels vs dense oracles)
# --------------------------------------------------------------------------

def phase_kernel_parity(ctx):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from chainermn_tpu.ops import flash_attention
    from chainermn_tpu.ops.conv_backward import (_xla_conv, conv3x3_dgrad,
                                                 conv3x3_wgrad)
    from chainermn_tpu.ops.decode_attention import decode_attend

    interpret = not ctx["on_tpu"]     # rehearsal only: the Pallas interpreter
    sz = ctx["sizes"]["parity"]
    rng = np.random.RandomState(ctx["seed"])
    t0 = time.time()

    b, s, h, d = sz["flash"]
    q, k, v = (jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
               for _ in range(3))

    def dense(q, k, v):
        sc = jnp.einsum("bqhd,bkhd->bhqk", q, k) / (d ** 0.5)
        sc = jnp.where(np.tril(np.ones((s, s), bool))[None, None], sc, -1e30)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, -1), v)

    flash = jax.jit(partial(flash_attention, causal=True))
    np.testing.assert_allclose(np.asarray(flash(q, k, v)),
                               np.asarray(dense(q, k, v)),
                               rtol=2e-2, atol=2e-2)
    got = jax.grad(lambda *a: (flash(*a) ** 2).sum(), argnums=(0, 1, 2))(
        q, k, v)
    want = jax.grad(lambda *a: (dense(*a) ** 2).sum(), argnums=(0, 1, 2))(
        q, k, v)
    for g, w, name in zip(got, want, "qkv"):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=5e-2,
                                   atol=5e-2, err_msg=f"flash d{name}")

    b, s, h, hd = sz["decode"]
    pos = s // 2 + 3
    qd = jnp.asarray(rng.randn(b, h * hd), jnp.float32)
    kc, vc = (jnp.asarray(rng.randn(b, s, h * hd), jnp.float32)
              for _ in range(2))
    got = decode_attend(qd, kc, vc, pos, n_heads=h, head_dim=hd,
                        interpret=interpret)
    sc = jnp.einsum("bhd,bkhd->bhk", qd.reshape(b, h, hd),
                    kc.reshape(b, s, h, hd)) / (hd ** 0.5)
    sc = jnp.where(jnp.arange(s)[None, None] <= pos, sc, -1e30)
    want = jnp.einsum("bhk,bkhd->bhd", jax.nn.softmax(sc, -1),
                      vc.reshape(b, s, h, hd)).reshape(b, h * hd)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-2, atol=2e-2)

    n, hh, ww, c = sz["conv"]
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(ctx["seed"]), 3)
    x = jax.random.normal(k1, (n, hh, ww, c), jnp.bfloat16)
    w = jax.random.normal(k2, (3, 3, c, c), jnp.bfloat16)
    dy = jax.random.normal(k3, (n, hh, ww, c), jnp.bfloat16)
    ex, ew = jax.vjp(lambda x, w: _xla_conv(x, w, 1), x, w)[1](dy)
    dx = jax.jit(lambda dy, w: conv3x3_dgrad(
        dy, w, x.shape, 1, interpret=interpret))(dy, w)
    dw = jax.jit(lambda x, dy: conv3x3_wgrad(
        x, dy, 1, interpret=interpret))(x, dy)
    # bf16 oracle accumulates in its own order
    np.testing.assert_allclose(np.asarray(dx, np.float32),
                               np.asarray(ex, np.float32), rtol=0.1,
                               atol=0.25)
    np.testing.assert_allclose(np.asarray(dw, np.float32),
                               np.asarray(ew, np.float32), rtol=0.1,
                               atol=2.0 * n / 16 + 1.0)
    has_kernel = None
    if ctx["on_tpu"]:
        has_kernel = _has_kernel(flash.lower(q, k, v).compile())
        require(has_kernel, "flash_attention compiled without its kernel")
    emit(phase="kernel-parity", config=sz, kernels=["flash fwd+bwd",
         "decode_attend", "conv3x3 dgrad+wgrad"], interpret=interpret,
         wall_s=round(time.time() - t0, 2), tpu_custom_call=has_kernel)


# --------------------------------------------------------------------------
# train-resnet50: the examples/imagenet entry point, in-process
# --------------------------------------------------------------------------

def _example_main(relpath):
    path = os.path.join(REPO, relpath)
    spec = importlib.util.spec_from_file_location(
        "_smoke_" + os.path.basename(relpath)[:-3], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.main


def phase_train_resnet(ctx):
    import numpy as np

    sz = ctx["sizes"]["resnet"]
    main = _example_main("examples/imagenet/train_imagenet.py")
    t0 = time.time()
    with contextlib.redirect_stdout(sys.stderr):   # stdout: JSON lines only
        res = main(["--arch", sz["arch"], "--image-size", str(sz["image"]),
                    "--batchsize", str(sz["batch"]),
                    "--steps", str(sz["steps"]),
                    "--num-classes", str(sz["classes"]),
                    "--dataset-size", str(4 * sz["batch"])])
    losses = [res["first_loss"], res["last_loss"]]
    require(np.all(np.isfinite(losses)), losses)
    require(losses[0] != losses[1], f"loss did not move: {losses}")
    emit(phase="train-resnet50", config=dict(sz, dtype="bfloat16",
         data="synthetic"), chips=res["chips"], steps=sz["steps"],
         first_loss=losses[0],
         last_loss=losses[1], compile_s=res["compile_s"],
         run_s=res["run_s"], wall_s=round(time.time() - t0, 2),
         images_per_sec_per_chip=res["images_per_sec_per_chip"],
         peak_bytes_in_use=_peak_bytes(ctx["devices"][0]),
         tpu_custom_call=res["tpu_custom_call"])


# --------------------------------------------------------------------------
# train-lm: make_hybrid_shard_map_step + flash attention + fused CE
# --------------------------------------------------------------------------

def _lm_setup(ctx, devices, dp, tp, global_batch):
    """The 135M LM train step on a (dp, tp) mesh over ``devices``:
    (step, params, opt_state, batch, mesh, specs), weights from --seed."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    import chainermn_tpu as mn
    from chainermn_tpu.parallel import (
        init_tp_transformer_lm, make_hybrid_shard_map_step, shard_pytree,
        state_specs_like, tp_transformer_lm_loss, transformer_lm_specs)

    sz = ctx["sizes"]["lm"]
    mesh = mn.make_nd_mesh(("data", "model"), (dp, tp), devices)
    params = init_tp_transformer_lm(
        jax.random.PRNGKey(ctx["seed"]), sz["vocab"], sz["d_model"],
        sz["n_heads"], sz["n_layers"], max_len=sz["seq"],
        dtype=jnp.bfloat16)
    specs = transformer_lm_specs(params, "model")
    loss_fn = partial(tp_transformer_lm_loss,
                      head_dim=sz["d_model"] // sz["n_heads"],
                      axis_name="model", attn_impl="flash", ce_impl="fused")
    optimizer = optax.sgd(1e-2)
    step = make_hybrid_shard_map_step(
        loss_fn, optimizer, mesh, params, specs, data_axis="data",
        batch_spec=P("data"))
    p = shard_pytree(params, mesh, specs)
    st = shard_pytree(optimizer.init(params), mesh,
                      state_specs_like(optimizer, params, specs))
    tokens = np.random.RandomState(ctx["seed"]).randint(
        0, sz["vocab"], (global_batch, sz["seq"] + 1)).astype(np.int32)
    batch = (jax.device_put(tokens, NamedSharding(mesh, P("data"))),)
    return step, p, st, batch, mesh, specs


def _run_lm(compiled, p, st, batch, steps):
    losses = []
    for _ in range(steps):
        p, st, loss = compiled(p, st, batch)
        losses.append(float(loss))      # host readback = the barrier
    return p, st, losses


def phase_train_lm(ctx):
    import numpy as np

    sz = ctx["sizes"]["lm"]
    t0 = time.time()
    step, p, st, batch, _, _ = _lm_setup(
        ctx, ctx["devices"][:1], 1, 1, sz["batch"])
    t1 = time.time()
    compiled = step.lower(p, st, batch).compile()
    compile_s = time.time() - t1
    n_kernels = compiled.as_text().count("tpu_custom_call")
    if ctx["on_tpu"]:
        # flash fwd + bwd per layer and the fused-CE kernels: neither the
        # interpreter nor the XLA reference may have been taken
        require(n_kernels >= 2 * sz["n_layers"] + 2, n_kernels)
    t2 = time.time()
    p, st, losses = _run_lm(compiled, p, st, batch, sz["steps"] + 1)
    run_s = time.time() - t2
    require(np.all(np.isfinite(losses)), losses)
    require(losses[-1] < losses[0], f"loss did not fall: {losses}")
    emit(phase="train-lm", config=dict(sz, dtype="bfloat16", mesh=[1, 1],
         attn_impl="flash", ce_impl="fused"), steps=len(losses),
         first_loss=losses[0], last_loss=losses[-1],
         compile_s=round(compile_s, 2), run_s=round(run_s, 2),
         wall_s=round(time.time() - t0, 2),
         tokens_per_step=sz["batch"] * sz["seq"],
         peak_bytes_in_use=_peak_bytes(ctx["devices"][0]),
         tpu_custom_call=n_kernels > 0, n_tpu_custom_calls=n_kernels)
    ctx["lm_state"] = (compiled, p, st, batch)


# --------------------------------------------------------------------------
# serve: ServingEngine at the LM width
# --------------------------------------------------------------------------

def phase_serve(ctx):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from jax.sharding import PartitionSpec as P

    import chainermn_tpu as mn
    from chainermn_tpu._compat import shard_map
    from chainermn_tpu.parallel import make_lm_generator
    from chainermn_tpu.parallel.decode import lm_generate
    from chainermn_tpu.serving import ServingEngine

    sz, lm = ctx["sizes"]["serve"], ctx["sizes"]["lm"]
    head_dim = lm["d_model"] // lm["n_heads"]
    t0 = time.time()

    # a few more steps of the train-lm phase's own executable, then serve
    # what it trained (serving is shape-, not weight-dependent)
    compiled, p, st, batch = ctx.pop("lm_state")
    p, st, _ = _run_lm(compiled, p, st, batch, sz["train_steps"])
    params = jax.tree_util.tree_map(np.asarray, p)    # global host copy
    del p, st, batch, compiled

    mesh = mn.make_nd_mesh(("model",), (1,), ctx["devices"][:1])
    total = sz["prompt"] + sz["new"]
    eng = ServingEngine(params, head_dim=head_dim, n_slots=sz["n_slots"],
                        max_total=total, mesh=mesh,
                        queue_capacity=2 * sz["requests"])
    rng = np.random.RandomState(ctx["seed"] + 1)
    prompts = rng.randint(0, lm["vocab"], (sz["requests"] + 1, sz["prompt"])
                          ).astype(np.int32)

    def busy():
        return eng.scheduler.queue_depth > 0 or eng.pool.busy_count > 0

    # warm-up request: compiles the prefill and the tick, timed apart
    t1 = time.time()
    warm = eng.submit(prompts[-1], 2)
    while busy():
        eng.step()
    compile_s = time.time() - t1
    require(warm.status == "done", warm.status)

    t2 = time.time()
    first_wave = min(sz["n_slots"], sz["requests"])
    handles = [eng.submit(prompts[i], sz["new"]) for i in range(first_wave)]
    steps, budget = 0, 40 * sz["requests"] * sz["new"]
    while len(handles) < sz["requests"] or busy():
        eng.step()
        steps += 1
        if len(handles) < sz["requests"] and steps % 2 == 0:
            handles.append(eng.submit(prompts[len(handles)], sz["new"]))
        require(steps < budget, "serving did not drain")
    run_s = time.time() - t2
    goodput = eng.goodput.report()     # the ledger's wall ends here
    metrics = eng.metrics()

    statuses = [h.status for h in handles]
    require(statuses == ["done"] * sz["requests"], statuses)
    require(all(len(h.tokens) == sz["new"] for h in handles))
    # one request token-exact against the closed-batch greedy generator
    check = sz["requests"] - 1      # a later-wave request: a recycled slot
    gen = make_lm_generator(mesh, "model", head_dim=head_dim,
                            max_new_tokens=sz["new"])
    want = np.asarray(gen(params, prompts[check][None]))[0].tolist()
    require(handles[check].tokens == want, (handles[check].tokens, want))

    # which kernels the served programs hold.  The engine's tick feeds
    # PER-ROW positions, which parallel/decode.py routes to the einsum
    # attention (its flash-decode branch needs one scalar position per
    # call) — so the tick holds no Pallas kernel by design today; the
    # flash-decode kernel is proven in the scalar-position greedy decode
    # above's program (and in kernel-parity), the flash kernel in the
    # engine's prefill.
    dec = eng.engine
    z = np.zeros

    def compile_tick(engine):
        n = sz["n_slots"]
        return engine.engine._tick_prog.lower(
            engine.engine._params, engine.pool.caches,
            engine.engine._last_result,      # the tick before's, on device
            jnp.asarray(z(n, np.int32)), jnp.asarray(z(n, np.int32)),
            jnp.asarray(z((n, 2), np.uint32)),
            jnp.asarray(z(n, np.float32)),
            jnp.asarray(z(n, bool))).compile()      # the busy mask

    tick = compile_tick(eng)
    prefill = dec._prefill_progs[dec.padded_len(sz["prompt"])].lower(
        dec._params, eng.pool.caches, jnp.asarray(prompts[:1]),
        jnp.int32(sz["prompt"]), jnp.int32(0),
        jnp.asarray(z(2, np.uint32)), jnp.float32(0)).compile()
    greedy = jax.jit(shard_map(
        partial(lm_generate, head_dim=head_dim, axis_name="model",
                max_new_tokens=sz["new"], temperature=0.0),
        mesh=mesh, in_specs=(dec._specs, P(), P()), out_specs=P())).lower(
        dec._params, jnp.asarray(prompts[:1]),
        jnp.asarray(z(2, np.uint32))).compile()
    kernels = {"tick": _has_kernel(tick), "prefill": _has_kernel(prefill),
               "greedy_decode": _has_kernel(greedy)}
    if ctx["on_tpu"]:
        require(kernels["prefill"], "engine prefill lost the flash kernel")
        require(kernels["greedy_decode"],
                "greedy decode lost the flash-decode kernel")
    # a second engine (what a replica behind the router would build)
    # compiles the SAME tick program from a fresh jit and asks the
    # persistent compile cache for it.  Reported, not required: a key
    # covers more than the program text (argument placement, the call
    # path), so this in-process lookup misses today; hits show across
    # calls, in the compile-cache line.
    hits0 = ctx["cache"].hits
    eng2 = ServingEngine(params, head_dim=head_dim, n_slots=sz["n_slots"],
                         max_total=total, mesh=mesh,
                         queue_capacity=2 * sz["requests"])
    t3 = time.time()
    compile_tick(eng2)
    tick_rebuild_s = time.time() - t3
    tick_cache_hit = ctx["cache"].hits > hits0
    eng2.close()
    eng.close()
    emit(phase="serve", config=dict(sz, width=lm, dtype="bfloat16"),
         requests=sz["requests"], statuses=statuses, engine_steps=steps,
         token_exact_request=check, tokens_emitted=sz["requests"] * sz["new"],
         compile_s=round(compile_s, 2), run_s=round(run_s, 2),
         wall_s=round(time.time() - t0, 2),
         ttft_p50_ms=metrics.get("serving/ttft_p50_ms"),
         tick_gap_p50_ms=metrics.get("serving/tick_gap_p50_ms"),
         goodput_coverage_frac=goodput["coverage_frac"],
         second_engine_tick_compile_s=round(tick_rebuild_s, 2),
         second_engine_tick_cache_hit=tick_cache_hit,
         peak_bytes_in_use=_peak_bytes(ctx["devices"][0]),
         tpu_custom_call=kernels)


# --------------------------------------------------------------------------
# --chips 4: what exists only across chips, and what it is compared with
# --------------------------------------------------------------------------

def _assert_placement(x, n_devices, rows_frac, what):
    require(len(x.sharding.device_set) == n_devices,
            (what, x.sharding.device_set))
    shard_rows = x.addressable_shards[0].data.shape[0]
    require(shard_rows * rows_frac == x.shape[0],
            (what, shard_rows, x.shape, rows_frac))


def phase_cross_collectives(ctx):
    import numpy as np

    import chainermn_tpu as mn

    t0 = time.time()
    n = len(ctx["devices"])
    xla = mn.create_communicator("xla", devices=ctx["devices"])
    naive = mn.create_communicator("naive", size=n)
    require(xla.size == n, xla.size)
    rng = np.random.RandomState(ctx["seed"])
    x = rng.randn(n, n, 64).astype(np.float32)   # rank-major stack
    ops = {"allreduce": lambda c: c.allreduce(x),
           "bcast": lambda c: c.bcast(x, root=n - 1),
           "alltoall": lambda c: c.alltoall(x),
           "allgather": lambda c: c.allgather(x)}
    for name, call in ops.items():
        got = call(xla)
        np.testing.assert_allclose(np.asarray(got), np.asarray(call(naive)),
                                   rtol=1e-5, atol=1e-5, err_msg=name)
        require(len(got.sharding.device_set) == n, (name, got.sharding))
    emit(phase="cross-collectives", chips=n, ops=sorted(ops),
         oracle="naive (numpy)", wall_s=round(time.time() - t0, 2))


def _resnet_steps(ctx, devices, sz):
    """``steps`` DP train steps of the imagenet example's flax step on a
    mesh over ``devices`` at a fixed GLOBAL batch; returns the losses."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import chainermn_tpu as mn
    from chainermn_tpu.models.mlp import cross_entropy_loss
    from chainermn_tpu.models.resnet import ARCHS

    n = len(devices)
    comm = mn.create_communicator("xla", devices=devices)
    mesh = comm.mesh
    model = ARCHS[sz["arch"]](num_classes=sz["classes"],
                              stem_strides=2 if sz["image"] >= 64 else 1)
    variables = dict(model.init(
        jax.random.PRNGKey(ctx["seed"]),
        jnp.zeros((1, sz["image"], sz["image"], 3)), train=False))
    optimizer = mn.create_multi_node_optimizer(
        optax.sgd(0.05, momentum=0.9), comm)

    def loss_and_metrics(logits, batch):
        return cross_entropy_loss(logits, batch[1]), {}

    step = mn.make_flax_train_step(model, loss_and_metrics, optimizer,
                                   mesh=mesh)
    variables = mn.replicate(variables, mesh)
    opt_state = mn.replicate(optimizer.init(variables["params"]), mesh)
    rng = np.random.RandomState(ctx["seed"])
    images = rng.randn(sz["global_batch"], sz["image"], sz["image"], 3
                       ).astype(np.float32)
    labels = rng.randint(0, sz["classes"], sz["global_batch"]
                         ).astype(np.int32)
    batch = mn.shard_batch((images, labels), mesh)
    _assert_placement(batch[0], n, n, "resnet batch")
    leaf = jax.tree_util.tree_leaves(variables["params"])[0]
    _assert_placement(leaf, n, 1, "resnet params (replicated)")
    losses = []
    for _ in range(sz["steps"]):
        variables, opt_state, loss, _ = step(variables, opt_state, batch)
        losses.append(float(loss))
    return losses


def phase_cross_dp(ctx):
    import numpy as np

    sz = ctx["sizes"]["cross"]
    t0 = time.time()
    n = len(ctx["devices"])
    got = _resnet_steps(ctx, ctx["devices"], sz)
    mem = [(d.memory_stats() or {}).get("peak_bytes_in_use")
           for d in ctx["devices"]]
    if ctx["on_tpu"]:
        # code that has never seen more than one real chip may put
        # everything on the first: every chip must have held real work
        require(all(m and m > (64 << 20) for m in mem), mem)
    want = _resnet_steps(ctx, ctx["devices"][:1], sz)
    require(np.all(np.isfinite(got + want)), (got, want))
    # same global batch, same seed: the DP gradient mean makes the two
    # runs the same optimisation up to bf16 reduction order and BN's
    # per-replica batch statistics
    np.testing.assert_allclose(got, want, rtol=5e-2)
    emit(phase="cross-dp-resnet", chips=n, config=sz, losses_n_chips=got,
         losses_1_chip=want, peak_bytes_per_device=mem,
         wall_s=round(time.time() - t0, 2))


def phase_cross_lm(ctx):
    import jax
    import numpy as np

    sz, steps = ctx["sizes"]["lm"], ctx["sizes"]["cross"]["lm_steps"]
    t0 = time.time()
    n = len(ctx["devices"])
    dp, tp = n // 2, 2
    step, p, st, batch, _, specs = _lm_setup(ctx, ctx["devices"], dp, tp,
                                             sz["batch"])
    _assert_placement(batch[0], n, dp, "lm batch (split over data)")
    # a column-parallel weight: sharded 1/tp on the model axis, on all chips
    from jax.sharding import PartitionSpec as P
    flat_p = jax.tree_util.tree_leaves(p)
    flat_s = jax.tree_util.tree_leaves(
        specs, is_leaf=lambda s: isinstance(s, P))
    sharded = [(x, s) for x, s in zip(flat_p, flat_s) if "model" in tuple(s)]
    require(sharded, "no parameter is sharded over the model axis")
    for x, s in sharded:
        require(len(x.sharding.device_set) == n)
        dim = tuple(s).index("model")
        require(x.addressable_shards[0].data.shape[dim] * tp
                == x.shape[dim], (x.shape, s))
    compiled = step.lower(p, st, batch).compile()
    n_kernels = compiled.as_text().count("tpu_custom_call")
    _, _, got = _run_lm(compiled, p, st, batch, steps)
    step1, p1, st1, batch1, _, _ = _lm_setup(ctx, ctx["devices"][:1], 1, 1,
                                             sz["batch"])
    _, _, want = _run_lm(step1.lower(p1, st1, batch1).compile(), p1, st1,
                         batch1, steps)
    require(np.all(np.isfinite(got + want)), (got, want))
    np.testing.assert_allclose(got, want, rtol=2e-2)
    if ctx["on_tpu"]:
        require(n_kernels > 0)
    emit(phase="cross-dpxtp-lm", chips=n, mesh=[dp, tp], config=sz,
         losses_2x2=got, losses_1x1=want, n_tpu_custom_calls=n_kernels,
         n_model_sharded_params=len(sharded),
         wall_s=round(time.time() - t0, 2))


ONE_CHIP_PHASES = (phase_kernel_parity, phase_train_resnet, phase_train_lm,
                   phase_serve)
CROSS_CHIP_PHASES = (phase_cross_collectives, phase_cross_dp, phase_cross_lm)


def main(argv=None, *, _allow_cpu=False, _sizes=None) -> int:
    parser = argparse.ArgumentParser(
        description="run the training and serving main paths once on the "
                    "TPU and check what comes out")
    parser.add_argument("--chips", type=int, default=1, choices=(1, 4),
                        help="4 = ONLY the cross-chip phase and what it is "
                             "compared with")
    parser.add_argument("--seed", type=int, default=0,
                        help="weights and data are made from this seed")
    args = parser.parse_args(argv)

    import jax

    devices = jax.devices()        # first action: is there a chip at all?
    dev = devices[0]
    if dev.platform != "tpu" and not _allow_cpu:
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}, "
              f"{len(devices)} device(s)); this script has no CPU path",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"JAX sees {len(devices)}", file=sys.stderr)
        return 2

    import chainermn_tpu as mn
    from chainermn_tpu.topology import enable_compile_cache

    cache_dir = enable_compile_cache()
    mn.init_distributed()          # single host: must stay a no-op
    native = mn.runtime.native_available()
    if dev.platform == "tpu":
        require(native, "the C++ prefetcher did not build from this checkout")
    emit(phase="setup", chips=args.chips, seed=args.seed,
         device={"platform": dev.platform, "kind": dev.device_kind,
                 "count": len(devices)},
         jax=jax.__version__, process_count=jax.process_count(),
         compile_cache_dir=cache_dir,
         compile_cache_placed_by=("JAX_COMPILATION_CACHE_DIR"
                                  if os.environ.get(
                                      "JAX_COMPILATION_CACHE_DIR")
                                  else "chainermn_tpu (fixed, in-checkout)"),
         native_prefetcher=native,
         tpu_worker_hostnames=os.environ.get("TPU_WORKER_HOSTNAMES"))

    ctx = {"devices": devices[:args.chips], "seed": args.seed,
           "on_tpu": dev.platform == "tpu", "sizes": _sizes or SIZES,
           "cache": CacheEvents()}
    for phase in (ONE_CHIP_PHASES if args.chips == 1 else CROSS_CHIP_PHASES):
        phase(ctx)                 # a failing phase raises: non-zero exit
    emit(phase="compile-cache", dir=cache_dir, hits=ctx["cache"].hits,
         misses=ctx["cache"].misses)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
