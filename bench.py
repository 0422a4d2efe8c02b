#!/usr/bin/env python
"""Headline benchmark: ResNet-50 training throughput per chip, with MFU.

Matches `BASELINE.json :: metric` ("ResNet-50 images/sec/chip; allreduce
scaling efficiency; >=90% DP efficiency").  The baseline per-chip figure is
derived from the reference's published headline run (BASELINE.md): 1.28M
ImageNet images x 90 epochs in 15 min on 1024 P100s => ~125 images/sec/chip
end-to-end.  vs_baseline = ours / 125.

Honesty layer (round-2):
  * FLOPs/step are read from the *compiled executable*
    (``step.lower(...).compile().cost_analysis()['flops']``), cross-checked
    against the analytic ResNet FLOP count, and turned into
    ``mfu = flops * steps / dt / peak_flops(device_kind)``.
  * MFU > 1.0 is physically impossible; the run is then marked
    ``"suspect": true`` and a loud warning goes to stderr (a platform that
    elides or misreports work can no longer smuggle a fake number through).
  * A DP weak-scaling sweep (1->2->4->8 virtual CPU devices, fixed per-chip
    batch) reports total-throughput efficiency vs 1 device.  On a single
    physical host the ideal is flat total throughput, so the efficiency
    isolates collective/step overhead growth, the quantity BASELINE.md row 4
    tracks across 8->256 chips.
  * On a real TPU chip, a per-chip batch sweep shows where throughput
    saturates.

Prints the result JSON line on stdout INCREMENTALLY: the full line is
emitted as soon as the headline section completes and re-emitted (enriched)
after every later section, so the LAST parseable stdout line is always a
complete result no matter when a driver-side timeout kills the process
(a run killed at its time limit once parsed to null because the line
printed only at the end).  Schema:
  {"metric": ..., "value": N, "unit": "images/sec/chip", "vs_baseline": N,
   "mfu": N|null, "suspect": bool, "flops_per_image": N,
   "batch_sweep": {...}, "scaling": {"total_ips": {...}, "efficiency_pct": N},
   "sections_complete": [...], "wall_clock_s": N}
Everything else (warnings, progress) goes to stderr.
"""

import argparse
import json
import os
import subprocess
import sys
import time

#: What raised during this run (sections, sub-measurements).  Listed in
#: every emitted result line as ``failed`` and turned into exit code 1 —
#: a handler may carry the run on to the next section, never to "ok".
FAILED = []

REFERENCE_IMAGES_PER_SEC_PER_CHIP = 125.0  # ChainerMN 1024xP100 headline run


# The per-generation peak-FLOPs / HBM-bandwidth tables moved to
# chainermn_tpu.observability.metrics (single source of truth shared with
# the step-breakdown MFU gauge); these thin faces keep bench.py's import
# graph lazy — chainermn_tpu is only pulled in once a benchmark actually
# needs it.

def peak_flops_for(device_kind: str):
    from chainermn_tpu.observability.metrics import peak_flops_for as _f
    return _f(device_kind)


def hbm_bw_for(device_kind: str):
    from chainermn_tpu.observability.metrics import hbm_bw_for as _f
    return _f(device_kind)


def build_step(arch, image_size, per_chip_batch, allreduce_grad_dtype=None,
               double_buffering=False, norm="bn", conv_impl="xla"):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import chainermn_tpu as mn
    from chainermn_tpu.models.mlp import cross_entropy_loss
    from chainermn_tpu.models.resnet import ARCHS

    comm = mn.create_communicator("xla")
    mesh = comm.mesh
    n_chips = comm.size
    global_batch = per_chip_batch * n_chips

    kw = {"norm": norm} if norm != "bn" else {}
    if conv_impl != "xla":
        kw["conv_impl"] = conv_impl
    model = ARCHS[arch](stem_strides=2 if image_size >= 64 else 1, **kw)
    variables = dict(model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, image_size, image_size, 3)),
        train=False))
    # the step contract is {'params', 'batch_stats'} (train.py docstring);
    # norm-free models (norm='affine') init without the stats collection
    variables.setdefault("batch_stats", {})
    optimizer = mn.create_multi_node_optimizer(
        optax.chain(optax.add_decayed_weights(1e-4),
                    optax.sgd(0.1, momentum=0.9)),
        comm, allreduce_grad_dtype=allreduce_grad_dtype,
        double_buffering=double_buffering)

    def loss_and_metrics(logits, batch):
        return cross_entropy_loss(logits, batch[1]), {}

    step = mn.make_flax_train_step(
        model, loss_and_metrics, optimizer, mesh=mesh,
        allreduce_grad_dtype=allreduce_grad_dtype)
    variables = mn.replicate(variables, mesh)
    opt_state = mn.replicate(optimizer.init(variables["params"]), mesh)

    rng = np.random.RandomState(0)
    batch = mn.shard_batch(
        (rng.randn(global_batch, image_size, image_size, 3).astype(np.float32),
         rng.randint(0, 1000, global_batch).astype(np.int32)),
        mesh)
    return step, variables, opt_state, batch, n_chips, global_batch


def comm_bytes_model(step_fn, *step_args):
    """Predicted vs ledgered wire bytes for one step program (ISSUE 6).

    ``measured_comm_bytes`` — the PR 1 comm-ledger rows booked while
    TRACING the step under the accounting layer: in-jit bookings land at
    trace time and are replayed per execution, so this is exactly the
    per-step ledger a traced run reports.  MUST run before any other
    lower/compile of the same function: a pjit cache hit books nothing
    (probed; the shard-flow reconciliation relies on the same fact).

    ``predicted_comm_bytes`` — the shard-flow static cost model over the
    identical jaxpr (ledger convention: payload bytes per collective
    call).

    Both series land in every BENCH section and in bench_history.jsonl,
    so ``check_perf_regression.py --history`` gates wire-byte drift —
    "bytes" keys compare lower-is-better — not just time.
    """
    import jax

    from chainermn_tpu import observability as obs
    from chainermn_tpu.analysis import shardflow
    from chainermn_tpu.observability.comm import get_accountant

    was = obs.enabled()
    obs.enable()
    acct = get_accountant()
    try:
        with acct.step("bench_comm_model"):
            jaxpr = jax.make_jaxpr(step_fn)(*step_args)
        rows = dict((acct.last_step_report or {}).get("per_op", {}))
    finally:
        if not was:
            obs.disable()
    measured = sum(int(r["bytes"]) for r in rows.values())
    predicted = sum(shardflow.group_bytes(
        shardflow.static_costs(jaxpr)).values())
    return {
        "predicted_comm_bytes": int(predicted),
        "measured_comm_bytes": int(measured),
        "per_op": {k: {"bytes": int(v["bytes"])} for k, v in rows.items()},
    }


def compile_with_flops(step, variables, opt_state, batch):
    """AOT-compile the step once; return (callable, flops, bytes_accessed)
    — the same executable is then timed, so the compile cost is paid
    exactly once.  ``bytes_accessed`` feeds the HBM roofline (see
    docs/PERF.md — ResNet-50 is bandwidth-bound on v5e, so FLOPs alone
    misdiagnose it).  A failed compile raises: the caller's section is
    then listed as failed, never timed on something else."""
    compiled = step.lower(variables, opt_state, batch).compile()
    flops, nbytes = None, None
    try:
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0]
        flops = float(cost.get("flops", 0.0)) or None
        nbytes = float(cost.get("bytes accessed", 0.0)) or None
    except Exception as e:  # pragma: no cover
        FAILED.append("cost_analysis")
        print(f"bench: cost_analysis unavailable ({e!r})", file=sys.stderr)
    return compiled, flops, nbytes


def measure(step, variables, opt_state, batch, steps, epochs=2,
            reduce="max"):
    """Timing epochs ending at a HOST READBACK; report max or median.

    ``float(loss)`` is the barrier: the scalar must physically exist on
    the host, and each step's params feed the next, so the final loss
    transitively depends on every timed step.

    ``reduce="max"`` (default, 2 epochs) guards against first-loop
    artifacts for the honest-headline sections; the scaling sweep uses
    ``reduce="median"`` with 3 epochs so a single scheduler hiccup on the
    time-shared virtual mesh cannot publish a >100% efficiency point
    (round-4 artifact carried a single-sample 116.9%).
    """
    if reduce not in ("max", "median"):
        raise ValueError(f"reduce must be 'max' or 'median', got {reduce!r}")
    for _ in range(2):  # compile + warmup
        variables, opt_state, loss, *_ = step(variables, opt_state, batch)
    float(loss)
    dts, out = [], 0.0
    for _ in range(epochs):
        t0 = time.perf_counter()
        for _ in range(steps):
            variables, opt_state, loss, *_ = step(variables, opt_state, batch)
        out = float(loss)  # host readback = the timing barrier
        dts.append(time.perf_counter() - t0)
    dts.sort()
    dt = dts[-1] if reduce == "max" else dts[len(dts) // 2]
    return dt, out


def bench_transformer_lm(n_chips_hint=None, seq=1024, per_chip_batch=8,
                         pos_impl="learned", d_model=1024, n_layers=8,
                         n_heads=8):
    """Tokens/sec/chip + MFU for a TP transformer LM with flash attention.

    The FLOPs-dense half of the perf story: ResNet-50's conv shapes cap its
    MFU well below what the MXU sustains on big matmuls; a decoder LM shows
    the framework's ceiling.  Runs DP×TP over a (n_chips, 1) mesh via the
    same make_hybrid_shard_map_step users call.  The long-context section
    re-runs it at ``seq=4096`` — same honesty layer (analytic fallback,
    suspect flag) for both.

    ``n_heads=8`` (head_dim 128) is the TPU-NATIVE default: head_dim must
    fill the 128-lane vreg and the MXU's 128-wide contraction, or every
    attention-adjacent op (flash tiles, the (B,S,H,hd)↔(BH,S,hd) layout
    round-trips) runs on half-empty registers.  Measured round 5, same
    135M params (the projection shapes don't depend on the head split):
    h16/hd64 0.534 compiled MFU → h8/hd128 0.630 (130.1k → 153.6k
    tok/s/chip) — the r04 "135M pays fixed costs" gap was substantially
    the GPU-era head shape, not the step machinery (docs/PERF.md).
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import chainermn_tpu as mn
    from chainermn_tpu.parallel import (
        init_tp_transformer_lm, make_hybrid_shard_map_step, shard_pytree,
        state_specs_like, tp_transformer_lm_loss, transformer_lm_specs)
    from functools import partial
    from jax.sharding import NamedSharding, PartitionSpec as P

    vocab = 32768
    n_chips = len(jax.devices())
    mesh = mn.make_nd_mesh(("data", "model"), (n_chips, 1))
    params = init_tp_transformer_lm(
        jax.random.PRNGKey(0), vocab, d_model, n_heads, n_layers,
        max_len=seq, dtype=jnp.bfloat16, pos_impl=pos_impl)
    specs = transformer_lm_specs(params, "model")
    loss_fn = partial(tp_transformer_lm_loss, head_dim=d_model // n_heads,
                      axis_name="model", attn_impl="flash")
    optimizer = optax.sgd(1e-2)
    step = make_hybrid_shard_map_step(
        loss_fn, optimizer, mesh, params, specs, data_axis="data",
        batch_spec=P("data"))
    p = shard_pytree(params, mesh, specs)
    st = shard_pytree(optimizer.init(params), mesh,
                      state_specs_like(optimizer, params, specs))
    tokens = np.random.RandomState(0).randint(
        0, vocab, (per_chip_batch * n_chips, seq + 1)).astype(np.int32)
    batch = (jax.device_put(tokens, NamedSharding(mesh, P("data"))),)

    step_c, flops_per_step, _ = compile_with_flops(step, p, st, batch)
    # 40 steps per host readback amortize the readback over the loop.
    steps = 40
    # median-of-3 epochs: the median survives one stalled AND one
    # anomalously fast epoch.
    dt, _ = measure(step_c, p, st, batch, steps=steps, epochs=3,
                    reduce="median")
    toks = per_chip_batch * seq  # per chip per step
    tps = steps * toks / dt  # measure() already covers all chips' shards: dt
    # is wall-clock for the whole mesh, so per-chip tokens/sec uses per-chip
    # toks
    n_params = sum(np.prod(l.shape) for l in jax.tree_util.tree_leaves(params))
    flops_source = "compiled"
    # Per-chip convention throughout, same as the ResNet path: GSPMD
    # compiles one per-device program, so cost_analysis FLOPs are per-chip.
    if not flops_per_step:
        # 6·N per token (fwd+bwd matmuls) + 12·L·D·S per token (attention)
        flops_per_step = (6.0 * n_params
                          + 12.0 * n_layers * d_model * seq) * toks
        flops_source = "analytic"
    dev = jax.devices()[0]
    peak = peak_flops_for(dev.device_kind)
    mfu = flops_per_step * steps / dt / peak if peak else None
    analytic_step = (6.0 * n_params + 12.0 * n_layers * d_model * seq) * toks
    mfu_useful = analytic_step * steps / dt / peak if peak else None
    suspect = bool(mfu and mfu > 1.0)
    if suspect:
        print(f"bench: WARNING transformer MFU {mfu:.2f} > 1.0 impossible — "
              f"number not credible", file=sys.stderr)
    return {
        "tokens_per_sec_per_chip": round(tps, 1),
        "mfu": round(mfu, 4) if mfu else None,
        "mfu_useful": round(mfu_useful, 4) if mfu_useful else None,
        "suspect": suspect,
        "flops_source": flops_source,
        "n_params": int(n_params),
        "config": f"d{d_model} L{n_layers} h{n_heads} S{seq} V{vocab} "
                  f"b{per_chip_batch}/chip bf16 flash {pos_impl}",
    }


def bench_long_context():
    """Long-sequence numbers: the flash kernel pair at S=8k/16k (attention
    is the whole story there) and a full LM train step at S=4096.

    Attention MFU is against the causal-attention FLOPs only — the number
    that shows whether the Pallas fwd+bwd kernels hold up when the O(S²)
    term dominates (the round-2 XLA-scan backward degraded here: it cannot
    skip above-diagonal blocks).

    Round 5: the headline rows use head_dim 128 (8 heads × 128 at the
    same 1024 model width) — the TPU-native head shape (docs/DESIGN.md);
    at head_dim 64 each score cell buys half the MXU FLOPs (64-wide
    contraction) for the same VPU softmax cost, capping fwd+bwd at ~0.38
    asymptotically (docs/PERF.md round-5 ceiling argument).  One hd64 row
    is retained at S=8192 for continuity with the r01–r04 artifacts.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    dev = jax.devices()[0]
    peak = peak_flops_for(dev.device_kind)
    out = {}
    rs = np.random.RandomState(0)

    from chainermn_tpu.ops.flash_attention import flash_attention

    def flash_row(S, B, reps, H, HD):
        """Per-rep time by the SLOPE between two chain lengths (reps and
        3·reps): (t2-t1)/(r2-r1) cancels every fixed per-readback cost
        without assuming its size."""
        q = jax.device_put(rs.randn(B, S, H, HD).astype(jnp.bfloat16))
        flops = 2 * 2 * B * H * S * S * HD / 2 * 3.5  # causal fwd+bwd

        def chain_n(n):
            @jax.jit
            def chain(qq):
                def body(c, _):
                    o, vjp = jax.vjp(
                        lambda a: flash_attention(a, a, a, causal=True), c)
                    (dq,) = vjp(o)
                    return dq.astype(c.dtype), None
                fin, _ = jax.lax.scan(body, qq, None, length=n)
                return jnp.max(fin).astype(jnp.float32)
            return chain

        # The two programs differ ONLY in scan trip count — the while
        # body compiles once per program with the same schedule, so the
        # slope cancels the fixed cost without assuming its size (the
        # c6678d7 schedule variance was CROSS-process; raw chain times
        # are recorded in the row for auditability).
        c1, c2 = chain_n(reps), chain_n(3 * reps)
        float(c1(q)); float(c2(q))
        t1s, t2s = [], []
        for _ in range(2):
            t0 = time.perf_counter(); float(c1(q))
            t1s.append(time.perf_counter() - t0)
            t0 = time.perf_counter(); float(c2(q))
            t2s.append(time.perf_counter() - t0)
        best = max((min(t2s) - min(t1s)) / (2 * reps), 1e-4)
        mfu = flops / best / peak if peak else None
        if mfu and mfu > 1.0:
            print(f"bench: WARNING long-context S={S} attention MFU "
                  f"{mfu:.2f} > 1.0 impossible — number not credible",
                  file=sys.stderr)
        return {
            "ms": round(best * 1e3, 2),
            "attn_mfu": round(mfu, 3) if mfu else None,
            "heads": f"{H}x{HD}",
            "chains_s": [round(min(t1s), 3), round(min(t2s), 3)],
            "reps": [reps, 3 * reps],
            "suspect": bool(mfu and mfu > 1.0),
        }

    out["flash_fwd_bwd_S8192"] = flash_row(8192, 2, 20, 8, 128)
    out["flash_fwd_bwd_S16384"] = flash_row(16384, 1, 12, 8, 128)
    out["flash_fwd_bwd_S8192_hd64"] = flash_row(8192, 2, 12, 16, 64)

    # full LM step at S=4096 (b=2: same 8192 tokens/step as the headline)
    # — same builder and honesty layer as the headline transformer section.
    out["lm_S4096"] = bench_transformer_lm(seq=4096, per_chip_batch=2,
                                           pos_impl="rope")
    return out


def bench_data_path(demand_ips=None):
    """ImageNet-SHAPE input pipeline vs the training step's own demand
    (round-5 directive #7).

    Corpus: synthetic pixels in the REAL layout — 224×224×3 **uint8**
    records (the on-disk form of a decoded ImageNet corpus; JPEG decode
    happens once at ingest) produced by the real ingest CLI
    (``scripts/ingest_images.py``, npz source) and consumed exactly the
    way training consumes it: ``FileDataset`` → C++ prefetch ring →
    batch views → ``shard_batch`` → on-chip cast/normalize inside the
    jitted NF-ResNet step (``preprocess=``).

    Reports ASSEMBLY throughput (iterator drained, no step) for the
    consumed path (``copy=False``: slot views valid until the next batch
    — the training loop device_puts them immediately, so this is the
    semantics training actually uses) and the detach path (``copy=True``),
    against ``demand_ips`` — the NF-ResNet-50 img/s/chip measured EARLIER
    IN THIS SAME RUN.  The loader is "not the bottleneck at pod rates"
    iff assembly ≥ demand.  ``train_ips_uint8_disk`` additionally proves
    end-to-end consumption; uint8 cuts the upload bytes 4× vs float32.
    """
    import shutil
    import subprocess as sp
    import tempfile

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import chainermn_tpu as mn
    from chainermn_tpu.models.mlp import cross_entropy_loss
    from chainermn_tpu.models.resnet import ARCHS

    b, img, n_records, steps = 128, 224, 1536, 10
    rng = np.random.RandomState(0)
    tmp = tempfile.mkdtemp(prefix="bench_data_")
    out = {"batch": b, "record": f"{img}x{img}x3 uint8",
           "n_records": n_records, "steps": steps,
           "demand_ips": demand_ips}
    try:
        npz = os.path.join(tmp, "corpus.npz")
        np.savez(npz,
                 images=rng.randint(0, 256, (n_records, img, img, 3),
                                    dtype=np.uint8),
                 labels=rng.randint(0, 1000, n_records).astype(np.int32))
        sp.run([sys.executable,
                os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "scripts", "ingest_images.py"),
                "--source", f"npz:{npz}",
                "--out", os.path.join(tmp, "ds"), "--val-frac", "0.0"],
               # a host-only data tool: it imports the package (and so
               # jax) but must never reach for the chip this process holds
               env=dict(os.environ, JAX_PLATFORMS="cpu"),
               check=True, capture_output=True, timeout=600)
        os.unlink(npz)
        disk = mn.FileDataset(os.path.join(tmp, "ds", "train"))

        def assembly_ips(copy):
            # With the default 16-slot ring the C++ workers pre-assemble
            # the WHOLE 11-batch run during warmup and the loop times
            # pointer acquisition (a round-5 artifact read 5M img/s).
            # Fix: a 4-slot ring, and the rate counts only the
            # ``steps - n_slots`` batches the workers must ASSEMBLE
            # during the drain (the first n_slots acquisitions consume
            # pre-built slots) — a conservative true-assembly rate.
            n_slots = 4
            it = mn.PrefetchIterator(disk, batch_size=b, seed=1, copy=copy,
                                     n_slots=n_slots)
            next(it)  # spin up the ring
            t0 = time.perf_counter()
            for _ in range(steps):
                next(it)
            dt = time.perf_counter() - t0
            it.close()
            return (steps - n_slots) * b / dt

        nocopy = assembly_ips(copy=False)
        out["assembly_ips_nocopy"] = round(nocopy, 1)
        out["assembly_ips_copy"] = round(assembly_ips(copy=True), 1)
        if demand_ips:
            # one host loader feeds every local chip — the capability
            # claim must clear n_chips × the per-chip step demand
            n_chips = len(jax.devices())
            out["demand_scope"] = f"{n_chips} local chip(s)"
            out["assembly_meets_demand"] = bool(
                nocopy >= demand_ips * n_chips)

        # end-to-end: uint8 slot views → shard_batch (compact wire) →
        # cast+normalize fused into the jitted step on chip.
        comm = mn.create_communicator("xla")
        model = ARCHS["nf_resnet50"](stem_strides=2)
        variables = dict(model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, img, img, 3)), train=False))
        variables.setdefault("batch_stats", {})
        optimizer = mn.create_multi_node_optimizer(
            optax.chain(optax.add_decayed_weights(1e-4),
                        optax.sgd(0.1, momentum=0.9)), comm)
        step = mn.make_flax_train_step(
            model,
            lambda logits, bt: (cross_entropy_loss(logits, bt[1]), {}),
            optimizer, mesh=comm.mesh,
            preprocess=lambda bt: (bt[0].astype(jnp.float32) / 255.0 - 0.5,
                                   bt[1]))
        variables = mn.replicate(variables, comm.mesh)
        opt_state = mn.replicate(optimizer.init(variables["params"]),
                                 comm.mesh)
        it = mn.PrefetchIterator(disk, batch_size=b, seed=2, copy=False)
        batch = mn.shard_batch(next(it), comm.mesh)
        variables, opt_state, loss, _ = step(variables, opt_state, batch)
        float(loss)  # compile barrier
        t0 = time.perf_counter()
        for _ in range(steps):
            batch = mn.shard_batch(next(it), comm.mesh)
            variables, opt_state, loss, _ = step(variables, opt_state, batch)
        float(loss)  # host readback barrier
        out["train_ips_uint8_disk"] = round(
            steps * b / (time.perf_counter() - t0), 1)
        it.close()
        out["note"] = (
            "assembly_ips_nocopy is the consumed path (slot views, "
            "device_put before the next acquire); train_ips includes the "
            "per-step host->device upload — demand_ips is the same-run "
            "NF-ResNet step rate the assembly number must beat")
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return out


def bench_decode():
    """Generation perf over the KV cache on the real chip: prefill vs
    decode split, tokens/s and per-token latency, greedy and beam.

    Method: one jitted program covers prefill + scan-decode, so timing a
    ``max_new=1`` run isolates (approximately) the prefill; the greedy
    512-token run minus that is pure incremental decode.  Best-of-3;
    nothing is subtracted from a measured wall time."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import chainermn_tpu as mn
    from chainermn_tpu.parallel import (
        init_tp_transformer_lm, make_lm_beam_generator, make_lm_generator,
        shard_pytree, transformer_lm_specs)

    vocab, d_model, n_heads, n_layers = 32768, 1024, 16, 8
    b, s_prompt, new = 8, 512, 512
    n_chips = len(jax.devices())
    mesh = mn.make_nd_mesh(("model",), (n_chips,))
    params = init_tp_transformer_lm(
        jax.random.PRNGKey(0), vocab, d_model, n_heads, n_layers,
        max_len=s_prompt + new, dtype=jnp.bfloat16)
    p = shard_pytree(params, mesh, transformer_lm_specs(params, "model"))
    prompt = jnp.asarray(np.random.RandomState(0).randint(
        0, vocab, (b, s_prompt)), jnp.int32)

    def timed(fn, *args, reps=5):
        """Dispatch ``reps`` runs back-to-back, one readback at the end:
        device execution is FIFO, so the final array bounds them all and
        the one readback amortizes over reps."""
        out = fn(*args)
        np.asarray(out)  # compile + readback barrier
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(reps - 1):
                fn(*args)
            np.asarray(fn(*args))
            best = min(best, (time.perf_counter() - t0) / reps)
        return best

    hd = d_model // n_heads
    prefill = timed(make_lm_generator(
        mesh, head_dim=hd, max_new_tokens=1), p, prompt)
    greedy = timed(make_lm_generator(
        mesh, head_dim=hd, max_new_tokens=new), p, prompt)
    decode_s = max(greedy - prefill, 1e-9)
    beam = timed(make_lm_beam_generator(
        mesh, head_dim=hd, max_new_tokens=new, beam_size=4), p, prompt)
    beam_decode_s = max(beam - prefill, 1e-9)
    return {
        "config": f"d{d_model} L{n_layers} h{n_heads} V{vocab} "
                  f"b{b} prompt{s_prompt} new{new} bf16",
        "prefill_ms": round(prefill * 1e3, 1),
        "prefill_tokens_per_sec": round(b * s_prompt / prefill, 1),
        "greedy_tokens_per_sec": round(b * new / decode_s, 1),
        "greedy_ms_per_token": round(decode_s / new * 1e3, 3),
        "beam4_tokens_per_sec": round(b * new / beam_decode_s, 1),
        "beam4_ms_per_token": round(beam_decode_s / new * 1e3, 3),
    }


def bench_serving():
    """Continuous-batching serving perf: offered-load sweep over the
    slot-managed engine (chainermn_tpu/serving/) — TTFT p50/p99,
    tokens/s, slot occupancy per load point.

    This is the BENCH trajectory's serving starting point: a tiny
    random-init LM (serving perf is shape- not weight-dependent), a
    4-slot pool, and two arrival regimes — ``load_high`` submits every
    engine step (queue always backed up: occupancy and queue depth show
    saturation behavior) and ``load_low`` submits every 4th step (pool
    mostly idle: TTFT shows the unloaded floor).  All numbers come from
    the engine's own metrics() — the same dict the Prometheus exporter
    scrapes — so the bench, the gauges, and the regression gate
    (scripts/check_perf_regression.py: ``_ms`` keys lower-is-better,
    throughput higher) see one source of truth.
    """
    import jax
    import numpy as np

    import chainermn_tpu as mn
    from chainermn_tpu.parallel import init_tp_transformer_lm
    from chainermn_tpu.serving import AdmissionError, ServingEngine

    vocab, d_model, n_heads, n_layers = 128, 32, 4, 2
    n_slots, n_requests, s_p, new = 4, 8, 8, 8
    params = init_tp_transformer_lm(
        jax.random.PRNGKey(0), vocab, d_model, n_heads, n_layers,
        max_len=s_p + new, pos_impl="rope")
    mesh = mn.make_nd_mesh(("model",), (1,), jax.devices()[:1])
    # ONE seeded arrival source (ISSUE 18 satellite): the scenario
    # engine's staggered generator replaces the hand-rolled loop —
    # event t is in virtual units; each load point scales a unit to
    # submit_every engine steps
    from chainermn_tpu.serving import scenarios as _sc
    arrivals = _sc.staggered(n_requests, 1.0, seed=0, prompt_len=s_p,
                             max_new_tokens=new)
    prompts = [np.asarray(_sc.materialize_prompt(ev["prompt"], vocab),
                          np.int32) for ev in arrivals]

    def run_point(submit_every):
        eng = ServingEngine(params, head_dim=d_model // n_heads,
                            n_slots=n_slots, max_total=s_p + new, mesh=mesh,
                            queue_capacity=n_requests)
        # warm the compiles OUTSIDE the measured window (prefill + tick:
        # max_new=2 keeps the slot active into the tick), then reset the
        # stats clock: cold-compile TTFT is a one-off cost the
        # steady-state serving numbers must not absorb.
        h = eng.submit(prompts[0], 2)
        eng.run(steps_budget=4)
        assert h.status == "done", h.status
        eng.reset_stats()
        nxt, steps = 0, 0
        while nxt < n_requests or eng.pool.busy_count > 0 \
                or eng.scheduler.queue_depth > 0:
            if nxt < n_requests and steps % submit_every == 0 \
                    and steps >= arrivals[nxt]["t"] * submit_every:
                try:
                    eng.submit(prompts[nxt],
                               arrivals[nxt]["max_new_tokens"])
                except AdmissionError:
                    pass  # backpressure counted in rejected_total
                else:
                    nxt += 1
            eng.step()
            steps += 1
            if steps > 40 * n_requests * new:  # safety valve
                break
        m = eng.metrics()
        return {
            "tokens_per_sec": round(m["serving/tokens_per_sec"], 1),
            "ttft_p50_ms": round(m.get("serving/ttft_p50_ms", 0.0), 2),
            "ttft_p99_ms": round(m.get("serving/ttft_p99_ms", 0.0), 2),
            "token_latency_p50_ms": round(
                m.get("serving/token_latency_p50_ms", 0.0), 3),
            "slot_occupancy_pct": round(m["serving/slot_occupancy_pct"], 1),
            "rejected": m["serving/rejected_total"],
            "steps": steps,  # bookkeeping; the gate's _SKIP drops it
        }

    def tick_comm_model():
        """Predicted vs ledgered wire bytes of ONE decode tick at the
        bench config.  The engine's live tick is already compiled (a
        cache-hit trace books nothing), so trace a FRESH build of the
        IDENTICAL program (`_build_tick` closes over the same params/
        specs/mesh) against the warmed pool state."""
        import jax.numpy as jnp

        from chainermn_tpu.serving import ServingEngine as _SE

        eng = _SE(params, head_dim=d_model // n_heads, n_slots=n_slots,
                  max_total=s_p + new, mesh=mesh,
                  queue_capacity=n_requests)
        h = eng.submit(prompts[0], 2)
        eng.run(steps_budget=4)
        assert h.status == "done", h.status
        de = eng.engine
        tokens = jnp.zeros((n_slots,), jnp.int32)
        pos = jnp.asarray(np.array(eng.pool.pos, np.int32, copy=True))
        cm = comm_bytes_model(de._build_tick(), de._params,
                              eng.pool.caches, tokens, pos)
        cm.pop("per_op", None)  # the tick's 2 ops don't warrant rows
        return cm

    def journal_overhead():
        """The causal journal's serving cost (ISSUE 17; the acceptance
        bound is < 3% — cheap enough to leave on in production).

        Differencing journal-on vs journal-off runs of THIS tiny bench
        cannot resolve a 3% bound: adjacent identical runs vary ±40%
        under CI load.  So the overhead is measured directly — the
        journal-on run counts the events the serving path actually
        emits, a microbench prices ONE emit (HLC stamp + JSON encode +
        line-buffered write, the exact production code path, against
        the same configured journal), and ``journal_overhead_frac`` is
        journal-seconds over the run's own measured serving window
        (tokens / tokens_per_sec).  Gates lower-is-better."""
        import shutil
        import tempfile
        import time as _time

        from chainermn_tpu.observability import journal as _journal

        jdir = tempfile.mkdtemp(prefix="bench-journal-")
        _journal.configure(jdir, "bench")
        try:
            on = run_point(1)
            n_events = sum(len(_journal.read_journal(p))
                           for p in _journal.find_journals(jdir))
            reps = 5000
            t0 = _time.perf_counter()
            for i in range(reps):
                _journal.emit("slot", op="bench", alloc=-1, slot=i % 4)
            per_event_s = (_time.perf_counter() - t0) / reps
        finally:
            _journal.reset()
            shutil.rmtree(jdir, ignore_errors=True)
        tokens = max(n_requests - int(on["rejected"]), 1) * new
        window_s = tokens / max(on["tokens_per_sec"], 1e-9)
        return {
            "tokens_per_sec_journal_on": on["tokens_per_sec"],
            "journal_events": n_events,
            "journal_event_cost_us": round(per_event_s * 1e6, 2),
            "journal_overhead_frac": round(
                (n_events * per_event_s) / window_s, 4),
        }

    out = {
        "config": f"d{d_model} L{n_layers} h{n_heads} V{vocab} "
                  f"slots{n_slots} prompt{s_p} new{new} "
                  f"x{n_requests} requests",
        "load_high": run_point(1),
        "load_low": run_point(4),
    }
    try:
        out["journal"] = journal_overhead()
    except Exception as e:
        FAILED.append(f"serving journal overhead")
        print(f"bench: serving journal overhead failed: {e!r}",
              file=sys.stderr)
    try:
        out["comm_per_tick"] = tick_comm_model()
    except Exception as e:
        FAILED.append(f"serving comm model")
        print(f"bench: serving comm model failed: {e!r}", file=sys.stderr)
    return out


def bench_serving_router():
    """Serving FLEET perf (ISSUE 7): the same offered load pushed
    through 1, 2, and 4 router-fronted replicas — TTFT p50/p99, fleet
    tokens/s, occupancy, and the router's shed rate per point.

    The workload is prefix-heavy (every prompt shares one system
    prefix) so the radix-trie prefix cache and the router's
    prefix-affinity dispatch are on the measured path; the offered load
    (submit every fleet round) is sized beyond one replica's capacity,
    so ``replicas_1`` sheds hard and the sweep shows shed rate falling
    and fleet throughput rising with replica count.  Direction under
    the regression gate: ``ttft*/shed*`` lower-is-better, throughput /
    occupancy higher (scripts/check_perf_regression.py).
    """
    import jax
    import numpy as np

    import chainermn_tpu as mn
    from chainermn_tpu.parallel import init_tp_transformer_lm
    from chainermn_tpu.serving import AdmissionError, build_fleet

    vocab, d_model, n_heads, n_layers = 128, 32, 4, 2
    n_slots, n_requests, s_p, new = 2, 16, 8, 8
    params = init_tp_transformer_lm(
        jax.random.PRNGKey(0), vocab, d_model, n_heads, n_layers,
        max_len=s_p + new, pos_impl="rope")
    mesh = mn.make_nd_mesh(("model",), (1,), jax.devices()[:1])
    rs = np.random.RandomState(0)
    shared = rs.randint(0, vocab, s_p - 2)
    prompts = [np.concatenate([shared, rs.randint(0, vocab, 2)])
               .astype(np.int32) for _ in range(n_requests)]

    def run_point(n_replicas):
        router = build_fleet(
            params, n_replicas, head_dim=d_model // n_heads,
            n_slots=n_slots, max_total=s_p + new, mesh=mesh,
            queue_capacity=4)
        # warm every replica's compiles (prefill + tick + prefix copy)
        # outside the measured window, then reset the stats clocks.
        # TWO warm requests per replica: the first (a cold-cache miss)
        # compiles prefill+tick and donates the shared prefix, the
        # second HITS it and compiles the lazy copy_prefix program —
        # otherwise the first measured hit pays that compile inside
        # the gated ttft_p99 window
        for rep in router.replicas:
            for _ in range(2):
                h = rep.submit(prompts[0], 2)
                rep.engine.run(steps_budget=8)
                assert h.status == "done", h.status
            assert rep.engine.engine.prefix_copies >= 1, \
                "warm-up failed to exercise the prefix-copy path"
        router.run(steps_budget=50)
        router.reset_stats()
        nxt, steps, shed = 0, 0, 0
        while nxt < n_requests or any(not rep.idle
                                      for rep in router.replicas):
            if nxt < n_requests:
                try:
                    router.submit(prompts[nxt], new)
                except AdmissionError:
                    shed += 1  # also counted in router/rejected_total
                nxt += 1
            router.step()
            steps += 1
            if steps > 40 * n_requests * new:  # safety valve
                break
        m = router.metrics()
        router.close()
        return {
            "tokens_per_sec": round(m["router/fleet_tokens_per_sec"], 1),
            "ttft_p50_ms": round(m.get("router/fleet_ttft_p50_ms", 0.0),
                                 2),
            "ttft_p99_ms": round(m.get("router/fleet_ttft_p99_ms", 0.0),
                                 2),
            "slot_occupancy_pct": round(
                m["router/fleet_slot_occupancy_pct"], 1),
            "shed_rate": round(m["router/shed_rate"], 4),
            "rejected_queue_full": m["router/rejected/queue_full"],
            "rejected_shed_slo": m["router/rejected/shed_slo"],
            "affinity_dispatches": m["router/affinity_dispatches_total"],
            "steps": steps,  # bookkeeping; the gate's _SKIP drops it
        }

    return {
        "config": f"d{d_model} L{n_layers} h{n_heads} V{vocab} "
                  f"slots{n_slots}/replica prompt{s_p} new{new} "
                  f"x{n_requests} requests, shared {s_p - 2}-token prefix",
        "replicas_1": run_point(1),
        "replicas_2": run_point(2),
        "replicas_4": run_point(4),
    }


def bench_serving_disagg():
    """Disaggregated prefill/decode perf (ISSUE 9, docs/SERVING.md
    "Disaggregated prefill/decode"): the SAME offered load pushed
    through the fused single engine and through 1:1 and 2:1 P:D
    disaggregated fleets — per point the decode tick-GAP p50/p99 +
    variance (the inter-token latency a decoding request actually
    experiences; a prefill between ticks inflates it), TTFT p50/p99,
    fleet tokens/s, and the transfer plane's wall (p50/p99 ms).

    Offered load is wall-clock (one submit every few ms from the
    driver) and every service runs its own background driver —
    role-PARALLEL for the fleets (``DisaggRouter.start()``: one thread
    per role), which is where moving prefill off the decode workers
    becomes observable: the acceptance contract is disagg decode
    ``tick_gap_p99 / tick_gap_p50`` strictly below the fused engine's,
    with each point's goodput queue-wait/compute split as evidence.
    Direction under the regression gate: ``*_ms``/``gap``/``variance``/
    ``transfer`` keys lower-is-better (scripts/check_perf_regression
    .py), throughput higher.
    """
    import jax
    import numpy as np

    import chainermn_tpu as mn
    from chainermn_tpu.parallel import init_tp_transformer_lm
    from chainermn_tpu.serving import (AdmissionError, ServingEngine,
                                       build_disagg_fleet)

    vocab, d_model, n_heads, n_layers = 128, 32, 4, 2
    n_slots, n_requests, s_p, new = 4, 16, 32, 16
    submit_every_s = 0.012
    params = init_tp_transformer_lm(
        jax.random.PRNGKey(0), vocab, d_model, n_heads, n_layers,
        max_len=s_p + new, pos_impl="rope")
    mesh = mn.make_nd_mesh(("model",), (1,), jax.devices()[:1])
    rs = np.random.RandomState(0)
    prompts = [rs.randint(0, vocab, s_p).astype(np.int32)
               for _ in range(n_requests)]

    def drive(service, submit, drained):
        """Fixed wall-clock offered load against a started service."""
        service.start()
        handles, shed = [], 0
        for p in prompts:
            try:
                handles.append(submit(p))
            except AdmissionError:
                shed += 1
            time.sleep(submit_every_s)
        t0 = time.time()
        while not drained() and time.time() - t0 < 120:
            time.sleep(0.005)
        service.stop()
        return handles, shed

    def point_row(m, prefix, shed, goodput):
        gp = {k.rsplit("/", 1)[-1]: v for k, v in goodput.items()}
        return {
            "tick_gap_p50_ms": round(m.get(f"{prefix}_p50_ms", 0.0), 3),
            "tick_gap_p99_ms": round(m.get(f"{prefix}_p99_ms", 0.0), 3),
            "tick_gap_p99_over_p50": round(
                m.get(f"{prefix}_p99_ms", 0.0)
                / max(m.get(f"{prefix}_p50_ms", 1e-9), 1e-9), 3),
            "tick_gap_variance_ms2": round(
                m.get(f"{prefix}_variance_ms2", 0.0), 4),
            "shed": shed,
            "goodput_queue_wait_s": round(gp.get("queue_wait_s", 0.0), 4),
            "goodput_compute_s": round(gp.get("compute_s", 0.0), 4),
        }

    def run_fused():
        eng = ServingEngine(params, head_dim=d_model // n_heads,
                            n_slots=n_slots, max_total=s_p + new,
                            mesh=mesh, queue_capacity=n_requests)
        # warm prefill+tick compiles outside the measured window
        h = eng.submit(prompts[0], 2)
        eng.run(steps_budget=4)
        assert h.status == "done", h.status
        eng.reset_stats()
        handles, shed = drive(
            eng, lambda p: eng.submit(p, new),
            lambda: eng.pool.busy_count == 0
            and eng.scheduler.queue_depth == 0)
        m = eng.metrics()
        row = point_row(m, "serving/tick_gap", shed,
                        {k: v for k, v in m.items() if "goodput" in k})
        row.update({
            "tokens_per_sec": round(m["serving/tokens_per_sec"], 1),
            "ttft_p50_ms": round(m.get("serving/ttft_p50_ms", 0.0), 2),
            "ttft_p99_ms": round(m.get("serving/ttft_p99_ms", 0.0), 2),
            "done": sum(h.status == "done" for h in handles),
        })
        eng.close()
        return row

    def run_disagg(n_p, n_d):
        fleet = build_disagg_fleet(
            params, n_p, n_d, head_dim=d_model // n_heads,
            max_total=s_p + new, n_slots=n_slots, staging_slots=2,
            mesh=mesh, queue_capacity=n_requests,
            transport_mode="local")
        # warm EVERY worker's compiles (prefill + tick + transfer): the
        # least-loaded dispatch spreads one warm request per prefill
        # worker (each owns its own prefill-program family)
        warm = [fleet.submit(prompts[0], 2) for _ in range(n_p)]
        fleet.run(steps_budget=60)
        assert all(h.status == "done" for h in warm), \
            [(h.status, h.finish_reason) for h in warm]
        fleet.reset_stats()
        handles, shed = drive(
            fleet, lambda p: fleet.submit(p, new),
            lambda: all(w.idle for w in fleet.prefill_workers)
            and all(dw.idle for dw in fleet.decode_workers))
        m = fleet.metrics()
        # the decode-side goodput split (queue-wait/compute evidence)
        gp = {}
        for dw in fleet.decode_workers:
            for k, v in dw.engine.goodput.buckets().items():
                gp[f"goodput/{k}_s"] = gp.get(f"goodput/{k}_s", 0.0) + v
        row = point_row(m, "disagg/decode_tick_gap", shed, gp)
        row.update({
            "tokens_per_sec": round(m["disagg/fleet_tokens_per_sec"], 1),
            "ttft_p50_ms": round(m.get("disagg/fleet_ttft_p50_ms", 0.0),
                                 2),
            "ttft_p99_ms": round(m.get("disagg/fleet_ttft_p99_ms", 0.0),
                                 2),
            "transfer_p50_ms": round(m.get("disagg/transfer_p50_ms",
                                           0.0), 3),
            "transfer_p99_ms": round(m.get("disagg/transfer_p99_ms",
                                           0.0), 3),
            "transfers": m["disagg/transfers_total"],
            "requeued": m["disagg/requeued_total"],
            "done": sum(h.status == "done" for h in handles),
        })
        fleet.close()
        return row

    return {
        "config": f"d{d_model} L{n_layers} h{n_heads} V{vocab} "
                  f"slots{n_slots} prompt{s_p} new{new} x{n_requests} "
                  f"requests, submit every {submit_every_s * 1e3:.0f}ms, "
                  f"local transport, role-parallel drive",
        "fused": run_fused(),
        "disagg_1_1": run_disagg(1, 1),
        "disagg_2_1": run_disagg(2, 1),
    }


def bench_serving_autoscale():
    """Elastic autoscaling + multi-tenant QoS perf (ISSUE 11,
    docs/ROBUSTNESS.md "Autoscaling & overload"): does the control loop
    track a diurnal offered-load curve with a burst, without flapping,
    while the paid tenant's TTFT holds and best-effort degrades first?

    A 1-worker cross-process-protocol fleet (in-process runtimes over
    the loopback lanes — the REAL lease/policy/drain code) with the
    autoscaler attached (min 1, max 3) is pushed through five load
    phases (night → morning → PEAK+BURST → evening → night).  Two
    tenants split the traffic: ``gold`` (paid) and ``free``
    (best_effort, concurrency-budgeted).  Recorded:

    * ``worker_trace`` — live worker count at each phase boundary vs
      the offered interarrival (the tracking evidence).
    * ``scale_ups`` / ``scale_downs`` / ``flap`` — ``flap`` re-derives
      the no-flap invariant from the recorded decision history (an
      up-then-down inside one cooldown window); MUST stay 0.
    * ``drain_shed`` — in-flight requests shed by scale-down; every
      shrink is a drain, so this stays 0 (the chaos-tier acceptance).
    * ``shed_rate`` (bounded), ``gold_ttft_p99_ms`` (held),
      ``free_shed`` / ``free_degraded`` / ``max_rung`` — the QoS
      split: best-effort absorbs the burst, machine-readably.

    Every-backend contract; ``flap``/``shed``/``ttft``/``rung``/
    ``degraded`` keys gate lower-is-better in bench_history.jsonl.
    """
    import threading

    import jax
    import numpy as np

    import chainermn_tpu as mn
    from chainermn_tpu.parallel import init_tp_transformer_lm
    from chainermn_tpu.serving import AdmissionError, TenantTable
    from chainermn_tpu.serving.autoscale import (AutoscalePolicy,
                                                 FleetAutoscaler,
                                                 local_spawn_factory)
    from chainermn_tpu.serving.fleet import (build_local_fleet,
                                             submit_with_retry)

    vocab, d_model, n_heads, n_layers = 128, 32, 4, 2
    s_p, new = 16, 12
    params = init_tp_transformer_lm(
        jax.random.PRNGKey(0), vocab, d_model, n_heads, n_layers,
        max_len=s_p + new, pos_impl="rope")
    mesh = mn.make_nd_mesh(("model",), (1,), jax.devices()[:1])
    wk = dict(n_slots=4, max_total=s_p + new, queue_capacity=8,
              mesh=mesh)

    # ONE seeded arrival source: the diurnal curve, its gold/free
    # alternation and every prompt come from the scenario engine —
    # this section no longer hand-rolls its arrival loop
    from chainermn_tpu.serving import scenarios as _sc
    by_phase = {}
    for ev in _sc.diurnal(0, prompt_len=s_p, max_new_tokens=new):
        by_phase.setdefault(ev["phase"], []).append(ev)

    tenancy = TenantTable()
    tenancy.register("gold", "paid")
    tenancy.register("free", "best_effort", max_inflight=3)
    # window 0.05 × (16+1) = 0.85s: this scenario runs up to 4 engine/
    # router threads in ONE process, and a spawned worker's fresh
    # prefill/tick compiles GIL-starve every beat thread for hundreds
    # of ms — a tighter window misreads that as death and sheds its
    # in-flight work, polluting drain_shed with a detection artifact
    # (real fleets are processes; docs/ROBUSTNESS.md lease tuning)
    router, runtimes = build_local_fleet(
        params, {"engine": 1}, head_dim=d_model // n_heads,
        beat_interval_s=0.05, miss_beats=16, worker_kwargs=wk,
        tenancy=tenancy)
    autoscaler = FleetAutoscaler(
        router,
        local_spawn_factory(params, router,
                            head_dim=d_model // n_heads,
                            beat_interval_s=0.05, worker_kwargs=wk,
                            runtimes=runtimes),
        # thresholds sized for the offered curve below: the burst piles
        # ≥5 queued / ≥100 backlog tokens onto one worker, the night
        # phases sit at ~0 — both bands are crossed decisively, so the
        # section is not sensitive to which 20ms sample the policy got
        policies=[AutoscalePolicy(
            role="engine", min_workers=1, max_workers=3,
            up_backlog_tokens_per_worker=32.0,
            down_backlog_tokens_per_worker=4.0,
            up_queue_depth_per_worker=1.5,
            down_queue_depth_per_worker=0.25,
            up_cooldown_s=0.3, down_cooldown_s=0.6,
            down_stable_s=0.5)],
        interval_s=0.02)
    threads = [threading.Thread(target=rt.run, daemon=True)
               for rt in runtimes]
    for t in threads:
        t.start()
    router.start()   # the router thread drives the autoscaler too

    def live_count():
        # snapshot: the router thread's autoscaler mutates the dict
        return sum(1 for w in list(router.workers.values())
                   if w.state in ("starting", "live"))

    sheds = {"gold": 0, "free": 0}

    def offer(events, gap_s):
        handles = []
        for ev in events:
            prompt = np.asarray(
                _sc.materialize_prompt(ev["prompt"], vocab), np.int32)
            try:
                handles.append(submit_with_retry(
                    router.submit, prompt, ev["max_new_tokens"],
                    tenant=ev["tenant"], max_attempts=2))
            except AdmissionError:
                sheds[ev["tenant"]] += 1
            time.sleep(gap_s)
        return handles

    def wait_done(handles, timeout=60):
        t0 = time.time()
        while (any(h.status not in ("done", "evicted") for h in handles)
               and time.time() - t0 < timeout):
            time.sleep(0.005)

    # warm the first worker's compiles outside the measured window
    wait_done(offer(_sc.diurnal(1, phases=(("warm", 2, 0.0),),
                                prompt_len=s_p,
                                max_new_tokens=new), 0.0))

    # diurnal curve + burst: (phase, requests, interarrival seconds)
    phases = _sc.DIURNAL_PHASES
    worker_trace = []
    all_handles = []
    for name, n_req, gap_s in phases:
        hs = offer(by_phase[name], gap_s)
        all_handles.extend(hs)
        if name == "peak_burst":
            # the burst's backlog is the scale-up evidence — sample
            # BEFORE it drains
            time.sleep(0.3)
        worker_trace.append({"phase": name, "offered": n_req,
                             "interarrival_s": gap_s,
                             "live_workers": live_count()})
        wait_done(hs)
    # idle tail: the scale-down half of the curve
    t0 = time.time()
    policy = autoscaler.policies["engine"]
    while policy.downs == 0 and time.time() - t0 < 10.0:
        time.sleep(0.05)
    worker_trace.append({"phase": "idle_tail", "offered": 0,
                         "interarrival_s": None,
                         "live_workers": live_count()})

    m = router.metrics()
    tm = tenancy.metrics()
    done = sum(h.status in ("done", "evicted") for h in all_handles)
    router.stop()
    for rt in runtimes:
        rt.finished = True
    for t in threads:
        t.join(timeout=5)
    router.close()

    drained = [n for n, w in router.workers.items()
               if w.state == "drained"]
    return {
        "config": f"engine fleet 1->3 (autoscaled), d{d_model} "
                  f"L{n_layers} V{vocab} prompt{s_p} new{new}, "
                  f"diurnal {len(phases)} phases + burst, tenants "
                  f"gold(paid)/free(best_effort, max_inflight 3), "
                  f"beat 50ms × miss 16, loopback lanes",
        "worker_trace": worker_trace,
        "peak_workers": max(p["live_workers"] for p in worker_trace),
        "final_workers": worker_trace[-1]["live_workers"],
        "scale_ups": int(policy.ups),
        "scale_downs": int(policy.downs),
        "flap": int(policy.flap_count()),
        "drained_workers": len(drained),
        # every scale-down is a drain: nothing in flight may shed
        "drain_shed": int(m.get("fleet/shed_inflight_total", 0)),
        # spurious in-process deaths (GIL-starved beats) — 0 with the
        # window above; gated lower-is-better via 'detection'
        "worker_lost_detections": int(m.get("fleet/dead_workers", 0)),
        "shed_rate": round(m.get("fleet/shed_rate", 0.0), 4),
        "terminal_frac": round(done / max(len(all_handles), 1), 4),
        "gold_ttft_p99_ms": round(
            tm.get("tenant/gold/ttft_p99_ms", 0.0), 2),
        "free_ttft_p99_ms": round(
            tm.get("tenant/free/ttft_p99_ms", 0.0), 2),
        # symmetric with free_shed: the table already counts EVERY
        # rejected attempt (submit_with_retry give-ups included)
        "gold_shed": int(tm.get("tenant/gold/shed_total", 0)),
        "free_shed": int(tm.get("tenant/free/shed_total", 0)),
        "free_degraded": int(tm.get("tenant/free/degraded_total", 0)),
        "max_rung": max(
            (i for i, name in enumerate(tenancy.ladder.RUNGS)
             if tenancy.ladder.state()["rung_entries"].get(name)),
            default=0),
        "decisions": [
            {k: d.get(k) for k in ("direction", "before", "target",
                                   "reason", "t")}
            for d in policy.decisions],
    }


def bench_serving_chaos():
    """Serving-fleet chaos perf (ISSUE 10, docs/ROBUSTNESS.md "Serving
    failure domains"): what a worker death and a rolling drain actually
    cost, on the gate.

    A 2-worker cross-process-protocol fleet (in-process runtimes over
    the loopback lanes — the REAL mailbox/lease/fencing/failover code,
    no spawn cost) under steady offered load:

    * ``steady_tokens_per_sec`` — pre-fault baseline.
    * ``detection_ms`` — kill one worker mid-decode (heartbeats stop
      dead, exactly a SIGKILL's signature); wall until the supervisor
      marks it dead.  Bounded by ``detection_window_ms`` = beat ×
      (miss_beats + 1).
    * ``failover_ttft_p99_ms`` — TTFT of re-dispatched requests,
      measured from ORIGINAL submit (the failover penalty).
    * ``kill_shed_rate`` — requests shed during the kill window at the
      same offered load (failover should hold it near 0 with a live
      survivor).
    * ``kill_recovery_s`` — wall from the kill until the backlog fully
      drains on the survivor.
    * ``drain_shed`` / ``drain_recovery_frac`` — graceful rolling
      restart: drain a worker (must shed NOTHING, exit cleanly), admit
      a replacement, and the fleet's tokens/s recovers to within 10% of
      the pre-drain steady state (the acceptance bound).

    Every-backend contract; ``detection``/``failover``/``shed``/
    ``recovery_s`` keys gate lower-is-better, ``drain_recovery_frac``
    higher, in bench_history.jsonl.  The whole run records an HLC
    causal journal and replays it through the PR 15 protocol models
    (ISSUE 17): ``conformance_violations`` gates lower-is-better — the
    acceptance bound is 0.
    """
    import shutil
    import tempfile
    import threading

    import jax
    import numpy as np

    import chainermn_tpu as mn
    from chainermn_tpu.parallel import init_tp_transformer_lm
    from chainermn_tpu.serving import AdmissionError
    from chainermn_tpu.serving.fleet import (WorkerClient,
                                             build_local_fleet,
                                             submit_with_retry)
    from chainermn_tpu.serving.worker import WorkerRuntime

    vocab, d_model, n_heads, n_layers = 128, 32, 4, 2
    s_p, new, n_requests = 16, 12, 12
    submit_every_s = 0.008
    params = init_tp_transformer_lm(
        jax.random.PRNGKey(0), vocab, d_model, n_heads, n_layers,
        max_len=s_p + new, pos_impl="rope")
    mesh = mn.make_nd_mesh(("model",), (1,), jax.devices()[:1])
    rs = np.random.RandomState(0)
    prompts = [rs.randint(0, vocab, s_p).astype(np.int32)
               for _ in range(n_requests)]
    wk = dict(n_slots=4, max_total=s_p + new, queue_capacity=n_requests,
              mesh=mesh)

    from chainermn_tpu.observability import journal as _journal
    jdir = tempfile.mkdtemp(prefix="bench-chaos-journal-")
    _journal.configure(jdir, "bench")

    router, runtimes = build_local_fleet(
        params, {"engine": 2}, head_dim=d_model // n_heads,
        beat_interval_s=0.02, miss_beats=4, worker_kwargs=wk)
    threads = [threading.Thread(target=rt.run, daemon=True)
               for rt in runtimes]
    for t in threads:
        t.start()
    router.start()

    def offer(n, shed_box):
        handles = []
        for i in range(n):
            try:
                handles.append(submit_with_retry(
                    router.submit, prompts[i % n_requests], new,
                    max_attempts=3))
            except AdmissionError:
                shed_box[0] += 1
            time.sleep(submit_every_s)
        return handles

    def wait_done(handles, timeout=60):
        t0 = time.time()
        while (any(h.status not in ("done", "evicted") for h in handles)
               and time.time() - t0 < timeout):
            time.sleep(0.005)

    # warm every worker's compiles, then the steady baseline
    warm = offer(4, [0])
    wait_done(warm)
    router.reset_stats()
    shed = [0]
    t0 = time.time()
    handles = offer(n_requests, shed)
    wait_done(handles)
    steady_s = time.time() - t0
    steady_tps = sum(len(h.tokens) for h in handles) / max(steady_s, 1e-9)

    # --- kill one worker mid-decode under live load ---
    router.reset_stats()
    kill_shed = [0]
    t_kill = [None]

    def kill_midway():
        time.sleep(submit_every_s * 3)
        t_kill[0] = time.time()
        runtimes[0].kill()

    killer = threading.Thread(target=kill_midway)
    killer.start()
    handles = offer(n_requests, kill_shed)
    killer.join()
    wait_done(handles)
    kill_recovery_s = time.time() - t_kill[0]
    m = router.metrics()
    terminal = sum(h.status in ("done", "evicted") for h in handles)
    kill_shed_total = kill_shed[0] + int(
        m.get("fleet/shed_inflight_total", 0))

    # --- graceful rolling restart: drain the survivor's sibling -------
    # admit a replacement first so capacity survives the drain
    replacement = WorkerRuntime("engine2", "engine", params,
                                router.store,
                                head_dim=d_model // n_heads, epoch=1,
                                beat_interval_s=0.02, **wk)
    rthread = threading.Thread(target=replacement.run, daemon=True)
    rthread.start()
    router.add_worker(WorkerClient("engine2", "engine", router.store,
                                   epoch=1))
    runtimes.append(replacement)
    threads.append(rthread)
    pre_drain_tps = steady_tps
    m_pre = router.metrics()
    shed_before = (int(m_pre.get("fleet/shed_inflight_total", 0))
                   + int(m_pre.get("fleet/rejected_total", 0)))
    router.drain("engine1")
    drained = router.wait_drained("engine1", timeout_s=30)
    m_post = router.metrics()
    drain_shed = (int(m_post.get("fleet/shed_inflight_total", 0))
                  + int(m_post.get("fleet/rejected_total", 0))
                  - shed_before)
    # warm the replacement's programs outside the measured window
    warm = offer(2, [0])
    wait_done(warm)
    router.reset_stats()
    t0 = time.time()
    post_shed = [0]
    handles = offer(n_requests, post_shed)
    wait_done(handles)
    post_s = time.time() - t0
    post_tps = sum(len(h.tokens) for h in handles) / max(post_s, 1e-9)

    router.stop()
    for rt in runtimes:
        rt.finished = True
    for t in threads:
        t.join(timeout=5)
    router.close()

    # replay the run's causal journal through the protocol models: the
    # kill, the failover, and the drain must all conform (0 violations)
    _journal.reset()
    conformance = {"conformance_ok": None, "conformance_violations": None}
    try:
        from chainermn_tpu.observability.conform import (check_dir,
                                                         render_report)
        report = check_dir(jdir)
        conformance = {
            "conformance_ok": bool(report["ok"]),
            "conformance_violations": len(report["violations"]),
            "conformance_checked": report["checked"],
        }
        if not report["ok"]:
            print(render_report(report), file=sys.stderr)
    except Exception as e:
        FAILED.append(f"chaos conformance replay")
        print(f"bench: chaos conformance replay failed: {e!r}",
              file=sys.stderr)
    finally:
        shutil.rmtree(jdir, ignore_errors=True)

    return {
        **conformance,
        "config": f"2 engine workers (+1 replacement), d{d_model} "
                  f"L{n_layers} V{vocab} prompt{s_p} new{new} "
                  f"x{n_requests}, beat 20ms × miss 4, loopback lanes",
        "steady_tokens_per_sec": round(steady_tps, 1),
        "detection_ms": round(m.get("fleet/detection_ms", 0.0), 1),
        "detection_window_ms": round(router.lease_window_s * 1e3, 1),
        "failover_ttft_p99_ms": round(
            m.get("fleet/failover_ttft_p99_ms", 0.0), 2),
        "redispatched": int(m.get("fleet/redispatched_total", 0)),
        "kill_shed_rate": round(
            kill_shed_total / max(n_requests, 1), 4),
        "kill_terminal_frac": round(terminal / max(n_requests, 1), 4),
        "kill_recovery_s": round(kill_recovery_s, 3),
        "drain_completed": bool(drained),
        "drain_shed": max(drain_shed, 0) if drained else None,
        "post_drain_tokens_per_sec": round(post_tps, 1),
        "drain_recovery_frac": round(
            post_tps / max(pre_drain_tps, 1e-9), 4),
        "fenced_refusals": int(sum(
            v for k, v in m.items()
            if k.startswith("fleet/fenced_refusals/"))),
    }


def bench_serving_kv_economy():
    """Fleet-global KV economy perf (ISSUE 12, docs/SERVING.md "Fleet
    KV economy"): what the global prefix index + remote pulls + the
    host-RAM spill tier actually buy, on the gate.

    A 4-engine-worker fleet (in-process runtimes over the loopback
    lanes — the REAL announce/index/pull/fencing code) under a
    shared-prefix workload: per unique prefix, ONE leader prefills and
    every follower lands on a different worker, whose miss resolves by
    PULLING the slab over the transfer plane instead of re-prefilling.

    * ``prefill_calls_per_unique_prefix`` — THE economy metric:
      fleet-wide prefill calls per unique prefix (1.0 = perfect reuse;
      the pre-ISSUE-12 fleet paid ~1 per REQUEST).  Acceptance bound:
      ≈ 1.
    * ``remote_pull_hit_rate`` — followers served by pull (the rest hit
      a local copy a previous pull already installed).
    * ``leader_ttft_p50_ms`` vs ``pulled_ttft_p50_ms`` — the
      transfer-vs-re-prefill wall, measured end to end.
    * ``stale_fallbacks`` / ``crc_refusals`` — the degrade paths (must
      stay 0 on a healthy run; both gate lower-is-better).
    * ``spill_restore_ms`` vs ``reprefill_ms`` — a 2-slot engine forced
      to scavenge a hot prefix: eviction spills the slab to host RAM,
      the next matching prompt restores it through the compiled inject
      path (CRC verified) instead of re-prefilling.

    Every-backend contract; ``prefill_calls``/``stale``/``spill``/
    ``crc``/``*_ms`` keys gate lower-is-better in bench_history.jsonl.
    """
    import threading

    import jax
    import numpy as np

    import chainermn_tpu as mn
    from chainermn_tpu.parallel import init_tp_transformer_lm
    from chainermn_tpu.serving.fleet import build_local_fleet

    vocab, d_model, n_heads, n_layers = 128, 32, 4, 2
    s_p, new = 24, 6
    n_unique, fanout = 2, 4          # requests per unique prefix
    params = init_tp_transformer_lm(
        jax.random.PRNGKey(0), vocab, d_model, n_heads, n_layers,
        max_len=s_p + new, pos_impl="rope")
    mesh = mn.make_nd_mesh(("model",), (1,), jax.devices()[:1])
    head_dim = d_model // n_heads
    rs = np.random.RandomState(0)
    uniques = [rs.randint(0, vocab, s_p).astype(np.int32)
               for _ in range(n_unique)]
    wk = dict(n_slots=4, max_total=s_p + new, queue_capacity=16,
              mesh=mesh)

    router, runtimes = build_local_fleet(
        params, {"engine": 4}, head_dim=head_dim,
        beat_interval_s=0.02, miss_beats=4, worker_kwargs=wk)
    threads = [threading.Thread(target=rt.run, daemon=True)
               for rt in runtimes]
    for t in threads:
        t.start()
    router.start()

    def wait_done(handles, timeout=120):
        t0 = time.time()
        while (any(h.status not in ("done", "evicted") for h in handles)
               and time.time() - t0 < timeout):
            time.sleep(0.003)
        return [h for h in handles
                if h.status not in ("done", "evicted")]

    # warm every worker's prefill/tick compiles with DISTINCT prompts
    # (same padded length, different content — no cross-hits)
    warm = [router.submit(rs.randint(0, vocab, s_p).astype(np.int32), 2)
            for _ in range(8)]
    wait_done(warm)
    # warm the PULL path too (each worker's inject program compiles on
    # its first landing): one shared warm prefix, leader then fan-out
    warm_shared = rs.randint(0, vocab, s_p).astype(np.int32)
    wait_done([router.submit(warm_shared, 2)])
    time.sleep(0.1)                      # announce lands in the index
    wait_done([router.submit(warm_shared, 2) for _ in range(6)])
    time.sleep(0.1)                      # leases carry warm counters
    m0 = router.metrics()
    prefills_before = m0.get("fleet/cache/prefill_calls", 0.0)
    router.reset_stats()

    # leaders: one prefill per unique prefix, donated + announced
    leaders = [router.submit(p, new) for p in uniques]
    wait_done(leaders)
    time.sleep(0.1)                      # announces land in the index
    # followers: identical prompts, least-loaded spread across the
    # other workers — local misses resolved by remote pulls
    followers = []
    for p in uniques:
        followers += [router.submit(p, new)
                      for _ in range(fanout - 1)]
    hung = wait_done(followers)
    time.sleep(0.1)                      # final lease refresh
    m = router.metrics()
    router.stop()
    for rt in runtimes:
        rt.finished = True
    for t in threads:
        t.join(timeout=5)
    router.close()

    prefill_calls = m.get("fleet/cache/prefill_calls", 0.0) \
        - prefills_before
    leader_ttfts = sorted(h.ttft_ms for h in leaders
                          if h.ttft_ms is not None)
    pulled_ttfts = sorted(h.ttft_ms for h in followers
                          if h.ttft_ms is not None)
    mid = lambda xs: xs[len(xs) // 2] if xs else None  # noqa: E731

    # --- spill tier: eviction -> host RAM -> restore ------------------
    from chainermn_tpu.serving import ServingEngine
    eng = ServingEngine(params, head_dim=head_dim, n_slots=2,
                        max_total=s_p + new, mesh=mesh)
    hot = uniques[0]

    def run_one(prompt):
        t0 = time.time()
        h = eng.submit(prompt, new)
        eng.run()
        return h, (time.time() - t0) * 1e3

    run_one(rs.randint(0, vocab, s_p).astype(np.int32))   # warm compiles
    _, reprefill_ms = run_one(hot)       # prefills + donates the slab
    # churn: enough distinct donations to scavenge (and spill) `hot`
    for _ in range(3):
        run_one(rs.randint(0, vocab, s_p).astype(np.int32))
    spills = eng.spill.spills
    _, restore_ms = run_one(hot)         # spill hit -> compiled restore
    sp = eng.spill.stats()
    eng.close()

    return {
        "config": f"4 engine workers, d{d_model} L{n_layers} V{vocab} "
                  f"prompt{s_p} new{new}, {n_unique} unique prefixes × "
                  f"{fanout} requests, beat 20ms, loopback lanes; "
                  f"spill: 2-slot engine, same model",
        "requests_total": n_unique * fanout,
        "unique_prefixes": n_unique,
        "fleet_prefill_calls": int(prefill_calls),
        "prefill_calls_per_unique_prefix": round(
            prefill_calls / max(n_unique, 1), 3),
        "remote_pulls": int(m.get("fleet/cache/remote_pulls", 0)),
        "remote_pull_hit_rate": round(
            m.get("fleet/cache/remote_pulls", 0.0)
            / max(n_unique * (fanout - 1), 1), 4),
        "index_entries": int(m.get("fleet/cache/index_entries", 0)),
        "stale_fallbacks": int(m.get("fleet/cache/stale_fallbacks", 0)),
        "crc_refusals": int(m.get("fleet/cache/crc_refusals", 0)),
        "orphan_tags_swept": int(
            m.get("fleet/cache/orphan_tags_swept", 0)),
        "hung_requests": len(hung),
        "leader_ttft_p50_ms": (round(mid(leader_ttfts), 2)
                               if leader_ttfts else None),
        "pulled_ttft_p50_ms": (round(mid(pulled_ttfts), 2)
                               if pulled_ttfts else None),
        "spills": int(sp["spills"]),
        "restores": int(sp["restores"]),
        "spilled_before_restore": int(spills),
        "spill_store_bytes": int(sp["bytes"]),
        "reprefill_ms": round(reprefill_ms, 2),
        "spill_restore_ms": round(restore_ms, 2),
    }


def bench_serving_scenarios():
    """Scenario-plane perf (ISSUE 18, docs/SERVING.md "Scenario engine
    & heterogeneous fleet"): seeded, replayable workloads against the
    REAL fleet, plus the zero-shed rolling weight upgrade, on the gate.

    Four scenario matrix rows (each on a FRESH small fleet so the
    metrics are per-scenario, each under its own causal journal):

    * ``diurnal`` — the offered-load curve the autoscale section also
      drives, replayed from the ONE seeded arrival source.
    * ``flash_crowd`` — steady background + a shared-prefix burst.
    * ``adversarial`` — prefix-sniping + long-prompt hog tenants
      against a paid tenant; the acceptance bound is QoS isolation:
      ``tenant_gold_degraded == 0`` (no rung ever clamps the paid
      tenant) while best-effort absorbs the ladder.
    * ``composed_chaos`` — worker kill + flash crowd + SIGSTOP zombie
      in ONE run, on a 2-worker fleet.
    * ``hetero_skew`` — the flash-crowd stream against a size-skewed
      variant PAIR (d32 big + d16 small, ISSUE 19 satellite) behind
      one router, plus pinned probes: per-variant determinism
      (``pin_parity_violations`` == 0), cross-variant divergence
      (``variant_distinct`` == 1), unknown-model shed
      (``unknown_model_refused`` == 1).

    Then the upgrade: a checkpoint-v2 generation (saved SHARDED,
    installed through ``reshard_host``) rolls across a live 2-worker
    fleet — ``rolling_upgrade/drain_shed`` gates at 0 and
    ``parity_violations`` counts pinned pre/post token divergence.

    Every-backend contract; ``shed_rate``/``slo_burn``/``max_rung``/
    ``flap``/``drain_shed``/``*_degraded``/``*_violations`` keys gate
    lower-is-better in bench_history.jsonl.  ``repro_violations``
    counts same-seed digest mismatches (the replayability bound, 0);
    ``conformance_violations`` replays every scenario's journal —
    including the upgrade window — through the PR 15 protocol models
    (the acceptance bound is 0).
    """
    import shutil
    import tempfile
    import threading

    import jax
    import numpy as np

    import chainermn_tpu as mn
    from chainermn_tpu.parallel import init_tp_transformer_lm
    from chainermn_tpu.serving import TenantTable
    from chainermn_tpu.serving import scenarios as _sc
    from chainermn_tpu.serving.fleet import (build_local_fleet,
                                             rolling_upgrade)

    vocab, d_model, n_heads, n_layers = 128, 32, 4, 2
    s_p, new = 16, 8
    params = init_tp_transformer_lm(
        jax.random.PRNGKey(0), vocab, d_model, n_heads, n_layers,
        max_len=64, pos_impl="rope")
    mesh = mn.make_nd_mesh(("model",), (1,), jax.devices()[:1])
    # max_total 64 covers the adversarial hog's near-capacity prompts
    wk = dict(n_slots=4, max_total=64, queue_capacity=24, mesh=mesh)

    from chainermn_tpu.observability import journal as _journal
    from chainermn_tpu.observability.conform import (check_dir,
                                                     render_report)
    jroot = tempfile.mkdtemp(prefix="bench-scenario-journal-")

    # same seed must reproduce the byte-identical stream — gated as an
    # int violation counter (the gate's _flatten drops booleans)
    specs = {
        "diurnal": dict(prompt_len=s_p, max_new_tokens=new,
                        deadline_s=10.0),
        "flash_crowd": dict(prompt_len=s_p, max_new_tokens=new,
                            deadline_s=10.0),
        "adversarial": dict(prompt_len=s_p, max_new_tokens=new,
                            long_prompt_len=48),
        "composed_chaos": dict(prompt_len=s_p, max_new_tokens=new,
                               deadline_s=10.0),
    }
    repro_violations = 0
    streams = {}
    for name, kw in specs.items():
        streams[name] = _sc.build_scenario(name, seed=0, **kw)
        if _sc.stream_digest(streams[name]) != _sc.stream_digest(
                _sc.build_scenario(name, seed=0, **kw)):
            repro_violations += 1

    conformance_violations = 0
    conformance_checked = 0

    def run_one(name, *, n_workers=1, tenants=(), faults=False,
                topology=None, registry=None, jname=None, probe=None):
        nonlocal conformance_violations, conformance_checked
        tenancy = None
        if tenants:
            tenancy = TenantTable()
            for tname, cls, cap in tenants:
                budgets = {} if cap is None else {"max_inflight": cap}
                tenancy.register(tname, cls, **budgets)
        jdir = os.path.join(jroot, jname or name)
        _journal.configure(jdir, "bench")
        router, runtimes = build_local_fleet(
            params, topology or {"engine": n_workers},
            head_dim=d_model // n_heads,
            # wide lease window: in-process prefill compiles stall the
            # GIL for seconds and the scenarios measure workload
            # response, not detection latency (composed_chaos's kill
            # still detects — its settle window dwarfs 0.85 s)
            beat_interval_s=0.05, miss_beats=16, worker_kwargs=wk,
            tenancy=tenancy, registry=registry)
        threads = [threading.Thread(target=rt.run, daemon=True)
                   for rt in runtimes]
        for t in threads:
            t.start()
        router.start()
        try:
            # warm every prompt-length compile outside the window —
            # pinned per variant on a heterogeneous fleet (each model
            # compiles its own prefill programs)
            pins = registry.ids() if registry is not None else [None]
            for plen in sorted({ev["prompt"]["len"]
                                for ev in streams[name]
                                if ev["kind"] == "request"}):
                for mid in pins:
                    h = router.submit(np.zeros(plen, np.int32), 2,
                                      model_id=mid)
                    t0 = time.time()
                    while (h.status not in ("done", "evicted")
                           and time.time() - t0 < 30):
                        time.sleep(0.005)
            router.reset_stats()
            out = _sc.run_scenario(
                streams[name], router, vocab=vocab,
                runtimes=runtimes if faults else (),
                tenancy=tenancy, max_attempts=2, settle_timeout_s=60.0)
            if probe is not None:
                out.update(probe(router))
        finally:
            router.stop()
            for rt in runtimes:
                rt.finished = True
            for t in threads:
                t.join(timeout=5)
            router.close()
            _journal.reset()
        report = check_dir(jdir)
        conformance_checked += int(sum(report["checked"].values()))
        if not report["ok"]:
            conformance_violations += len(report["violations"])
            print(render_report(report), file=sys.stderr)
        return out

    result = {}
    try:
        # 2 workers: the peak burst must land in queue capacity, not
        # overflow into worker-side shed-backs (the scenario measures
        # the curve's response, not an undersized fleet's collapse)
        result["diurnal"] = run_one("diurnal", n_workers=2)
        result["flash_crowd"] = run_one("flash_crowd", n_workers=2)
        result["adversarial"] = run_one(
            "adversarial",
            tenants=(("gold", "paid", None),
                     ("sniper", "best_effort", 2),
                     ("hog", "best_effort", 2)))
        result["composed_chaos"] = run_one("composed_chaos",
                                           n_workers=2, faults=True)

        # --- size-skewed variant pair on ONE fleet (ISSUE 19) ---------
        # A d32 "big" and a d16 "small" variant behind one router: the
        # flash-crowd burst routes unpinned across both (the token-unit
        # least-loaded order exists for exactly this skew), then pinned
        # probes assert variant isolation — greedy decodes are
        # deterministic per variant and the two weight sets must
        # disagree on the same prompt.
        from chainermn_tpu.serving.models import (ModelRegistry,
                                                  ModelVariant)
        from chainermn_tpu.serving.scheduler import AdmissionError
        params_small = init_tp_transformer_lm(
            jax.random.PRNGKey(1), vocab, 16, 2, 1, max_len=64,
            pos_impl="rope")
        registry = ModelRegistry()
        registry.register(ModelVariant(
            "lm-big", params, head_dim=d_model // n_heads))
        # the size skew is real capacity: the small variant affords
        # twice the decode slots on the same footprint
        registry.register(ModelVariant(
            "lm-small", params_small, head_dim=8,
            worker_kwargs=dict(n_slots=8)))
        hetero_prompt = np.arange(s_p, dtype=np.int32) % vocab

        def hetero_probe(router):
            def pinned(mid):
                h = router.submit(hetero_prompt, new, model_id=mid)
                t0 = time.time()
                while (h.status not in ("done", "evicted")
                       and time.time() - t0 < 30):
                    time.sleep(0.005)
                return list(h.tokens)

            big, small = pinned("lm-big"), pinned("lm-small")
            try:
                router.submit(hetero_prompt, new, model_id="lm-ghost")
                ghost_refused = 0
            except AdmissionError:
                ghost_refused = 1
            return {
                "variants": 2,
                # pinned greedy decode is deterministic per variant
                "pin_parity_violations": (int(big != pinned("lm-big"))
                                          + int(small
                                                != pinned("lm-small"))),
                # different weights must disagree (bound: 1)
                "variant_distinct": int(big != small),
                # an unregistered model_id must shed, not misroute
                "unknown_model_refused": ghost_refused,
            }

        result["hetero_skew"] = run_one(
            "flash_crowd", topology={"engine": ["lm-big", "lm-small"]},
            registry=registry, jname="hetero_skew",
            probe=hetero_probe)

        # --- rolling weight upgrade on a live 2-worker fleet ----------
        jdir = os.path.join(jroot, "rolling_upgrade")
        _journal.configure(jdir, "bench")
        router, runtimes = build_local_fleet(
            params, {"engine": 2}, head_dim=d_model // n_heads,
            beat_interval_s=0.05, miss_beats=16, worker_kwargs=wk)
        threads = [threading.Thread(target=rt.run, daemon=True)
                   for rt in runtimes]
        for t in threads:
            t.start()
        router.start()
        try:
            pinned = np.arange(s_p, dtype=np.int32) % vocab

            def decode_pinned():
                h = router.submit(pinned, new)
                t0 = time.time()
                while (h.status not in ("done", "evicted")
                       and time.time() - t0 < 30):
                    time.sleep(0.005)
                return list(h.tokens)

            before = decode_pinned()
            # checkpoint v2: the same values RE-SAVED by a 2-process
            # world with the embedding row-sharded — reshard_host must
            # reassemble them bit-for-bit on install
            params_np = jax.tree_util.tree_map(np.asarray, params)
            layout = jax.tree_util.tree_map(lambda x: None, params_np)
            layout["embed"] = 0
            shards = []
            for i in range(2):
                s = jax.tree_util.tree_map(lambda x: x, params_np)
                s["embed"] = np.split(params_np["embed"], 2, axis=0)[i]
                shards.append(s)
            t_up = time.time()
            report = rolling_upgrade(
                router, runtimes, shards, layout, generation=2,
                head_dim=d_model // n_heads, worker_kwargs=wk,
                timeout_s=60.0)
            upgrade_wall_s = time.time() - t_up
            after = decode_pinned()
            m = router.metrics()
            result["rolling_upgrade"] = {
                "upgraded": len(report["upgraded"]),
                "upgrade_wall_s": round(upgrade_wall_s, 3),
                # the acceptance bound: a drain sheds NOTHING
                "drain_shed": int(report["drain_shed"]),
                "rejected_during_upgrade": int(report["rejected_delta"]),
                # pinned pre/post token divergence (bound: 0)
                "parity_violations": int(before != after),
                "live_generation": max(
                    w.weights_generation
                    for w in router.workers.values()
                    if w.state in ("starting", "live")),
                "fenced_refusals": int(sum(
                    v for k, v in m.items()
                    if k.startswith("fleet/fenced_refusals/"))),
            }
        finally:
            router.stop()
            for rt in runtimes:
                rt.finished = True
            for t in threads:
                t.join(timeout=5)
            router.close()
            _journal.reset()
        report = check_dir(jdir)
        conformance_checked += int(sum(report["checked"].values()))
        if not report["ok"]:
            conformance_violations += len(report["violations"])
            print(render_report(report), file=sys.stderr)
    finally:
        shutil.rmtree(jroot, ignore_errors=True)

    result.update({
        "config": f"per-scenario fleets (1-2 engine workers), "
                  f"d{d_model} L{n_layers} V{vocab} prompt{s_p} "
                  f"new{new}, seed 0, beat 50ms × miss 16, "
                  f"loopback lanes",
        "repro_violations": repro_violations,
        "conformance_violations": conformance_violations,
        "conformance_checked": conformance_checked,
    })
    return result


def bench_collective_schedules():
    """Collective schedule compile plane (ISSUE 19, docs/ANALYSIS.md
    "Schedule verifier"): every fleet-reachable reshard spec pair is
    lowered to candidate comm programs (single / chunked / pipelined /
    hierarchically staged), every candidate passes the FULL static
    verifier (byte coverage vs the array_split statics, exhaustive BFS
    of the start/done machine, interpreter byte-exactness), and the
    cheapest verified candidate under the r04 cost model is chosen.

    Host-only (stdlib + numpy; no device work) — every-backend
    contract.  Gated keys: per-pair ``speedup_vs_single`` and the
    headline ``hier_speedup`` higher-is-better (acceptance bound: the
    hierarchical candidate beats the single-collective baseline on the
    ICI+DCN fan-out pair, > 1.0); ``*_cost_ms``/``*_bytes``/
    ``*_violations`` lower-is-better (both violation counters bound at
    0); ``faults_caught``/``verified_pairs`` higher-is-better (the
    seeded-fault corpus: every expressible mutation caught — 0 false
    negatives — on schedules whose clean forms all verify).
    """
    from chainermn_tpu.analysis import schedule as S
    from chainermn_tpu.analysis import schedule_check as SC

    shape, dtype = (24, 4), "float32"
    result = {}
    schedule_violations = 0
    hier_speedup = None
    for name, src, dst, sw, dw in SC.FLEET_PAIRS:
        topo = SC.fleet_pair_topology(sw, dw)
        try:
            sched, report = SC.compile_verified(
                shape, dtype, src, dst, sw, dw, topo)
        except RuntimeError as e:
            schedule_violations += 1
            print(f"bench: schedule pair {name} failed verification: "
                  f"{e}", file=sys.stderr)
            continue
        result[name] = {
            "chosen": report["kind"],
            "best_cost_ms": report["cost_ms"],
            "single_cost_ms": report["baseline_cost_ms"],
            "speedup_vs_single": round(report["speedup_vs_single"], 4),
            "ici_bytes": report["ici_bytes"],
            "dcn_bytes": report["dcn_bytes"],
        }
        if name == "rolling_upgrade_fanout":
            hier_speedup = report["speedup_vs_single"]

    # seeded-fault corpus: each mutator class on a hierarchical and a
    # flat chunked schedule — the verifier must catch every expressible
    # fault (0 false negatives) and pass both clean forms (0 false
    # positives, enforced above by compile_verified raising)
    faults_checked = faults_caught = fault_miss_violations = 0
    topo = S.Topology(2, 2)
    for sched in (
            S.lower_hierarchical(shape, dtype, 0, None, 4, 4, topo,
                                 n_chunks=2),
            S.lower_chunked(shape, dtype, 0, None, 4, 4, topo,
                            n_chunks=2)):
        for fault in SC.SEEDED_FAULTS:
            try:
                bad = SC.seed_fault(sched, fault)
            except ValueError:
                continue  # fault class not expressible on this shape
            faults_checked += 1
            if SC.verify_schedule(bad).ok:
                fault_miss_violations += 1
            else:
                faults_caught += 1

    result.update({
        "config": f"shape {shape} {dtype}, chunks 2 depth 2, r04 cost "
                  f"model, {len(SC.FLEET_PAIRS)} fleet pairs",
        "verified_pairs": len(SC.FLEET_PAIRS) - schedule_violations,
        "schedule_violations": schedule_violations,
        "hier_speedup": (round(hier_speedup, 4)
                         if hier_speedup is not None else None),
        "faults_checked": faults_checked,
        "faults_caught": faults_caught,
        "fault_miss_violations": fault_miss_violations,
    })
    return result


def bench_schedule_truth():
    """Schedule execution truth plane (ISSUE 20, docs/PERF.md
    "Cost-model calibration loop"): every fleet pair's chosen schedule
    EXECUTES under the ``ScheduleExecProfile``, measured transfer
    bytes reconcile EXACTLY against the IR's declared wire bytes, a
    per-link (alpha, bw) calibration is least-squares-fitted from the
    pooled records, and both the stock r04 constants and the
    calibrated model re-price every pair against its measured wall.

    Host-only (stdlib + numpy; no device work) — every-backend
    contract.  Gated keys: ``median_rel_err_stock`` /
    ``median_rel_err_calibrated`` lower-is-better (the acceptance
    criterion: calibrated prediction error <= stock on this host);
    ``wire_exposed_frac`` lower-is-better — the fraction of measured
    wire time EXPOSED on the critical path, i.e. the gateable face of
    the overlap fraction (``overlap_frac`` = 1 - exposed, reported
    alongside); ``profiler_overhead_frac`` lower-is-better (< 3%
    acceptance bound, measured directly per the PR 17
    ``journal_overhead_frac`` discipline — differencing adjacent runs
    cannot resolve 3% under CI load); ``reconcile_violations``
    lower-is-better (bound: 0 — a byte the profiler saw that the IR
    did not declare is a bug, not noise).  Per-pair raw walls live
    under ``raw`` (skipped by the gate: single host timings swing
    ±40% under CI load; the medians above are the stable faces).
    """
    import time as _time

    from chainermn_tpu.analysis import calibrate as C
    from chainermn_tpu.analysis import schedule as S
    from chainermn_tpu.analysis import schedule_check as SC
    from chainermn_tpu.observability import comm as _comm

    # MUCH larger than the verifier's (24,4): per-op walls must
    # dominate both clock granularity and the ~1us/record profiler
    # cost for the fit — and the overhead gate — to mean anything
    # (reshard_host's real payloads are model weights, MiBs+).  The
    # BFS model check's state space depends on program structure, not
    # element count, so verification cost stays put.
    shape, dtype = (1 << 17, 16), "float32"   # 8 MiB array
    reps = 3
    result = {"config": f"shape {shape} {dtype}, {reps} reps/pair, "
                        f"{len(SC.FLEET_PAIRS)} fleet pairs, "
                        f"least-squares per-link fit"}
    all_records = []
    pairs = {}
    reconcile_violations = 0
    for name, src, dst, sw, dw in SC.FLEET_PAIRS:
        topo = SC.fleet_pair_topology(sw, dw)
        sched, report = SC.compile_verified(
            shape, dtype, src, dst, sw, dw, topo)
        _, prof = SC.execute_profiled(sched, reps=reps)
        for run in prof.runs():
            reconcile_violations += len(prof.reconcile(run))
        all_records.extend(prof.records)
        walls = sorted(prof.wall_us(run) for run in prof.runs())
        pairs[name] = {
            "sched": sched, "prof": prof,
            "measured_wall_us": walls[len(walls) // 2],  # median rep
        }

    cal = C.fit_calibration(all_records)
    _comm.set_active_calibration(cal)  # /statusz calibration provider
    errs_stock, errs_cal, exposed, overlaps = [], [], [], []
    for name, row in pairs.items():
        sched, prof = row["sched"], row["prof"]
        m = row["measured_wall_us"]
        pred_stock = S.price_schedule(sched)["wall_us"]
        pred_cal = S.price_schedule(sched, calibration=cal)["wall_us"]
        re_stock = abs(pred_stock - m) / m if m else 0.0
        re_cal = abs(pred_cal - m) / m if m else 0.0
        errs_stock.append(re_stock)
        errs_cal.append(re_cal)
        cp = C.schedule_critical_path(prof.records)
        exposed.append(cp["wire_exposed_frac"])
        overlaps.append(cp["overlap_frac"])
        result[name] = {
            "chosen": sched.kind,
            "dominant_link": cp["dominant_link"],
            "dominant_op": cp["dominant_op"],
            "raw": {
                "measured_wall_us": round(m, 1),
                "predicted_stock_us": round(pred_stock, 1),
                "predicted_calibrated_us": round(pred_cal, 1),
                "rel_err_stock": round(re_stock, 4),
                "rel_err_calibrated": round(re_cal, 4),
                "critical_path_us": round(cp["critical_path_us"], 1),
                "wire_exposed_frac": round(cp["wire_exposed_frac"], 4),
                "overlap_frac": round(cp["overlap_frac"], 4),
            },
        }

    def med(xs):
        xs = sorted(xs)
        return xs[len(xs) // 2] if xs else 0.0

    # profiler overhead measured DIRECTLY (the PR 17 discipline): count
    # the records one execution of every pair produces, microbench one
    # on_op (two clock reads + record build, the exact production
    # path), and divide by the pairs' own measured walls.
    mb_sched = pairs["rolling_upgrade_fanout"]["sched"]
    mb_prof = SC.ScheduleExecProfile(mb_sched)
    mb_op = next(op for r in sorted(mb_sched.programs)
                 for op in mb_sched.programs[r] if op.kind == "start")
    mb_reps = 20000
    t0 = _time.perf_counter()
    for _ in range(mb_reps):
        tb = mb_prof.now_ns()
        mb_prof.on_op(mb_op, 0, tb, mb_prof.now_ns())
    per_record_s = (_time.perf_counter() - t0) / mb_reps
    records_one_rep = sum(len(row["prof"].run_records())
                          for row in pairs.values())
    window_s = sum(row["measured_wall_us"]
                   for row in pairs.values()) / 1e6
    result.update({
        "reconcile_violations": reconcile_violations,
        "calibration": {
            link: {"alpha_us": round(fit["alpha_s"] * 1e6, 3),
                   "bw_gbps": round(fit["bw"] / 1e9, 4),
                   "fit_residual": round(fit["residual_rel"], 4),
                   "n": fit["n"]}
            for link, fit in sorted(cal["links"].items())},
        "median_rel_err_stock": round(med(errs_stock), 4),
        "median_rel_err_calibrated": round(med(errs_cal), 4),
        "calibration_improves": bool(med(errs_cal) <= med(errs_stock)),
        "wire_exposed_frac": round(med(exposed), 4),
        "overlap_frac": round(med(overlaps), 4),
        "profiler_record_cost_us": round(per_record_s * 1e6, 3),
        "profiler_overhead_frac": round(
            (records_one_rep * per_record_s) / max(window_s, 1e-9), 4),
    })
    return result


def bench_elastic_resume():
    """Elastic/preemption robustness perf (ISSUE 8, docs/ROBUSTNESS.md):
    what fault tolerance actually costs, on the gate.

    * ``save_latency_s`` / ``restore_latency_s`` — one v2-manifest
      checkpoint generation (sync write path) of a ~6 MB state.
    * ``reshard_wall_s`` — the host-side n=4 → n=2 re-partition
      (``reshard_host``) of that state per the manifest layout: the
      added cost of resuming on a SMALLER world.
    * ``steps_to_recover_*`` — through the REAL maybe_load machinery: a
      run preempted at iteration 13 with periodic saves every 5.  The
      bounded-grace final save makes recovery exact (0 steps replayed);
      without it the periodic cadence pays its expected replay (3 here).
    * ``prefetch_step_ms_off/on`` + ``prefetch_gain_frac`` — the
      double-buffered input pipeline (ROADMAP 5a): demo-MLP steps with
      the synchronous handoff vs the one-deep background prefetcher.
      ``prefetch_gain_frac`` is the throughput gain, i.e. the
      ``mfu_useful`` delta the goodput bucket table books (the compute
      FLOPs are unchanged; only wall time moves).

    Runs on every backend (host-side machinery + the CPU demo step);
    keys ride bench_history.jsonl, latency/steps lower-is-better under
    scripts/check_perf_regression.py.
    """
    import shutil
    import tempfile

    import jax
    import numpy as np
    import optax

    import chainermn_tpu as mn
    from chainermn_tpu.extensions import create_multi_node_checkpointer
    from chainermn_tpu.iterators import SerialIterator
    from chainermn_tpu.parallel.reshard import reshard_host
    from chainermn_tpu.train import make_demo_step, replicate
    from chainermn_tpu.training.updaters import StandardUpdater

    rng = np.random.RandomState(0)
    # ~6 MB: a small model's params + one flat optimizer-moment vector
    # (the leaf shape ZeRO-1/elastic resume shards along axis 0)
    state = {
        "params": {f"w{i}": rng.randn(256, 256).astype(np.float32)
                   for i in range(8)},
        "m": rng.randn(16 * 256 * 256).astype(np.float32),
        "iteration": 0,
    }
    state_mb = sum(a.nbytes for a in jax.tree_util.tree_leaves(state)
                   if hasattr(a, "nbytes")) / 1e6
    paths = [jax.tree_util.keystr(kp) for kp, _ in
             jax.tree_util.tree_flatten_with_path(state)[0]]
    m_key = next(p for p in paths if "'m'" in p)
    layout = {m_key: ["sharded", 0]}
    spec_host = {"params": {f"w{i}": None for i in range(8)}, "m": 0,
                 "iteration": None}

    comm = mn.create_communicator("xla", devices=jax.devices()[:1])
    out = {"state_mb": round(state_mb, 1)}

    tmp = tempfile.mkdtemp(prefix="bench-elastic-")
    try:
        cp = create_multi_node_checkpointer(
            "bench", comm, path=tmp, keep=10, async_write=False,
            layout=layout)
        # save / restore latency (sync path: the number the preemption
        # grace budget must cover)
        saves = []
        for rep in range(3):
            t0 = time.perf_counter()
            cp.save(state, iteration=rep)
            saves.append(time.perf_counter() - t0)
        out["save_latency_s"] = round(min(saves), 4)
        t0 = time.perf_counter()
        loaded, it = cp.maybe_load()
        out["restore_latency_s"] = round(time.perf_counter() - t0, 4)
        assert it == 2

        # host-side elastic reshard n=4 -> n=2 (the resume-time add-on)
        shards4 = reshard_host([state], None, spec_host, 4)
        walls = []
        for _ in range(3):
            t0 = time.perf_counter()
            shards2 = reshard_host(shards4, spec_host, spec_host, 2)
            walls.append(time.perf_counter() - t0)
        np.testing.assert_array_equal(
            np.concatenate([s["m"] for s in shards2]), state["m"])
        out["reshard_wall_s"] = round(min(walls), 4)
        out["reshard_throughput_mb"] = round(state_mb / min(walls), 1)

        # steps-to-recover through the real machinery: periodic saves at
        # 5 and 10, preempted at 13 with the bounded-grace final save
        cp.finalize()
        cp = create_multi_node_checkpointer(
            "bench", comm, path=tmp, keep=10, async_write=False,
            layout=layout)
        for it in (5, 10, 13):   # 13 = the preemption handler's save
            state["iteration"] = it
            cp.save(state, iteration=it)
        _, resumed = cp.maybe_load()
        out["steps_to_recover_final_save"] = 13 - resumed
        os.unlink(cp._filename(13))           # no final save (SIGKILL)
        _, resumed = cp.maybe_load()
        out["steps_to_recover_periodic_only"] = 13 - resumed
        cp.finalize()
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    # double-buffered input prefetch (ROADMAP 5a): demo step, sync vs
    # prefetched host->device handoff
    in_dim, n_classes, batch, steps = 32, 10, 256, 30
    w_true = np.random.RandomState(42).randn(in_dim, n_classes)
    xs = np.random.RandomState(0).randn(4096, in_dim).astype(np.float32)
    ys = (xs @ w_true).argmax(-1).astype(np.int32)
    dataset = list(zip(xs, ys))
    mesh = comm.mesh
    optimizer = optax.sgd(0.05, momentum=0.9)
    params = {
        "w1": (np.random.RandomState(1).randn(in_dim, 64) / 6
               ).astype(np.float32),
        "b1": np.zeros((64,), np.float32),
        "w2": (np.random.RandomState(2).randn(64, n_classes) / 8
               ).astype(np.float32),
        "b2": np.zeros((n_classes,), np.float32),
    }

    def run_mode(prefetch):
        step = make_demo_step(optimizer, mesh=mesh)
        st = replicate((params, optimizer.init(params)), mesh)
        upd = StandardUpdater(
            SerialIterator(dataset, batch, seed=0), step, st, mesh=mesh,
            prefetch=prefetch)
        for _ in range(5):  # warm the compile + the prefetch pipeline
            upd.update()
        t0 = time.perf_counter()
        for _ in range(steps):
            obs = upd.update()
        wall = time.perf_counter() - t0
        upd.close()
        return wall / steps * 1e3, obs

    off_ms, _ = run_mode(False)
    on_ms, _ = run_mode(True)
    out["prefetch_step_ms_off"] = round(off_ms, 3)
    out["prefetch_step_ms_on"] = round(on_ms, 3)
    # the mfu_useful delta: compute per step is identical, so the
    # useful-throughput gain is exactly the wall-time ratio
    out["prefetch_gain_frac"] = round(max(0.0, 1.0 - on_ms / off_ms), 4)
    return out


def bench_train_chaos():
    """Self-healing training gang (ISSUE 13): what a mid-training rank
    death costs with live shrink vs the checkpoint-restart fallback.

    An n=4 gang runs lockstep collectives over the lane side channel
    with per-rank heartbeat leases; member 2 dies (stops beating and
    participating — the in-process stand-in for SIGKILL; the REAL
    multi-process SIGKILL is tests/test_chaos_gang.py's job) right
    before a step's allreduce:

    * ``detection_ms`` — wall time from death to the survivors'
      ``RankLostError`` NAMING the rank, vs ``detection_window_ms`` =
      beat × (miss_beats + 1).
    * ``consensus_wall_ms`` / ``reshard_wall_ms`` / ``reconfig_wall_ms``
      — the membership agreement, the ``reshard_host`` re-partition of
      the n=4 momentum blocks onto n=3, and the whole heal() wall.
    * ``steps_lost_live_shrink`` — completed steps re-executed after the
      live shrink (MUST stay 0: survivors resume from the last completed
      step off the shard leases, no checkpoint read) vs
      ``steps_lost_checkpoint_restart`` — what the same death costs
      through the PR 8 path at the periodic cadence (here: save every
      5, death after step 8 completes → 3 steps replayed).
    * ``step_collective_ms`` — steady-state per-step side-channel wall,
      so the health plane's own overhead rides the gate too.

    Every-backend contract (pure host machinery); ``detection``/
    ``consensus``/``reconfig``/``reshard``/``steps_lost`` keys gate
    lower-is-better in bench_history.jsonl.
    """
    import shutil
    import tempfile
    import threading

    import numpy as np

    from chainermn_tpu.extensions.gang import SelfHealingGang
    from chainermn_tpu.health import RankLostError, detection_window_s
    from chainermn_tpu.parallel.reshard import reshard_host
    from chainermn_tpu.serving.lanes import FileLaneStore

    N, VICTIM, KILL_AT, TOTAL, M = 4, 2, 9, 12, 24
    BEAT, MISS, CKPT_EVERY = 0.02, 3, 5
    tmp = tempfile.mkdtemp(prefix="bench-train-chaos-")
    from chainermn_tpu.observability import journal as _journal
    jdir = tempfile.mkdtemp(prefix="bench-train-journal-")
    _journal.configure(jdir, "bench")
    try:
        store = FileLaneStore(tmp)
        gangs = [SelfHealingGang(store, rank=i, world=N, name="bench",
                                 beat_interval_s=BEAT, miss_beats=MISS,
                                 min_world=2, register_provider=False)
                 for i in range(N)]
        for g in gangs:
            g.start()

        t_kill = [None]
        res = {}
        logical = np.arange(M, dtype=np.float64)

        def member(i):
            g = gangs[i]
            block = logical.reshape(N, -1)[i].copy()
            step_walls, detect_ms, rc_info = [], None, None
            it = 0
            while it < TOTAL:
                if i == VICTIM and it == KILL_AT:
                    t_kill[0] = time.perf_counter()
                    g.stop(release=False)  # lease goes stale: "SIGKILL"
                    res[i] = {"died_at": it}
                    return
                try:
                    t0 = time.perf_counter()
                    total = g.allreduce(1.0, label=f"s{it}")
                    step_walls.append(time.perf_counter() - t0)
                    assert total == float(g.world), total
                    block = block + 1.0
                    g.publish_shard(it, block)
                    it += 1
                except RankLostError as e:
                    # t_kill can still be None on a SPURIOUS pre-kill
                    # detection (in-process beat threads starved past
                    # the tight 80ms window under CI load) — record no
                    # latency rather than crashing the section
                    detect_ms = (None if t_kill[0] is None else
                                 (time.perf_counter() - t_kill[0]) * 1e3)

                    def repartition(rc):
                        order = rc.old_members
                        shards = [{"m": rc.shards[m]["payload"]}
                                  for m in order]
                        return reshard_host(shards, {"m": 0}, {"m": 0},
                                            rc.new_world)

                    rc = g.heal(repartition=repartition)
                    assert rc.resume_iteration() == it - 1, (
                        rc.resume_iteration(), it)
                    block = rc.repartitioned[rc.new_rank]["m"]
                    rc_info = rc.summary()
                    rc_info["missing"] = sorted(e.ranks)
            # exactness: the logical array survived the shrink
            res[i] = {"detect_ms": detect_ms, "rc": rc_info,
                      "block": block,
                      "step_ms": sorted(step_walls)[len(step_walls) // 2]
                      * 1e3}

        threads = [threading.Thread(target=member, args=(i,))
                   for i in range(N)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert all(not t.is_alive() for t in threads), "gang bench hung"
        survivors = [res[i] for i in range(N) if i != VICTIM]
        assert all(s.get("rc") for s in survivors), res
        full = np.concatenate([s["block"] for s in survivors])
        np.testing.assert_array_equal(full, logical + TOTAL)

        rc = survivors[0]["rc"]
        last_completed = KILL_AT - 1
        dms = [s["detect_ms"] for s in survivors
               if s.get("detect_ms") is not None]
        out = {
            "world": N,
            "detection_ms": round(min(dms), 1) if dms else None,
            "detection_window_ms": round(
                detection_window_s(BEAT, MISS) * 1e3, 1),
            "consensus_wall_ms": rc["consensus_wall_ms"],
            "reshard_wall_ms": rc["reshard_wall_ms"],
            "reconfig_wall_ms": round(
                rc["consensus_wall_ms"] + (rc["reshard_wall_ms"] or 0.0),
                1),
            "step_collective_ms": round(
                max(s["step_ms"] for s in survivors), 2),
            # live shrink resumes at the failed step: completed steps
            # replayed == 0; the checkpoint fallback replays back to the
            # last periodic generation
            "steps_lost_live_shrink": last_completed
            - rc["resume_iteration"],
            "steps_lost_checkpoint_restart": last_completed
            - (last_completed // CKPT_EVERY) * CKPT_EVERY,
            "fenced_refusals": sum(
                gangs[i].fenced_refusals().get("lease", 0)
                for i in range(N) if i != VICTIM),
        }
        for i in range(N):
            if i != VICTIM:
                gangs[i].stop()
        # conformance verdict for the gang run (ISSUE 17): the victim's
        # stale lease and the survivors' reconfig must replay cleanly
        _journal.reset()
        try:
            from chainermn_tpu.observability.conform import check_dir
            report = check_dir(jdir)
            out["conformance_ok"] = bool(report["ok"])
            out["conformance_violations"] = len(report["violations"])
        except Exception as e:
            FAILED.append(f"train chaos conformance replay")
            print(f"bench: train chaos conformance replay failed: {e!r}",
                  file=sys.stderr)
        return out
    finally:
        _journal.reset()
        shutil.rmtree(tmp, ignore_errors=True)
        shutil.rmtree(jdir, ignore_errors=True)


def scaling_worker(n, grad_dtype=None, double_buffering=False):
    """Subprocess body: weak-scaling point on an n-device virtual CPU mesh.

    Besides the train-step throughput, directly times the gradient-sized
    pmean ALONE (scan-chained inside one jit) so the sweep can attribute
    efficiency loss to the wire collective vs everything else."""
    import time as _time

    import jax
    import jax.numpy as jnp
    import numpy as np

    # a virtual-mesh harness by construction: pinned to the CPU backend
    # in-process, whatever the parent ran on
    jax.config.update("jax_platforms", "cpu")
    # per-chip batch 4 (was 8, round 5): halves every point's step time
    # so the median-of-3 epochs and the two n=8 extras fit the budget —
    # the weak-scaling statement (fixed per-chip batch, efficiency vs
    # n=1) is unchanged.
    step, variables, opt_state, batch, n_chips, global_batch = build_step(
        "resnet18", 32, 4, allreduce_grad_dtype=grad_dtype,
        double_buffering=double_buffering)
    assert n_chips == n, (n_chips, n)
    # wire-byte model per scaling point — BEFORE measure() compiles the
    # step (trace-time bookings; see comm_bytes_model).  The compressed
    # points' whole purpose is fewer wire bytes: with these two fields
    # in every point, the history gate catches a quantization/compression
    # change that silently regresses bytes while time stays flat.
    cm = None
    try:
        cm = comm_bytes_model(step, variables, opt_state, batch)
    except Exception as e:
        FAILED.append(f"scaling comm model")
        print(f"bench: scaling comm model failed: {e!r}", file=sys.stderr)
    steps = 3 if n <= 4 else 2
    # median-of-3: a single-sample point on a time-shared host published a
    # 116.9% efficiency in one earlier run — noise, but it reads as a claim.
    dt, _ = measure(step, variables, opt_state, batch, steps=steps,
                    epochs=3, reduce="median")
    out = {"n": n, "total_ips": steps * global_batch / dt,
           "step_ms": dt / steps * 1e3}
    if cm is not None:
        out["predicted_comm_bytes"] = cm["predicted_comm_bytes"]
        out["measured_comm_bytes"] = cm["measured_comm_bytes"]

    # gradient-sized pmean in isolation (same dtype as the wire)
    if n > 1:
        from jax.sharding import PartitionSpec as P
        from jax import shard_map

        import chainermn_tpu as mn

        mesh = mn.make_mesh(axis_name="mn")
        sizes = [int(np.prod(l.shape)) for l in
                 jax.tree_util.tree_leaves(variables["params"])]
        payload = jnp.zeros((sum(sizes),),
                            jnp.bfloat16 if grad_dtype else jnp.float32)
        reps = 10

        @jax.jit
        def psum_chain(x):
            def body(c, _):
                return jax.lax.pmean(c, "mn") * 0.999, None
            y, _ = jax.lax.scan(body, x, None, length=reps)
            return y.sum()

        run = jax.jit(shard_map(psum_chain, mesh=mesh, in_specs=P(),
                                out_specs=P()))
        float(np.asarray(run(payload)))  # compile
        t0 = _time.perf_counter()
        float(np.asarray(run(payload)))
        out["grad_pmean_ms"] = (_time.perf_counter() - t0) / reps * 1e3
        out["grad_bytes"] = int(payload.size * payload.dtype.itemsize)
    print(json.dumps(out))


def run_scaling_sweep(ns=(1, 8, 4), over_budget=None, budget_left=None):
    """Weak-scaling sweep in fresh CPU subprocesses (platform is per-process).

    Reports per-point efficiency vs n=1 and the measured gradient-pmean
    time, plus two extra n=8 points so the reference's v1.2 headline
    features (SURVEY.md §6) each have a recorded number: a COMPRESSED
    point (bf16 wire, ``compressed_bf16_n8``) and a DOUBLE-BUFFERED point
    (1-step-stale overlap, ``double_buffered_n8``).  The extras run
    immediately after the n=1 base — BEFORE the remaining plain points —
    because in round 4 they ran last and the budget gate nulled them out
    of the official artifact (earlier review).  Each point is
    the MEDIAN of 3 timing epochs (see ``measure``), and n=2 moved behind
    ``--full-sweep`` to pay for the extra epochs.

    Default tops out at n=8: docs/SCALING.md shows the n=16/32 tail
    measures single-core XLA host scheduling, not interconnect, and its
    16-50s steps are what timed out the round-3 driver bench
    (an earlier run was killed at its limit, rc=124).  ``--full-sweep`` restores it.  Every point
    — including the two extras — is additionally gated on the
    ``over_budget`` callable so a slow host degrades gracefully instead
    of losing the whole artifact, and each subprocess's timeout is capped
    by ``budget_left`` so a single slow point cannot overrun the budget
    by its full 1800 s allowance."""
    over_budget = over_budget or (lambda: False)
    budget_left = budget_left or (lambda: 1800.0)
    def run_point(n, grad_dtype=None, double_buffering=False):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                            + f" --xla_force_host_platform_device_count={n}")
        tag = (f"n={n}" + (f" wire={grad_dtype}" if grad_dtype else "")
               + (" double-buffered" if double_buffering else ""))
        print(f"bench: scaling point {tag} ...", file=sys.stderr)
        cmd = [sys.executable, os.path.abspath(__file__),
               "--scaling-worker", str(n)]
        if grad_dtype:
            cmd += ["--allreduce-grad-dtype", grad_dtype]
        if double_buffering:
            cmd += ["--double-buffering"]
        out = None
        try:
            out = subprocess.run(cmd, capture_output=True, text=True,
                                 timeout=min(1800.0, max(60.0, budget_left())),
                                 env=env)
            return json.loads(out.stdout.strip().splitlines()[-1])
        except Exception as e:
            FAILED.append(f"scaling point {tag}")
            print(f"bench: scaling point {tag} failed: {e!r}\n"
                  f"{out.stderr[-2000:] if out is not None else ''}",
                  file=sys.stderr)
            return None

    def finalize_point(p, base):
        if not p:
            return p
        if base:
            p["eff_pct"] = round(100.0 * p["total_ips"] / base, 1)
        p["total_ips"] = round(p["total_ips"], 2)
        for k in ("step_ms", "grad_pmean_ms"):
            if k in p:
                p[k] = round(p[k], 1)
        return p

    # Order (round-5 directive): the n=1 base, then the two reference-v1.2
    # headline extras (compressed bf16 wire, double-buffered overlap) so
    # they land in the driver artifact even if the budget later runs out —
    # in round 4 they ran LAST and were both null purely for budget —
    # then the remaining plain points.
    points = {"1": run_point(1)} if not over_budget() else {}
    base = (points.get("1") or {}).get("total_ips")
    compressed = (finalize_point(run_point(8, grad_dtype="bfloat16"), base)
                  if base and not over_budget() else None)
    double_buf = (finalize_point(run_point(8, double_buffering=True), base)
                  if base and not over_budget() else None)
    for n in ns:
        if str(n) in points:
            continue
        if over_budget():
            print(f"bench: over budget — scaling sweep stops before n={n}",
                  file=sys.stderr)
            break
        points[str(n)] = run_point(n)
    for p in points.values():
        finalize_point(p, base)
    eff8 = (points.get("8") or {}).get("eff_pct")
    try:
        cores = os.cpu_count()
    except Exception:
        cores = None
    return {"platform": "cpu",   # children are pinned to the CPU backend
            "per_chip_batch": 4, "arch": "resnet18", "points": points,
            "compressed_bf16_n8": compressed,
            "double_buffered_n8": double_buf,
            "efficiency_pct": eff8,
            "host_physical_cores": cores,
            "total_ips": {k: (p or {}).get("total_ips") for k, p in
                          points.items()},
            "note": "virtual CPU mesh TIME-SHARED on the host cores "
                    "(this box: see host_physical_cores): ideal weak "
                    "scaling = flat TOTAL throughput, and the efficiency "
                    "loss measures XLA per-device scheduling + emulated "
                    "collective overhead, NOT interconnect behavior — "
                    "grad_pmean_ms (the wire collective timed alone, "
                    "scan-chained) gives the collective's share directly; "
                    "see projected_scaling for the ICI-based pod "
                    "projection from measured single-chip quantities"}


def quantized_worker(n):
    """Subprocess body (``--quantized-worker N``): the ISSUE 14 quantized
    allreduce matrix on an n-device virtual CPU mesh.

    * ``ips`` — train-step throughput for the five contenders: plain
      fp32, double-buffered fp32 (1-step-stale overlap), compressed
      bf16, the block-scaled int8+EF ring (``quantized``), and the
      combined quantized+double-buffered mode (``quantized_db``) —
      shared MLP (~0.6M params), fixed per-chip batch: the weak-scaling
      statement.
    * ``accuracy`` — grad-cosine vs the exact fp32 mean for every
      (wire_dtype, block, k) point, on a fixed heavy-tailed payload:
      the accuracy-vs-wire-bytes table (wire bytes from
      ``quantized_ring_cost``, axis-size exact).
    * ``quant_wire_bytes`` / ``quant_predicted_bytes`` — the quantized
      step's measured comm-ledger bytes vs the static model (the drift
      gate pair, same mechanism as every scaling point).
    * ``ef_loss_gap`` — |loss(int8+EF) − loss(fp32)| / |loss(fp32)|
      after a 30-step run on the same data (the EF acceptance number).
    """
    import time as _time

    import jax

    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import numpy as np
    import optax

    import chainermn_tpu as mn
    from chainermn_tpu.ops.collective import (choose_pipeline_depth,
                                              quantized_ring_cost)

    D_IN, D_H, D_OUT, B = 256, 1024, 256, 8
    rng = np.random.RandomState(0)
    params0 = {
        "w1": (rng.randn(D_IN, D_H) / 16).astype(np.float32),
        "b1": np.zeros((D_H,), np.float32),
        "w2": (rng.randn(D_H, D_OUT) / 32).astype(np.float32),
        "b2": np.zeros((D_OUT,), np.float32),
    }
    n_grad = sum(int(np.prod(v.shape)) for v in params0.values())
    # the alpha/bw cost model picks the pipeline depth for the TIMED
    # quantized configs (chunk = the per-rank int8 ring chunk)
    k_auto = choose_pipeline_depth(-(-n_grad // max(n, 1)))

    def loss_fn(p, batch):
        h = jnp.tanh(batch[0] @ p["w1"] + p["b1"])
        pred = h @ p["w2"] + p["b2"]
        return jnp.mean((pred - batch[1]) ** 2)

    def build(dtype=None, ef=False, db=False, block=256, k=1,
              donate=True):
        comm = mn.create_communicator("xla")
        mesh = comm.mesh
        opt = mn.create_multi_node_optimizer(
            optax.sgd(0.01, momentum=0.9), comm,
            allreduce_grad_dtype=dtype, double_buffering=db,
            error_feedback=ef, quant_block=block, quant_pipeline=k)
        step = mn.make_train_step(loss_fn, opt, mesh=mesh, donate=donate,
                                  allreduce_grad_dtype=dtype,
                                  error_feedback=ef)
        ps = mn.replicate(params0, mesh)
        st = jax.device_put(opt.init(ps))
        b_rng = np.random.RandomState(1)
        xb = mn.shard_batch(
            (b_rng.randn(B * comm.size, D_IN).astype(np.float32),
             b_rng.randn(B * comm.size, D_OUT).astype(np.float32)), mesh)
        return step, ps, st, xb, comm.size

    configs = {
        "fp32": {},
        "double_buffered": {"db": True},
        "bf16": {"dtype": "bfloat16"},
        "quantized": {"dtype": "int8", "ef": True, "k": k_auto},
        "quantized_db": {"dtype": "int8", "ef": True, "db": True,
                         "k": k_auto},
    }
    # This host's virtual-mesh timings drift by 2-3x over seconds, so
    # per-config epochs are INTERLEAVED round-robin (every config sees
    # the same drift profile) and the per-config MEDIAN is reported.
    steps, epochs = 6, 7
    runs = {}
    for name, c in configs.items():
        step, ps, st, xb, world = build(**c)
        for _ in range(2):  # compile + warmup
            ps, st, loss = step(ps, st, xb)
        float(loss)
        runs[name] = {"step": step, "ps": ps, "st": st, "xb": xb,
                      "world": world, "dts": []}
    for _ in range(epochs):
        for name, r in runs.items():
            t0 = _time.perf_counter()
            ps, st = r["ps"], r["st"]
            for _ in range(steps):
                ps, st, loss = r["step"](ps, st, r["xb"])
            float(loss)  # host readback = the timing barrier
            r["ps"], r["st"] = ps, st
            r["dts"].append(_time.perf_counter() - t0)
    def ips_of(r):
        dts = sorted(r["dts"])
        return steps * B * r["world"] / dts[len(dts) // 2]
    out = {"n": n, "pipeline_k": k_auto, "per_chip_batch": B,
           "grad_bytes_fp32": n_grad * 4,
           "ips": {name: round(ips_of(r), 2) for name, r in runs.items()}}

    # wire-byte model for the quantized step: the trace-time ledger
    # (compressed-wire convention: ~1 byte/element for the bucket + the
    # 4-byte loss pmean) vs the SAME convention out of
    # quantized_ring_cost — the drift-gate pair, byte-exact
    try:
        step, ps, st, xb, _ = build(dtype="int8", ef=True, k=k_auto,
                                    donate=False)
        cm = comm_bytes_model(step, ps, st, xb)
        out["quant_wire_bytes"] = cm["measured_comm_bytes"]
        out["quant_predicted_bytes"] = (
            quantized_ring_cost(n_grad, n, "int8", 256,
                                k_auto)["ledger_bytes"]
            + 4)  # + the loss pmean's scalar
        if out["quant_wire_bytes"] != out["quant_predicted_bytes"]:
            print(f"bench: WARNING quantized ledger "
                  f"{out['quant_wire_bytes']} != static "
                  f"{out['quant_predicted_bytes']}", file=sys.stderr)
    except Exception as e:
        FAILED.append(f"quantized comm model")
        print(f"bench: quantized comm model failed: {e!r}", file=sys.stderr)

    # accuracy-vs-wire-bytes sweep: grad cosine against the exact mean
    if n > 1:
        from jax.sharding import PartitionSpec as P

        from chainermn_tpu._compat import shard_map
        from chainermn_tpu.ops.collective import quantized_ring_pmean

        mesh = mn.make_mesh(axis_name="mn")
        a_rng = np.random.RandomState(4)
        payload = (a_rng.lognormal(0.0, 2.0, (n, 1 << 14)).astype(np.float32)
                   * np.sign(a_rng.randn(n, 1 << 14)).astype(np.float32))
        exact = payload.mean(axis=0)

        def cosine(got):
            num = float(np.dot(got, exact))
            den = float(np.linalg.norm(got) * np.linalg.norm(exact))
            return num / den if den else 0.0

        acc = {}
        for block in (64, 256, 1024):
            for k in (1, 2, 4):
                fn = shard_map(
                    lambda v, _b=block, _k=k: quantized_ring_pmean(
                        v[0], "mn", "int8", _b, _k)[None],
                    mesh=mesh, in_specs=P("mn"), out_specs=P("mn"))
                got = np.asarray(jax.jit(fn)(payload))[0]
                cost = quantized_ring_cost(1 << 14, n, "int8", block, k)
                acc[f"int8_b{block}_k{k}"] = {
                    "grad_cosine": round(cosine(got), 6),
                    "wire_bytes": cost["wire_bytes"],
                    "scale_bytes": cost["scale_bytes"],
                }
        bf = shard_map(
            lambda v: jax.lax.pmean(v[0].astype(jnp.bfloat16),
                                    "mn").astype(jnp.float32)[None],
            mesh=mesh, in_specs=P("mn"), out_specs=P("mn"))
        from chainermn_tpu.ops.collective import collective_wire_cost
        acc["bf16"] = {
            "grad_cosine": round(cosine(np.asarray(jax.jit(bf)(payload))[0]),
                                 6),
            "wire_bytes": collective_wire_cost(
                "psum", (1 << 14) * 2, n)["wire_bytes"],
            "scale_bytes": 0,
        }
        out["accuracy"] = acc

    # EF acceptance number: 30-step loss gap vs fp32 on the same data
    def short_run(dtype=None, ef=False):
        step, ps, st, xb, _ = build(dtype=dtype, ef=ef, k=k_auto,
                                    donate=False)
        for _ in range(30):
            ps, st, loss = step(ps, st, xb)
        return float(loss)

    l32 = short_run()
    lef = short_run("int8", True)
    out["ef_loss_gap"] = round(abs(lef - l32) / max(abs(l32), 1e-12), 6)
    print(json.dumps(out))


def run_quantized_sweep(over_budget=None, budget_left=None):
    """The ISSUE 14 ``quantized_allreduce`` section: fresh-subprocess
    points at n ∈ {1, 2, 4, 8} (same mechanics as the scaling sweep),
    folded into per-config weak-scaling efficiencies against the n=1
    fp32 base, plus the accuracy table and the acceptance verdict —
    ``quantized_eff8 >= double_buffered_eff8`` and the combined mode
    beating both.  Gate keys (`check_perf_regression.py --history`,
    direction-aware): ``quantized_eff8`` / ``quantized_db_eff8`` higher
    is better, ``quant_wire_bytes`` / ``ef_loss_gap`` lower."""
    over_budget = over_budget or (lambda: False)
    budget_left = budget_left or (lambda: 1800.0)

    def run_point(n):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                            + f" --xla_force_host_platform_device_count={n}")
        print(f"bench: quantized point n={n} ...", file=sys.stderr)
        cmd = [sys.executable, os.path.abspath(__file__),
               "--quantized-worker", str(n)]
        out = None
        try:
            out = subprocess.run(cmd, capture_output=True, text=True,
                                 timeout=min(900.0, max(60.0, budget_left())),
                                 env=env)
            return json.loads(out.stdout.strip().splitlines()[-1])
        except Exception as e:
            FAILED.append(f"quantized point n={n}")
            print(f"bench: quantized point n={n} failed: {e!r}\n"
                  f"{out.stderr[-2000:] if out is not None else ''}",
                  file=sys.stderr)
            return None

    points = {}
    for n in (1, 8, 4, 2):
        if over_budget():
            print(f"bench: over budget — quantized sweep stops before "
                  f"n={n}", file=sys.stderr)
            break
        points[str(n)] = run_point(n)

    base = ((points.get("1") or {}).get("ips") or {}).get("fp32")
    effs = {}
    for n_str, p in points.items():
        if not p or not base:
            continue
        n = int(n_str)
        effs[n_str] = {cfg: round(100.0 * ips / (n * base), 1)
                       for cfg, ips in p["ips"].items()}
    e8 = effs.get("8", {})
    verdict = None
    if {"quantized", "double_buffered", "quantized_db"} <= set(e8):
        verdict = {
            "quantized_ge_double_buffered":
                e8["quantized"] >= e8["double_buffered"],
            "combined_beats_both":
                e8["quantized_db"] > max(e8["quantized"],
                                         e8["double_buffered"]),
        }
        verdict["holds"] = all(verdict.values())
        if not verdict["holds"]:
            print("bench: WARNING quantized acceptance ordering does NOT "
                  f"hold measured on this host: {e8} — on the emulated "
                  "mesh quant/dequant runs on the same cores as the "
                  "'wire' memcpys, so the int8 ring's arithmetic costs "
                  "about what its 4x byte saving buys back; on-chip the "
                  "VPU does that math at HBM speed overlapped with the "
                  "DMA (EQuARX's measured result), which is what the "
                  "wire_bound_projection prices", file=sys.stderr)
    p8 = points.get("8") or {}
    # Deterministic ordering statement from the r04 alpha/bw model: at
    # n=8 with per-step compute C and modeled wire time W(dtype),
    #   T(quantized)    = C + W(int8)      (no overlap)
    #   T(double_buf)   = max(C, W(fp32))  (1-step staleness hides wire)
    #   T(quantized_db) = max(C, W(int8))  (combined: both levers)
    # In the wire-bound regime (W(fp32) > C — multislice DCN, large
    # worlds, small per-chip batch) the combined mode wins strictly and
    # quantized alone beats double-buffered; compute-bound regimes tie
    # at C.  Priced for both an ICI ring and the 4x64 multislice DCN
    # case via project_dp_scaling.
    projection = None
    if p8.get("grad_bytes_fp32"):
        gb = p8["grad_bytes_fp32"]
        # per-chip batch comes from the n=1 point's own record, so the
        # worker's B and this back-derivation can never drift apart
        b1 = (points.get("1") or {}).get("per_chip_batch", 8)
        step_ms_1 = 1000.0 * b1 / base if base else None
        if step_ms_1:
            fp32p = project_dp_scaling(step_ms_1, gb, "v5e", 4)
            int8p = project_dp_scaling(step_ms_1, gb, "v5e", 1)
            w32 = fp32p["points"]["8"]["allreduce_ms"]
            wq = int8p["points"]["8"]["allreduce_ms"]
            # the wire-bound statement at a compute time of W32/4 (the
            # regime the motivation names: overlap-starved compressed
            # path) — pure arithmetic, host-independent
            c = w32 / 4.0
            t = {"quantized": c + wq, "double_buffered": max(c, w32),
                 "quantized_db": max(c, wq)}
            projection = {
                "fp32_wire": fp32p,
                "int8_wire": int8p,
                "wire_bound_n8": {
                    "compute_ms": round(c, 4),
                    "step_ms": {k2: round(v, 4) for k2, v in t.items()},
                    "quantized_ge_double_buffered":
                        t["quantized"] <= t["double_buffered"],
                    "combined_beats_both":
                        t["quantized_db"] < min(t["quantized"],
                                                t["double_buffered"]),
                },
            }
    return {
        "points": points,
        "efficiency_pct": effs,
        "quantized_eff8": e8.get("quantized"),
        "quantized_db_eff8": e8.get("quantized_db"),
        "double_buffered_eff8": e8.get("double_buffered"),
        "unquantized_eff8": e8.get("fp32"),
        "quant_wire_bytes": p8.get("quant_wire_bytes"),
        "quant_predicted_bytes": p8.get("quant_predicted_bytes"),
        "ef_loss_gap": p8.get("ef_loss_gap"),
        "platform": "cpu",       # children are pinned to the CPU backend
        "accuracy_n8": p8.get("accuracy"),
        "acceptance": verdict,
        "projection": projection,
        "note": "weak-scaling efficiencies vs the n=1 fp32 base on a "
                "TIME-SHARED virtual CPU mesh (collectives are memcpys: "
                "wire-byte savings mostly cancel against the ring's "
                "op-count overhead here — the projection row prices the "
                "ICI ordering); accuracy table: grad cosine vs exact "
                "fp32 mean, wire/scale bytes from quantized_ring_cost",
    }


def project_dp_scaling(step_ms: float, grad_bytes: int, device_kind: str,
                       wire_dtype_bytes: int = 4):
    """Project DP allreduce scaling efficiency to pod scale from measured
    single-chip quantities + public interconnect specs.

    Methodology (docs/SCALING.md): a bidirectional-ring allreduce moves
    ``2·(P-1)/P · bytes`` per chip; time = α·(P-1) + that / BW_ici.  One
    chip cannot measure ICI, so BW/α come from public v5e specs (stated
    below); step time and gradient size ARE measured.  The multislice row
    models the ICI-reduce → DCN-cross-slice → ICI-bcast two-tier mean of
    ``ops.collective.hierarchical_pmean`` with the slice count's share of
    DCN per host.  Efficiency assumes NO compute/comm overlap — a lower
    bound; the double-buffered optimizer hides most of the wire time.
    """
    # Interconnect specs per generation (public material); unknown kinds
    # fall back to v5e numbers WITH the mismatch flagged in the output.
    ici_specs = {
        "v5e": (1.8e11, 4), "v5 lite": (1.8e11, 4),
        "v4": (2.4e11, 4), "v5p": (4.8e11, 4),
        "v6e": (3.6e11, 4), "trillium": (3.6e11, 4),
    }
    kind = device_kind.lower()
    match = next((k for k in ici_specs if k in kind), None)
    bw_ici, chips_per_host = ici_specs[match or "v5e"]
    assumptions = {
        "ici_bw_bytes_per_s": bw_ici,
        "ici_spec_source": (f"{match} table entry" if match else
                            f"v5e defaults ({device_kind!r} not in table)"),
        "ici_alpha_us_per_hop": 1.0,
        "dcn_bw_bytes_per_s_per_host": 2.5e10,  # 200 Gbps NIC per host
        "chips_per_host": chips_per_host,
        "overlap": "none (lower bound); double-buffering hides wire time",
    }
    wire = grad_bytes * wire_dtype_bytes // 4
    out = {"assumptions": assumptions, "measured_step_ms": step_ms,
           "grad_bytes_fp32": grad_bytes, "points": {}}
    for p in (8, 64, 256):
        ring = 2.0 * (p - 1) / p * wire / assumptions["ici_bw_bytes_per_s"]
        ring += (p - 1) * assumptions["ici_alpha_us_per_hop"] * 1e-6
        eff = step_ms / (step_ms + ring * 1e3) * 100.0
        out["points"][str(p)] = {
            "allreduce_ms": round(ring * 1e3, 2),
            "efficiency_pct": round(eff, 1),
        }
    # 256 chips as 4 slices of 64 over DCN (hierarchical_pmean path):
    # ICI reduce within slice + cross-slice exchange of the full gradient
    # per host-pair over DCN + ICI bcast.
    slices, per_slice = 4, 64
    ici = 2.0 * (per_slice - 1) / per_slice * wire / assumptions[
        "ici_bw_bytes_per_s"] * 2  # reduce + bcast legs
    hosts_per_slice = per_slice // assumptions["chips_per_host"]
    dcn = (2.0 * (slices - 1) / slices * wire / hosts_per_slice
           / assumptions["dcn_bw_bytes_per_s_per_host"])
    eff = step_ms / (step_ms + (ici + dcn) * 1e3) * 100.0
    out["points"]["256_multislice_4x64"] = {
        "allreduce_ms": round((ici + dcn) * 1e3, 2),
        "efficiency_pct": round(eff, 1),
        "dcn_share_ms": round(dcn * 1e3, 2),
    }
    return out


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--scaling-worker", type=int, default=None)
    parser.add_argument("--quantized-worker", type=int, default=None)
    parser.add_argument("--allreduce-grad-dtype", default=None)
    parser.add_argument("--double-buffering", action="store_true")
    parser.add_argument("--skip-scaling", action="store_true")
    parser.add_argument("--full-sweep", action="store_true",
                        help="include the n=16/32 virtual-mesh points "
                             "(slow; measures host scheduling only)")
    parser.add_argument("--trace-out", default=None,
                        help="enable the observability tracer and write a "
                             "Chrome-trace/Perfetto JSON here (re-exported "
                             "after every section, so a killed run still "
                             "leaves a loadable artifact)")
    parser.add_argument("--json-out", default=None,
                        help="write the full result dict (section -> stats, "
                             "the BENCH_*.json 'parsed' shape) to this file, "
                             "atomically re-written after every section — "
                             "the perf-trajectory input that "
                             "scripts/check_perf_regression.py diffs")
    parser.add_argument("--history-out", default="bench_history.jsonl",
                        help="append ONE record "
                             "({n, cmd, rc, t, parsed}) per run to this "
                             "JSONL trajectory; "
                             "scripts/check_perf_regression.py --history "
                             "gates the newest round against the previous "
                             "one (empty string disables)")
    parser.add_argument("--statusz-port", type=int, default=None,
                        help="live introspection HTTP server (/statusz "
                             "/metricsz /debugz) for watching a long "
                             "bench run; 0 picks a free port")
    args = parser.parse_args()

    if args.scaling_worker is not None:
        scaling_worker(args.scaling_worker, args.allreduce_grad_dtype,
                       double_buffering=args.double_buffering)
        return
    if args.quantized_worker is not None:
        quantized_worker(args.quantized_worker)
        return

    # Timeout-proofing (after a run died rc=124 with nothing parseable):
    # the result JSON line is emitted INCREMENTALLY — once as soon as the
    # headline section completes (first few minutes), then re-emitted in
    # full after every later section.  A driver that keeps the last
    # parseable stdout line therefore always captures a complete headline
    # no matter when it kills the process.  Optional sections additionally
    # respect a wall-clock budget, and the scaling sweep is gated
    # per-point.
    t_start = time.time()
    # 1100 s budget lands the default run at ~18.5 min wall (measured
    # 20m03s at 1200 s, round 4) — margin under any plausible driver
    # timeout; every section still completed within it.
    budget_s = float(os.environ.get("BENCH_BUDGET_S", 1100))

    def over_budget():
        return time.time() - t_start > budget_s

    import jax

    from chainermn_tpu.topology import enable_compile_cache

    enable_compile_cache()
    obs = None
    if args.trace_out:
        from chainermn_tpu import observability as obs
        obs.enable()
    statusz = None
    if args.statusz_port is not None:
        from chainermn_tpu.observability import introspect as _introspect
        # /debugz?dump=1 needs somewhere to land: next to --json-out if
        # given, else the repo's conventional result dir
        dump_dir = (os.path.dirname(os.path.abspath(args.json_out))
                    if args.json_out else "result")
        statusz = _introspect.start_status_server(
            args.statusz_port, dump_dir=dump_dir)

    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    per_chip_batch = 128 if on_tpu else 8
    image_size = 224 if on_tpu else 32
    # 40 steps per host readback on TPU amortize the readback.
    steps = 40 if on_tpu else 2

    step, variables, opt_state, batch, n_chips, global_batch = build_step(
        "resnet50", image_size, per_chip_batch, args.allreduce_grad_dtype)
    import numpy as _np
    grad_bytes = int(sum(
        _np.prod(l.shape) for l in
        jax.tree_util.tree_leaves(variables["params"])) * 4)
    # predicted vs ledgered wire bytes — BEFORE the AOT lower (a cache-
    # hit trace books nothing); one extra host-side trace, no execution
    comm_model = None
    try:
        comm_model = comm_bytes_model(step, variables, opt_state, batch)
    except Exception as e:
        FAILED.append(f"comm model")
        print(f"bench: comm model failed: {e!r}", file=sys.stderr)
    step, flops_per_step, bytes_per_step = compile_with_flops(
        step, variables, opt_state, batch)
    dt, _ = measure(step, variables, opt_state, batch, steps)
    ips_per_chip = steps * global_batch / dt / n_chips

    # --- MFU + sanity bound ------------------------------------------------
    peak = peak_flops_for(dev.device_kind) if on_tpu else None
    mfu = None
    flops_suspect = False  # XLA's FLOP count itself looks elided
    mfu_suspect = False    # timing implies >peak throughput
    flops_per_image = None
    # analytic cross-check: ResNet-50 fwd ~4.1 GFLOP/img at 224^2
    # (scales ~(S/224)^2); training ~3x fwd.
    analytic = 3 * 4.1e9 * (image_size / 224.0) ** 2
    flops_source = "compiled"
    if flops_per_step:
        flops_per_image = flops_per_step / (global_batch / n_chips)
        # If XLA's count is under a quarter of analytic, the compiled
        # program is not doing the work.
        if flops_per_image < analytic / 4:
            flops_suspect = True
            print(f"bench: WARNING compiled FLOPs/image {flops_per_image:.3g} "
                  f"<< analytic {analytic:.3g} — work is being elided",
                  file=sys.stderr)
    elif on_tpu:
        # No compiled count (AOT unavailable on this platform) — fall back
        # to the analytic estimate so the physical-plausibility check still
        # runs; without it an impossible timing would sail through as
        # suspect=false, which is exactly the failure mode this bench
        # exists to prevent.
        flops_per_image = analytic
        flops_per_step = analytic * (global_batch / n_chips)
        flops_source = "analytic"
        print(f"bench: using analytic FLOP estimate {analytic:.3g}/image "
              f"for MFU (compiled cost_analysis unavailable)", file=sys.stderr)
    if peak and flops_per_step:
        mfu = flops_per_step * steps / dt / peak
        if mfu > 1.0:
            mfu_suspect = True
            print(f"bench: WARNING MFU {mfu:.2f} > 1.0 is PHYSICALLY "
                  f"IMPOSSIBLE on {dev.device_kind} (peak {peak:.3g} FLOP/s) "
                  f"— the platform is eliding or misreporting work; the "
                  f"throughput number is NOT credible", file=sys.stderr)
    elif on_tpu and not peak:
        print(f"bench: unknown device_kind {dev.device_kind!r}; MFU skipped",
              file=sys.stderr)

    def mfu_of(ips):
        if peak and flops_per_image:
            return round(ips * flops_per_image / peak, 4)
        return None

    def mfu_useful_of(ips):
        # MLPerf-style utilization from ANALYTIC model FLOPs; the compiled
        # count runs ~2x higher for conv backwards (docs/PERF.md).
        return round(ips * analytic / peak, 4) if peak else None

    # --- HBM roofline: is the step bandwidth- or compute-bound? ----------
    roofline = None
    bw = hbm_bw_for(dev.device_kind) if on_tpu else None
    if bw and peak and flops_per_step and bytes_per_step:
        t_mxu = flops_per_step / peak * 1e3
        t_hbm = bytes_per_step / bw * 1e3
        roofline = {
            "bytes_per_step": round(bytes_per_step),
            "t_mxu_ms": round(t_mxu, 2),
            "t_hbm_ms": round(t_hbm, 2),
            "bound": "hbm" if t_hbm > t_mxu else "mxu",
        }

    # --- per-chip batch sweep on the real chip -----------------------------
    # 3 points (was 5): each extra point costs a ~50 s AOT compile, and
    # round 5 rebalanced that time into the scaling sweep so the
    # reference-v1.2 extras (compressed/double-buffered) fit the budget;
    # the 5-point plateau curve is recorded in docs/PERF.md (round 2-4).
    batch_sweep = {}
    if on_tpu:
        for b in (64, 128, 256):
            if b == per_chip_batch:
                batch_sweep[str(b)] = {"ips": round(ips_per_chip, 2),
                                       "mfu": mfu_of(ips_per_chip)}
                continue
            try:
                s2, v2, o2, ba2, nc2, gb2 = build_step(
                    "resnet50", image_size, b, args.allreduce_grad_dtype)
                sweep_steps = max(10, 30 * 128 // b)  # ≥1.5s per timing loop
                d2, _ = measure(s2, v2, o2, ba2, steps=sweep_steps)
                ips_b = sweep_steps * gb2 / d2 / nc2
                batch_sweep[str(b)] = {"ips": round(ips_b, 2),
                                       "mfu": mfu_of(ips_b)}
            except Exception as e:
                FAILED.append(f"batch {b}")
                print(f"bench: batch {b} failed: {e!r}", file=sys.stderr)
                batch_sweep[str(b)] = None

    # --- headline selection: never report a physically impossible number ---
    # The fallback can only clear the TIMING suspicion, and only when the
    # FLOP count itself is trustworthy — sweep-batch MFUs derive from the
    # same flops_per_image, so an elided count would certify nonsense.
    headline_batch = per_chip_batch
    headline_ips = ips_per_chip
    if mfu_suspect and not flops_suspect:
        credible = {b: e for b, e in batch_sweep.items()
                    if e and e["mfu"] is not None and e["mfu"] <= 1.0}
        if credible:
            headline_batch = max(credible, key=lambda b: credible[b]["ips"])
            headline_ips = credible[headline_batch]["ips"]
            mfu_suspect = False
            print(f"bench: main config (batch {per_chip_batch}) was "
                  f"impossible; headline falls back to credible batch "
                  f"{headline_batch} @ {headline_ips} img/s/chip",
                  file=sys.stderr)
    suspect = flops_suspect or mfu_suspect

    # --- projected pod-scale DP efficiency (measured step + spec ICI) ------
    # Cheap (pure arithmetic from already-measured quantities) so it goes
    # into the FIRST emitted line rather than risking loss at the tail.
    projected = None
    if on_tpu:
        step_ms = dt / steps * 1e3
        projected = {
            "fp32_wire": project_dp_scaling(step_ms, grad_bytes,
                                            dev.device_kind, 4),
            "bf16_wire": project_dp_scaling(step_ms, grad_bytes,
                                            dev.device_kind, 2),
        }

    result = {
        "metric": "resnet50_images_per_sec_per_chip",
        "value": round(headline_ips, 2),
        "unit": "images/sec/chip",
        "vs_baseline": round(headline_ips / REFERENCE_IMAGES_PER_SEC_PER_CHIP, 3),
        "mfu": mfu_of(headline_ips),
        "mfu_useful": mfu_useful_of(headline_ips),
        "roofline": roofline,
        "suspect": suspect,
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "n_devices": len(jax.devices()),
        "failed": FAILED,
        "headline_batch": int(headline_batch),
        "flops_per_image": round(flops_per_image, 1) if flops_per_image else None,
        "flops_source": flops_source if flops_per_image else None,
        "allreduce_grad_dtype": args.allreduce_grad_dtype,
        "comm": comm_model,
        "batch_sweep": batch_sweep,
        "nf_resnet50": None,
        "transformer_lm": None,
        "transformer_lm_large": None,
        "decode": None,
        "serving": None,
        "serving_router": None,
        "serving_disagg": None,
        "serving_chaos": None,
        "serving_autoscale": None,
        "serving_kv_economy": None,
        "serving_scenarios": None,
        "collective_schedules": None,
        "schedule_truth": None,
        "train_chaos": None,
        "data_path": None,
        "long_context": None,
        "projected_scaling": projected,
        "quantized_allreduce": None,
        "scaling": None,
        "sections_complete": ["headline"],
        "wall_clock_s": None,
    }

    def compact_line():
        """One ≤1200-byte summary with the same driver schema (metric/
        value/unit/vs_baseline) plus the key per-section scalars.

        The enriched line
        grew to ~8 KB while the driver keeps only a 2000-char stdout TAIL,
        so rc=0 runs still parsed to null for two rounds running.  This
        line is printed AFTER every enriched emit, so the last complete
        JSON line in any tail window is always this one.
        """
        g = lambda d, *ks: (  # noqa: E731 — safe nested dict walk
            g(d[ks[0]], *ks[1:]) if ks and isinstance(d, dict)
            and d.get(ks[0]) is not None else (d if not ks else None))
        c = {
            "metric": result["metric"],
            "value": result["value"],
            "unit": result["unit"],
            "vs_baseline": result["vs_baseline"],
            "mfu": result["mfu"],
            "mfu_useful": result["mfu_useful"],
            "suspect": result["suspect"],
            "platform": result["platform"],
            "device_kind": result["device_kind"],
            "n_devices": result["n_devices"],
            "failed": result["failed"],
            "compact": True,
            "nf_resnet_ips": g(result, "nf_resnet50", "img_per_sec_per_chip"),
            "nf_resnet_mfu_useful": g(result, "nf_resnet50", "mfu_useful"),
            "lm_mfu": g(result, "transformer_lm", "mfu_useful"),
            "lm_large_mfu": g(result, "transformer_lm_large", "mfu_useful"),
            "decode_greedy_ms_tok": g(result, "decode",
                                      "greedy_ms_per_token"),
            "decode_beam4_ms_tok": g(result, "decode", "beam4_ms_per_token"),
            "serving_tps_high": g(result, "serving", "load_high",
                                  "tokens_per_sec"),
            "serving_ttft_p99_ms": g(result, "serving", "load_low",
                                     "ttft_p99_ms"),
            "router_tps_r4": g(result, "serving_router", "replicas_4",
                               "tokens_per_sec"),
            "router_shed_r2": g(result, "serving_router", "replicas_2",
                                "shed_rate"),
            "disagg_gap_p99_fused": g(result, "serving_disagg", "fused",
                                      "tick_gap_p99_ms"),
            "disagg_gap_p99_1_1": g(result, "serving_disagg",
                                    "disagg_1_1", "tick_gap_p99_ms"),
            "chaos_detection_ms": g(result, "serving_chaos",
                                    "detection_ms"),
            "chaos_drain_recovery": g(result, "serving_chaos",
                                      "drain_recovery_frac"),
            "chaos_conformance_violations": g(result, "serving_chaos",
                                              "conformance_violations"),
            "serving_journal_overhead": g(result, "serving", "journal",
                                          "journal_overhead_frac"),
            "autoscale_flap": g(result, "serving_autoscale", "flap"),
            "autoscale_gold_ttft_p99": g(result, "serving_autoscale",
                                         "gold_ttft_p99_ms"),
            "kv_economy_prefills_per_prefix": g(
                result, "serving_kv_economy",
                "prefill_calls_per_unique_prefix"),
            "scenario_adversarial_gold_degraded": g(
                result, "serving_scenarios", "adversarial",
                "tenant_gold_degraded"),
            "scenario_upgrade_drain_shed": g(
                result, "serving_scenarios", "rolling_upgrade",
                "drain_shed"),
            "schedules_hier_speedup": g(result, "collective_schedules",
                                        "hier_speedup"),
            "truth_rel_err_calibrated": g(result, "schedule_truth",
                                          "median_rel_err_calibrated"),
            "truth_overlap_frac": g(result, "schedule_truth",
                                    "overlap_frac"),
            "train_chaos_detection_ms": g(result, "train_chaos",
                                          "detection_ms"),
            "train_chaos_reconfig_ms": g(result, "train_chaos",
                                         "reconfig_wall_ms"),
            "flash_s8192_mfu": g(result, "long_context",
                                 "flash_fwd_bwd_S8192", "attn_mfu"),
            "flash_s16384_mfu": g(result, "long_context",
                                  "flash_fwd_bwd_S16384", "attn_mfu"),
            "data_assembly_ips": g(result, "data_path",
                                   "assembly_ips_nocopy"),
            "scaling_eff8_pct": g(result, "scaling", "efficiency_pct"),
            "compressed_bf16_n8_eff": g(result, "scaling",
                                        "compressed_bf16_n8", "eff_pct"),
            "double_buffered_n8_eff": g(result, "scaling",
                                        "double_buffered_n8", "eff_pct"),
            "quantized_eff8": g(result, "quantized_allreduce",
                                "quantized_eff8"),
            "quantized_db_eff8": g(result, "quantized_allreduce",
                                   "quantized_db_eff8"),
            "ef_loss_gap": g(result, "quantized_allreduce", "ef_loss_gap"),
            "sections_complete": result["sections_complete"],
            "wall_clock_s": result["wall_clock_s"],
        }
        line = json.dumps(c)
        if len(line) > 1200:  # never let the compact line outgrow the tail
            for k in ("sections_complete", "failed", "data_assembly_ips",
                      "flash_s16384_mfu",
                      "kv_economy_prefills_per_prefix"):
                c.pop(k, None)
            line = json.dumps(c)
        return line

    def emit(section=None):
        """Re-print the FULL result line, then the COMPACT summary line;
        ``section`` is recorded in ``sections_complete`` only when it
        actually SUCCEEDED (callers pass it after the result field is
        assigned; failed sections re-emit with no section so a null field
        is never advertised as complete)."""
        if section and section not in result["sections_complete"]:
            result["sections_complete"].append(section)
        result["suspect"] = suspect
        result["wall_clock_s"] = round(time.time() - t_start, 1)
        print(json.dumps(result), flush=True)
        print(compact_line(), flush=True)
        if args.json_out:
            # atomic re-write per section: a killed run leaves the last
            # COMPLETE result file, never a torn one
            tmp = f"{args.json_out}.tmp.{os.getpid()}"
            with open(tmp, "w") as f:
                json.dump(result, f, indent=1)
            os.replace(tmp, args.json_out)
        if obs is not None:
            if section:
                obs.instant(f"section/{section}", cat="bench")
            obs.export_chrome_trace(args.trace_out)

    emit("headline")

    # --- nf_resnet50: the measured BN-free variant (docs/PERF.md round 4) --
    # BatchNorm's activation passes cost 8.4 GB of the 44 GB step; the
    # probe (scripts/probe_bn_traffic.py) shows the zero-norm fusion floor
    # is +19-20%, and NF-ResNet (scaled weight standardization + SkipInit)
    # reaches it with published ImageNet convergence parity — convergence
    # re-demonstrated on-chip in docs/evidence_norm_convergence.json.
    if on_tpu and not over_budget():
        try:
            s3, v3, o3, b3, nc3, gb3 = build_step(
                "nf_resnet50", image_size, per_chip_batch,
                args.allreduce_grad_dtype)
            s3c, fl3, by3 = compile_with_flops(s3, v3, o3, b3)
            d3, _ = measure(s3c, v3, o3, b3, steps=steps)
            ips3 = steps * gb3 / d3 / nc3
            result["nf_resnet50"] = {
                "img_per_sec_per_chip": round(ips3, 2),
                "vs_bn_pct": round(100.0 * ips3 / ips_per_chip, 1),
                "mfu_useful": mfu_useful_of(ips3),
                "gbytes_per_step": round(by3 / 1e9, 2) if by3 else None,
                "note": "normalizer-free ResNet-50 (--arch nf_resnet50): "
                        "activations at the zero-norm HBM floor",
            }
            emit("nf_resnet50")
        except Exception as e:
            FAILED.append(f"nf_resnet50 section")
            print(f"bench: nf_resnet50 section failed: {e!r}",
                  file=sys.stderr)
            emit()

    # --- transformer LM: the FLOPs-dense half of the perf story ------------
    if on_tpu:
        try:
            result["transformer_lm"] = t = bench_transformer_lm()
            # The headline suspect flag covers EVERY reported number: a
            # physically impossible transformer MFU must not hide behind a
            # credible ResNet one.
            suspect = suspect or bool(t.get("suspect"))
            emit("transformer_lm")
        except Exception as e:
            FAILED.append(f"transformer section")
            print(f"bench: transformer section failed: {e!r}", file=sys.stderr)
            emit()
        try:
            # 875M params: the matmul-dominated ceiling (0.72 compiled /
            # 0.77 useful MFU measured on v5e — docs/PERF.md)
            result["transformer_lm_large"] = t = bench_transformer_lm(
                per_chip_batch=4, d_model=2048, n_layers=16, n_heads=16)
            suspect = suspect or bool(t.get("suspect"))
            emit("transformer_lm_large")
        except Exception as e:
            FAILED.append(f"large-transformer section")
            print(f"bench: large-transformer section failed: {e!r}",
                  file=sys.stderr)
            emit()

    # --- decode: generation perf over the KV cache -------------------------
    if on_tpu and not over_budget():
        try:
            result["decode"] = bench_decode()
            emit("decode")
        except Exception as e:
            FAILED.append(f"decode section")
            print(f"bench: decode section failed: {e!r}", file=sys.stderr)
            emit()
    elif on_tpu:
        print("bench: over budget — decode section skipped", file=sys.stderr)

    # --- serving: continuous-batching engine offered-load sweep ------------
    # Runs on every backend (the engine is the same host loop + compiled
    # tick everywhere; on CPU this is the serving trajectory's anchor).
    if not over_budget():
        try:
            result["serving"] = bench_serving()
            emit("serving")
        except Exception as e:
            FAILED.append(f"serving section")
            print(f"bench: serving section failed: {e!r}", file=sys.stderr)
            emit()
    else:
        print("bench: over budget — serving section skipped",
              file=sys.stderr)

    # --- serving fleet: router + prefix cache offered-load sweep -----------
    # (ISSUE 7) Same every-backend contract as the serving section; the
    # 1/2/4-replica sweep is the fleet trajectory's anchor and its
    # ttft/shed keys gate direction-aware in bench_history.jsonl.
    if not over_budget():
        try:
            result["serving_router"] = bench_serving_router()
            emit("serving_router")
        except Exception as e:
            FAILED.append(f"serving_router section")
            print(f"bench: serving_router section failed: {e!r}",
                  file=sys.stderr)
            emit()
    else:
        print("bench: over budget — serving_router section skipped",
              file=sys.stderr)

    # --- serving disagg: fused vs P:D role-split at fixed offered load -----
    # (ISSUE 9) Every-backend contract; the decode tick-gap p50/p99/
    # variance + transfer-ms keys gate direction-aware in
    # bench_history.jsonl — the acceptance metric is the disagg points'
    # tick_gap_p99_over_p50 sitting strictly below fused.
    if not over_budget():
        try:
            result["serving_disagg"] = bench_serving_disagg()
            emit("serving_disagg")
        except Exception as e:
            FAILED.append(f"serving_disagg section")
            print(f"bench: serving_disagg section failed: {e!r}",
                  file=sys.stderr)
            emit()
    else:
        print("bench: over budget — serving_disagg section skipped",
              file=sys.stderr)

    # --- serving chaos: worker death + rolling drain cost (ISSUE 10) -------
    # Every-backend contract; detection/failover/shed/recovery keys gate
    # lower-is-better (drain_recovery_frac higher) in bench_history.jsonl
    # — the acceptance bound is drain_recovery_frac >= 0.9.
    if not over_budget():
        try:
            result["serving_chaos"] = bench_serving_chaos()
            emit("serving_chaos")
        except Exception as e:
            FAILED.append(f"serving_chaos section")
            print(f"bench: serving_chaos section failed: {e!r}",
                  file=sys.stderr)
            emit()
    else:
        print("bench: over budget — serving_chaos section skipped",
              file=sys.stderr)

    # --- serving autoscale: diurnal curve + burst, two tenants (ISSUE 11) --
    # Every-backend contract; flap/shed/ttft/rung/degraded keys gate
    # lower-is-better in bench_history.jsonl — the acceptance bounds are
    # flap == 0 (no up-then-down inside one cooldown window) and
    # drain_shed == 0 (every scale-down is a drain).
    if not over_budget():
        try:
            result["serving_autoscale"] = bench_serving_autoscale()
            emit("serving_autoscale")
        except Exception as e:
            FAILED.append(f"serving_autoscale section")
            print(f"bench: serving_autoscale section failed: {e!r}",
                  file=sys.stderr)
            emit()
    else:
        print("bench: over budget — serving_autoscale section skipped",
              file=sys.stderr)

    # --- serving KV economy: global index + pulls + spill tier (ISSUE 12) --
    # Every-backend contract; prefill_calls/stale/spill/crc/*_ms keys gate
    # lower-is-better in bench_history.jsonl — the acceptance bound is
    # prefill_calls_per_unique_prefix ~= 1 (remote hits served by pull,
    # not re-prefill).
    if not over_budget():
        try:
            result["serving_kv_economy"] = bench_serving_kv_economy()
            emit("serving_kv_economy")
        except Exception as e:
            FAILED.append(f"serving_kv_economy section")
            print(f"bench: serving_kv_economy section failed: {e!r}",
                  file=sys.stderr)
            emit()
    else:
        print("bench: over budget — serving_kv_economy section skipped",
              file=sys.stderr)

    # --- scenario plane: seeded workloads + rolling upgrade (ISSUE 18) -----
    # Every-backend contract; shed_rate/slo_burn/max_rung/flap/drain_shed/
    # *_degraded/*_violations keys gate lower-is-better in
    # bench_history.jsonl — the acceptance bounds are
    # rolling_upgrade/drain_shed == 0, adversarial/tenant_gold_degraded
    # == 0, repro_violations == 0, conformance_violations == 0.
    if not over_budget():
        try:
            result["serving_scenarios"] = bench_serving_scenarios()
            emit("serving_scenarios")
        except Exception as e:
            FAILED.append(f"serving_scenarios section")
            print(f"bench: serving_scenarios section failed: {e!r}",
                  file=sys.stderr)
            emit()
    else:
        print("bench: over budget — serving_scenarios section skipped",
              file=sys.stderr)

    # --- collective schedules: compiled, verified comm programs (ISSUE 19) -
    # Host-only (stdlib + numpy); every-backend contract.  hier_speedup/
    # speedup_vs_single/verified_pairs/faults_caught gate higher-is-better,
    # *_cost_ms/*_bytes/*_violations lower-is-better — the acceptance
    # bounds are hier_speedup > 1.0 on the ICI+DCN fan-out pair and both
    # violation counters == 0.
    if not over_budget():
        try:
            result["collective_schedules"] = bench_collective_schedules()
            emit("collective_schedules")
        except Exception as e:
            FAILED.append(f"collective_schedules section")
            print(f"bench: collective_schedules section failed: {e!r}",
                  file=sys.stderr)
            emit()
    else:
        print("bench: over budget — collective_schedules section skipped",
              file=sys.stderr)

    # --- schedule truth plane: measured vs predicted (ISSUE 20) ------------
    # Every-backend contract (pure host execution under the
    # ScheduleExecProfile).  Gated keys: median_rel_err_stock /
    # median_rel_err_calibrated / wire_exposed_frac /
    # profiler_overhead_frac / reconcile_violations all lower-is-better
    # (wire_exposed_frac is the documented gateable face of the overlap
    # fraction: overlap_frac = 1 - exposed, so it gates
    # higher-is-better by construction); acceptance bounds are
    # reconcile_violations == 0 (measured bytes == IR-declared bytes
    # per link, exact), median_rel_err_calibrated <=
    # median_rel_err_stock, and profiler_overhead_frac < 0.03.
    if not over_budget():
        try:
            result["schedule_truth"] = bench_schedule_truth()
            emit("schedule_truth")
        except Exception as e:
            FAILED.append(f"schedule_truth section")
            print(f"bench: schedule_truth section failed: {e!r}",
                  file=sys.stderr)
            emit()
    else:
        print("bench: over budget — schedule_truth section skipped",
              file=sys.stderr)

    # --- train chaos: rank death -> live shrink cost (ISSUE 13) ------------
    # Every-backend contract (pure host machinery); detection/consensus/
    # reconfig/reshard/steps_lost keys gate lower-is-better in
    # bench_history.jsonl — the acceptance bound is
    # steps_lost_live_shrink == 0 (checkpoint-free resume from the
    # failed step) with detection_ms tracking detection_window_ms.
    if not over_budget():
        try:
            result["train_chaos"] = bench_train_chaos()
            emit("train_chaos")
        except Exception as e:
            FAILED.append(f"train_chaos section")
            print(f"bench: train_chaos section failed: {e!r}",
                  file=sys.stderr)
            emit()
    else:
        print("bench: over budget — train_chaos section skipped",
              file=sys.stderr)

    # --- elastic resume: checkpoint/reshard/preemption cost (ISSUE 8) ------
    # Every-backend contract (host-side machinery + the CPU demo step):
    # save/restore latency, n=4->n=2 reshard wall time, steps-to-recover,
    # and the prefetch on/off delta gate in bench_history.jsonl.
    if not over_budget():
        try:
            result["elastic_resume"] = bench_elastic_resume()
            emit("elastic_resume")
        except Exception as e:
            FAILED.append(f"elastic_resume section")
            print(f"bench: elastic_resume section failed: {e!r}",
                  file=sys.stderr)
            emit()
    else:
        print("bench: over budget — elastic_resume section skipped",
              file=sys.stderr)

    # --- input pipeline: disk-fed vs synthetic -----------------------------
    if on_tpu and not over_budget():
        try:
            result["data_path"] = bench_data_path(
                demand_ips=(result.get("nf_resnet50") or {}).get(
                    "img_per_sec_per_chip"))
            emit("data_path")
        except Exception as e:
            FAILED.append(f"data-path section")
            print(f"bench: data-path section failed: {e!r}", file=sys.stderr)
            emit()
    elif on_tpu:
        print("bench: over budget — data-path section skipped",
              file=sys.stderr)

    # --- long context: flash kernels at 8k/16k + LM step at 4096 -----------
    if on_tpu and not over_budget():
        try:
            result["long_context"] = bench_long_context()
            emit("long_context")
        except Exception as e:
            FAILED.append(f"long-context section")
            print(f"bench: long-context section failed: {e!r}",
                  file=sys.stderr)
            emit()
    elif on_tpu:
        print("bench: over budget — long-context section skipped",
              file=sys.stderr)

    # --- quantized allreduce: the ISSUE 14 matrix (every backend) ----------
    # int8 block-scaled ring + EF + double-buffer combinations at
    # n=1/2/4/8 with the accuracy-vs-wire-bytes table; quantized_eff8 /
    # quantized_db_eff8 gate higher-is-better, quant_wire_bytes /
    # ef_loss_gap lower, in bench_history.jsonl.
    if not args.skip_scaling and not over_budget():
        try:
            budget_left = lambda: budget_s - (time.time() - t_start)  # noqa: E731
            result["quantized_allreduce"] = run_quantized_sweep(
                over_budget=over_budget, budget_left=budget_left)
            emit("quantized_allreduce")
        except Exception as e:
            FAILED.append(f"quantized_allreduce section")
            print(f"bench: quantized_allreduce section failed: {e!r}",
                  file=sys.stderr)
            emit()
    elif not args.skip_scaling:
        print("bench: over budget — quantized_allreduce section skipped",
              file=sys.stderr)

    # --- DP weak-scaling sweep (virtual CPU mesh, fresh subprocesses) ------
    if not args.skip_scaling and not over_budget():
        ns = (1, 2, 4, 8, 16, 32) if args.full_sweep else (1, 8, 4)
        budget_left = lambda: budget_s - (time.time() - t_start)  # noqa: E731
        result["scaling"] = run_scaling_sweep(
            ns, over_budget=over_budget, budget_left=budget_left)
        emit("scaling")
    elif not args.skip_scaling:
        print("bench: over budget — scaling sweep skipped", file=sys.stderr)

    emit("final")

    # --- bench trajectory: one {n, cmd, rc, t, parsed} record per run -----
    # Self-written, so every local/CI bench run extends the trajectory and
    # `check_perf_regression.py --history` can gate round N against round
    # N-1 (docs/PERF.md "trajectory loop").  The recorded rc is the run's
    # own: a run with failed sections is not a clean round.
    if args.history_out:
        try:
            append_history(args.history_out, result)
        except Exception as e:
            FAILED.append(f"history append")
            print(f"bench: history append failed: {e!r}", file=sys.stderr)
    if statusz is not None:
        statusz.stop()
    if FAILED:
        print(f"bench: FAILED (exit 1): {FAILED}", file=sys.stderr)
    return 1 if FAILED else 0


def append_history(path, result, cmd=None):
    """Append one ``{n, cmd, rc, t, parsed}`` record to the JSONL
    trajectory at ``path``; ``n``
    continues from the highest round already in the file.  Returns the
    record."""
    n = 0
    if os.path.exists(path):
        with open(path) as f:
            for line in f:
                if not line.strip():
                    continue
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue  # torn tail from a killed run
                if isinstance(rec, dict) and isinstance(rec.get("n"), int):
                    n = max(n, rec["n"])
    record = {
        "n": n + 1,
        "cmd": cmd or " ".join(sys.argv),
        "rc": 1 if FAILED else 0,
        "t": round(time.time(), 3),
        "parsed": result,
    }
    with open(path, "a") as f:
        f.write(json.dumps(record) + "\n")
        f.flush()
    print(f"bench: trajectory round {record['n']} appended to {path}",
          file=sys.stderr)
    return record


if __name__ == "__main__":
    sys.exit(main())
