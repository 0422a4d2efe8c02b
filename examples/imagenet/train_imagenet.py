#!/usr/bin/env python
"""Data-parallel ImageNet ResNet training — BASELINE configs #2/#4.

Reference parity: ``examples/imagenet/train_imagenet.py`` [uv]
(SURVEY.md §2.9): the headline DP throughput workload.  The reference ran
one MPI process per GPU with a MultiprocessIterator + pure_nccl bucketed
allreduce; here the whole slice is driven by one jitted SPMD step (bf16
MXU compute, gradient mean over ICI fused into the step) and the input
pipeline is a host-side prefetch thread.

Without /imagenet on disk, synthetic data runs the identical compute graph
(what throughput benchmarks measure anyway).
Run:  python examples/imagenet/train_imagenet.py --arch resnet50 --steps 30
      python examples/imagenet/train_imagenet.py --devices 8 --image-size 32
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))


def main(argv=None):
    """Train; returns a small result dict (losses, compile and run
    seconds, throughput) so a caller such as ``chip_smoke.py`` can drive
    this entry point in-process."""
    parser = argparse.ArgumentParser(description="ChainerMN-TPU example: ImageNet")
    # Kept as a literal (not ARCHS.keys()): the registry import pulls in
    # jax, which must wait until --devices is applied.  A consistency
    # assert below catches drift.
    parser.add_argument("--arch", default="resnet50",
                        choices=["resnet18", "resnet34", "resnet50",
                                 "resnet101", "resnet152",
                                 "nf_resnet50", "nf_resnet101",
                                 "nf_resnet152",
                                 "alex", "googlenet", "vgg16",
                                 "vit_ti16", "vit_s16", "vit_b16"])
    parser.add_argument("--devices", type=int, default=0,
                        help="fake an N-device CPU mesh (0 = real chips)")
    parser.add_argument("--batchsize", type=int, default=64, help="per-chip batch")
    parser.add_argument("--dataset-size", type=int, default=512,
                        help="synthetic records held in the prefetch buffer")
    parser.add_argument("--data-dir", default=None,
                        help="train from an on-disk record dataset "
                             "(write_file_dataset layout); materialized "
                             "with synthetic records if absent")
    parser.add_argument("--steps", type=int, default=30)
    parser.add_argument("--image-size", type=int, default=224)
    parser.add_argument("--num-classes", type=int, default=1000)
    parser.add_argument("--lr", type=float, default=0.1)
    parser.add_argument("--momentum", type=float, default=0.9)
    parser.add_argument("--weight-decay", type=float, default=1e-4)
    parser.add_argument("--double-buffering", action="store_true")
    parser.add_argument("--optimizer", default="sgd",
                        choices=["sgd", "lars", "lamb"],
                        help="lars/lamb are the large-batch scaling "
                             "optimizers (layerwise adaptive LR) for pushing "
                             "global batch past ~8k images")
    parser.add_argument("--warmup-steps", type=int, default=0,
                        help="linear LR warmup (large-batch recipe)")
    parser.add_argument("--allreduce-grad-dtype", default=None,
                        choices=["bfloat16", "float16", "float32", "int8"],
                        help="wire dtype for the cross-chip gradient mean "
                             "(reference: pure_nccl allreduce_grad_dtype; "
                             "int8 = quantized ring, beyond-reference)")
    parser.add_argument("--conv-impl", default="xla",
                        choices=["xla", "pallas"],
                        help="3x3/1x1 conv backward impl. 'pallas' is the "
                             "measured-SLOWER opt-in kernel path kept for "
                             "the record (docs/PERF.md 'Conv backward: why "
                             "the Pallas kernels lost'); default XLA runs "
                             "at the HBM floor")
    parser.add_argument("--norm", default="bn",
                        choices=["bn", "stalebn", "affine"],
                        help="ResNet norm layer. For the MEASURED BN-free "
                             "fast path use --arch nf_resnet50 instead "
                             "(+20%% step throughput on v5e, docs/PERF.md); "
                             "'stalebn'/'affine' are perf-probe knobs — "
                             "stalebn DIVERGES in training "
                             "(docs/evidence_stalebn_divergence.json)")
    parser.add_argument("--agc", type=float, default=0.0,
                        help="adaptive gradient clipping threshold (0 = "
                             "off). The NF-ResNet large-batch ingredient "
                             "(use ~0.01 from global batch ~4096, Brock "
                             "et al. 2021); composes optax.adaptive_grad_"
                             "clip ahead of the optimizer")
    parser.add_argument("--communicator", default="xla")
    parser.add_argument("--fsdp", action="store_true",
                        help="ZeRO-3: params, grads and optimizer state all "
                             "sharded 1/P (BatchNorm-free archs only — use "
                             "a ViT, e.g. --arch vit_s16)")
    args = parser.parse_args(argv)

    # Flag-combination checks that need nothing from jax: fail fast,
    # before device config / distributed init.
    arch_kw = {"norm": args.norm} if args.norm != "bn" else {}
    if arch_kw and not args.arch.startswith("resnet"):
        parser.error("--norm applies to the resnet archs only")
    if args.conv_impl != "xla":
        if "resnet" not in args.arch:
            parser.error("--conv-impl applies to the (nf_)resnet archs only")
        arch_kw["conv_impl"] = args.conv_impl
    if args.agc < 0:
        # optax.adaptive_grad_clip(-x) silently negates every update
        # (gradient ascent) — reject rather than diverge.
        parser.error("--agc must be >= 0")

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

    import chainermn_tpu as mn
    from chainermn_tpu.models.mlp import cross_entropy_loss
    from chainermn_tpu.topology import enable_compile_cache

    enable_compile_cache()
    from chainermn_tpu.models.resnet import ARCHS

    # Drift guard over the FULL choices list (not just the picked arch),
    # with a real raise — an assert is stripped under python -O.
    missing = [c for c in parser._option_string_actions["--arch"].choices
               if c not in ARCHS]
    if missing:
        parser.error(f"--arch choices drifted from the model registry: "
                     f"{missing} not in {sorted(ARCHS)}")
    mn.init_distributed()
    comm = mn.create_communicator(args.communicator)
    mesh = getattr(comm, "mesh", None) or mn.make_mesh()
    n_chips = comm.size
    global_batch = args.batchsize * n_chips
    if comm.rank == 0:
        print(f"{args.arch}  chips={n_chips}  global_batch={global_batch}  "
              f"image={args.image_size}")

    model = ARCHS[args.arch](num_classes=args.num_classes,
                             stem_strides=2 if args.image_size >= 64 else 1,
                             **arch_kw)
    rng = jax.random.PRNGKey(0)
    variables = dict(model.init(
        rng, jnp.zeros((1, args.image_size, args.image_size, 3)), train=False))
    # step contract is {'params', 'batch_stats'}; norm='affine' models
    # (and the ViTs) init without the stats collection
    variables.setdefault("batch_stats", {})

    lr = args.lr
    if args.warmup_steps:
        lr = optax.linear_schedule(0.0, args.lr, args.warmup_steps)
    if args.optimizer == "lars":
        inner = optax.lars(lr, weight_decay=args.weight_decay,
                           momentum=args.momentum)
    elif args.optimizer == "lamb":
        inner = optax.lamb(lr, weight_decay=args.weight_decay)
    else:
        inner = optax.chain(
            optax.add_decayed_weights(args.weight_decay),
            optax.sgd(lr, momentum=args.momentum),
        )
    if args.agc:
        # NF-ResNet's large-batch ingredient (Brock et al.: needed from
        # batch ~4096): per-unit ratio clip BEFORE the optimizer, after
        # the gradient mean (create_multi_node_optimizer wraps the whole
        # chain, so the clip sees synchronized gradients).
        inner = optax.chain(optax.adaptive_grad_clip(args.agc), inner)
    if not args.fsdp:
        optimizer = mn.create_multi_node_optimizer(
            inner,
            comm, double_buffering=args.double_buffering,
            allreduce_grad_dtype=args.allreduce_grad_dtype)
    elif args.allreduce_grad_dtype or args.double_buffering:
        # These knobs live in the replicated-DP wrapper; silently dropping
        # them would mislabel a benchmark run.
        raise SystemExit(
            "--fsdp handles gradient reduction itself (GSPMD "
            "reduce-scatter); --allreduce-grad-dtype/--double-buffering "
            "do not apply")

    def loss_and_metrics(logits, batch):
        _, labels = batch
        loss = cross_entropy_loss(logits, labels)
        acc = (logits.argmax(-1) == labels).mean()
        return loss, {"accuracy": acc}

    def normalize_on_chip(batch):
        # uint8 corpora (scripts/ingest_images.py preserves uint8: 4x
        # fewer host->device bytes) cast+normalize ON CHIP, fused into
        # the first conv's prologue; float corpora pass through.  The
        # dtype is static at trace time, so this is a free trace-time
        # branch (docs/PERF.md round-5 data path).
        images, labels = batch
        if images.dtype == jnp.uint8:
            images = images.astype(jnp.float32) / 255.0 - 0.5
        return images, labels

    if args.fsdp:
        # ZeRO-3 path: GSPMD inserts per-use weight all-gathers and
        # gradient reduce-scatters from the 1/P shardings alone.  BN's
        # mutable running stats don't fit the pure-loss contract — the ViT
        # archs (stat-free) are the fit.
        from chainermn_tpu.parallel import (init_fsdp_params,
                                            init_fsdp_state,
                                            make_fsdp_train_step)

        if "batch_stats" in variables:
            raise SystemExit(
                f"--fsdp needs a BatchNorm-free arch (got {args.arch}); "
                f"try --arch vit_s16")

        def fsdp_loss(p, batch):
            batch = normalize_on_chip(batch)
            logits = model.apply({"params": p}, batch[0], train=True)
            loss, metrics = loss_and_metrics(logits, batch)
            return loss, metrics

        fsdp_params = init_fsdp_params(dict(variables)["params"], mesh)
        opt_state = init_fsdp_state(inner, fsdp_params, mesh)
        raw = make_fsdp_train_step(fsdp_loss, inner, mesh, has_aux=True)

        def step(v, st, batch):
            p, st, loss, metrics = raw(v["params"], st, batch)
            return {"params": p}, st, loss, metrics

        variables = {"params": fsdp_params}
    else:
        step = mn.make_flax_train_step(
            model, loss_and_metrics, optimizer, mesh=mesh,
            allreduce_grad_dtype=args.allreduce_grad_dtype,
            preprocess=normalize_on_chip)
        variables = mn.replicate(dict(variables), mesh)
        opt_state = mn.replicate(optimizer.init(variables["params"]), mesh)

    # Input pipeline: the native C++ prefetcher assembles batches in worker
    # threads (GIL-free) while the previous step computes — the reference's
    # MultiprocessIterator role (SURVEY.md §2.9).  With --data-dir the
    # records come OFF DISK (pread-ing C++ workers; the reference example's
    # defining job); otherwise synthetic in-memory records run the identical
    # path.  An empty/missing --data-dir is materialized first, standing in
    # for an ImageNet conversion step when /imagenet is absent.
    data_rng = np.random.RandomState(0)
    n_records = max(args.dataset_size, global_batch)
    if args.data_dir:
        meta = os.path.join(args.data_dir, "meta.json")
        # Rank 0 alone decides whether to materialize (a per-rank exists()
        # check would race with the write and leave ranks disagreeing on
        # whether to enter the barrier); the bcast is UNCONDITIONAL so it
        # is the same collective on every process.
        if comm.owns_rank(0) and not os.path.exists(meta):
            records = data_rng.randn(
                n_records, args.image_size, args.image_size, 3
            ).astype(np.float32)
            labels = data_rng.randint(
                0, args.num_classes, n_records).astype(np.int32)
            mn.write_file_dataset(args.data_dir, [records, labels])
            print(f"materialized {n_records} records to {args.data_dir}")
        comm.bcast_obj(None)  # barrier: dataset visible before readers
        dataset = mn.FileDataset(args.data_dir)
    else:
        records = data_rng.randn(n_records, args.image_size, args.image_size,
                                 3).astype(np.float32)
        labels = data_rng.randint(0, args.num_classes, n_records
                                  ).astype(np.int32)
        dataset = (records, labels)
    # copy=True: device_put is async on real chips, and without the copy the
    # prefetch ring could recycle the slot under a still-running H2D DMA.
    it = mn.PrefetchIterator(dataset, batch_size=global_batch,
                             shuffle=True, seed=1, copy=True)
    if comm.rank == 0 and not mn.runtime.native_available():
        print("note: native prefetcher unavailable, python fallback in use")

    # compile ahead of time (the jitted DP step; the FSDP face is a plain
    # wrapper), so compile and run are timed apart and the SAME executable
    # runs every step; then one warm-up step
    batch = mn.shard_batch(it.next(), mesh)
    t_compile = time.time()
    has_kernel = None
    if hasattr(step, "lower"):
        step = step.lower(variables, opt_state, batch).compile()
        has_kernel = "tpu_custom_call" in step.as_text()
    compile_s = time.time() - t_compile
    variables, opt_state, loss, metrics = step(variables, opt_state, batch)
    first_loss = float(loss)
    t0 = time.time()
    for i in range(args.steps):
        batch = mn.shard_batch(it.next(), mesh)
        variables, opt_state, loss, metrics = step(variables, opt_state, batch)
        if args.devices:  # lockstep on thin hosts; async on real chips
            loss.block_until_ready()
    last_loss = float(loss)  # host readback = the timing barrier
    dt = time.time() - t0
    ips = args.steps * global_batch / dt
    dev = jax.devices()[0]
    if comm.rank == 0:
        print(f"loss {last_loss:.4f}  acc {float(metrics['accuracy']):.4f}")
        print(f"throughput on {n_chips} x {dev.platform} "
              f"({dev.device_kind}): {ips:.1f} images/sec total, "
              f"{ips / n_chips:.1f} images/sec/chip")
    return {"arch": args.arch, "chips": n_chips, "platform": dev.platform,
            "device_kind": dev.device_kind, "global_batch": global_batch,
            "steps": args.steps, "first_loss": first_loss,
            "last_loss": last_loss, "compile_s": round(compile_s, 2),
            "run_s": round(dt, 2),
            "images_per_sec_per_chip": round(ips / n_chips, 1),
            "tpu_custom_call": has_kernel}


if __name__ == "__main__":
    main()
