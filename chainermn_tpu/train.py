"""SPMD training-step builder — the hot path.

Reference parity: the reference's hot loop (SURVEY.md §3.2) is
``_MultiNodeOptimizer.update``: forward/backward → eager bucketed NCCL
allreduce → optimizer kernels, four separate device phases.  TPU-native the
whole thing is ONE compiled SPMD program: forward, backward, the ICI
gradient mean (inside the optax wrapper) and the param update fuse into a
single XLA executable with buffer donation — the compiler overlaps the
collective with compute, which is what `_memory_utility` bucketing and the
double-buffering CUDA streams were approximating by hand.
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ._compat import pcast_varying, shard_map
from .ops import collective as _col
from .optimizers import compressed_mean
from .topology import DEFAULT_AXIS_NAME, make_mesh


def _value_and_global_grads(local_loss, params, axis_name,
                            allreduce_grad_dtype, grad_reduce=None):
    """``((loss, aux), grads)`` with the cross-rank gradient mean done right.

    Default path: differentiate the GLOBAL mean loss (pmean over ranks of
    the local mean).  Under shard_map, autodiff w.r.t. replicated params
    inserts the cross-rank psum of cotangents itself — i.e. the gradient
    allreduce IS this pmean's backward pass, scheduled by XLA inside the
    step.  Taking grads of the local loss and averaging after would
    double-count (the AD-inserted psum already summed).

    Compressed path (``allreduce_grad_dtype`` set): differentiate the LOCAL
    loss w.r.t. a per-rank view of the params (pcast to varying OUTSIDE the
    differentiated function, so AD does not insert its own fp32 cotangent
    psum); the explicit :func:`compressed_mean` is then the one wire
    collective, in the reduced dtype.  ``local_loss(p)`` must return
    ``(loss, aux)``.

    ``grad_reduce`` replaces :func:`compressed_mean` entirely (same
    local-grad derivation): a ``grads -> grads`` callable owning the wire
    collective — e.g. ``ops.collective.hierarchical_pmean`` for the
    two-tier ICI×DCN mean over a multislice mesh.
    """
    if allreduce_grad_dtype is None and grad_reduce is None:
        def global_loss(p):
            loss, aux = local_loss(p)
            return _col.pmean(loss, axis_name), aux

        out = jax.value_and_grad(global_loss, has_aux=True)(params)
        # The gradient all-reduce on this path is AUTODIFF-INSERTED (the
        # psum of replicated-param cotangents behind the loss pmean), so
        # no wrapped collective sees it — book it explicitly at its known
        # size so the ledger reports the step's dominant wire traffic
        # instead of a 4-byte loss pmean (docs/OBSERVABILITY.md).
        from .observability.comm import note as _note
        _note("grad_allreduce_ad", axis_name, out[1])
        return out

    p_local = jax.tree_util.tree_map(
        lambda v: pcast_varying(v, axis_name), params)
    (loss, aux), grads = jax.value_and_grad(local_loss, has_aux=True)(p_local)
    if grad_reduce is not None:
        grads = grad_reduce(grads)
    else:
        grads = compressed_mean(grads, axis_name, allreduce_grad_dtype)
    return (_col.pmean(loss, axis_name), aux), grads


def _accumulated_local_grads(local_loss, params, batch, axis_name, steps):
    """Mean LOCAL loss/grads over ``steps`` microbatches via ``lax.scan``.

    Each microbatch's backward runs with only its own activations live
    (O(B/steps) instead of O(B)); gradients accumulate in fp32.  Returned
    grads are still per-rank local (varying) — the caller owns the one wire
    collective, exactly like the compressed path of
    :func:`_value_and_global_grads`.  ``local_loss(p, microbatch)`` must
    return ``(loss, aux)``; aux is averaged over microbatches.
    """
    import jax.numpy as jnp

    from .ops.collective import zeros_like_vma

    b_local = jax.tree_util.tree_leaves(batch)[0].shape[0]
    if b_local % steps:
        raise ValueError(
            f"per-rank batch {b_local} not divisible by "
            f"grad_accum_steps {steps}")
    micro = jax.tree_util.tree_map(
        lambda x: x.reshape((steps, x.shape[0] // steps) + x.shape[1:]), batch)
    p_local = jax.tree_util.tree_map(
        lambda v: pcast_varying(v, axis_name), params)
    any_leaf = jax.tree_util.tree_leaves(p_local)[0]

    def acc(carry, mb):
        g_acc, l_acc = carry
        (l, aux), g = jax.value_and_grad(local_loss, has_aux=True)(p_local, mb)
        g_acc = jax.tree_util.tree_map(
            lambda a, gg: a + gg.astype(jnp.float32), g_acc, g)
        return (g_acc, l_acc + l), aux

    g0 = jax.tree_util.tree_map(
        lambda v: zeros_like_vma(v, jnp.float32), p_local)
    l0 = zeros_like_vma(any_leaf, jnp.float32, ())
    (g_sum, l_sum), aux_stack = jax.lax.scan(acc, (g0, l0), micro)
    grads = jax.tree_util.tree_map(lambda g: g / steps, g_sum)
    aux = jax.tree_util.tree_map(
        lambda x: x.astype(jnp.float32).mean(0), aux_stack)
    return (l_sum / steps, aux), grads


def make_train_step(
    loss_fn: Callable,
    optimizer: optax.GradientTransformation,
    mesh: Optional[Mesh] = None,
    axis_name: str = DEFAULT_AXIS_NAME,
    has_aux: bool = False,
    donate: bool = True,
    allreduce_grad_dtype=None,
    grad_reduce: Optional[Callable] = None,
    grad_accum_steps: int = 1,
    error_feedback: bool = False,
):
    """Build ``step(params, opt_state, batch) -> (params, opt_state, loss[, aux])``.

    ``loss_fn(params, local_batch)`` returns the mean loss over the *local*
    batch (plus an aux pytree when ``has_aux``).  ``batch`` leaves carry the
    global batch on their leading axis, sharded across ``axis_name``;
    ``params``/``opt_state`` are replicated.  ``optimizer`` should come from
    :func:`chainermn_tpu.optimizers.create_multi_node_optimizer`, whose
    in-jit pmean makes per-shard gradients globally correct.

    ``allreduce_grad_dtype`` (e.g. ``'bfloat16'``) is the reference's
    compressed-allreduce knob (``pure_nccl_communicator.py ::
    allreduce_grad_dtype`` [uv]): the cross-rank gradient mean — the step's
    dominant communication — runs in that dtype on the wire, halving ICI/DCN
    gradient bytes for bf16, with params and the optimizer update staying at
    full precision.  ``'int8'`` runs the block-scaled quantized ring
    (~1 byte/element; see ``ops.collective.quantized_ring_pmean``).

    ``error_feedback=True`` (int8 wire + an optimizer built with the same
    flag): the optimizer transform owns the wire collective — local
    gradients flow to it uncorrected and its :class:`~chainermn_tpu
    .optimizers.ErrorFeedbackState` residual rows shard per rank, so the
    step binding derives per-leaf opt-state specs from the state's
    structure (``opt_state_partition_specs``) at first call.  One
    compiled program per opt-state STRUCTURE — value variants reuse it
    (the ``train.quantized_step`` analysis entry point pins this).

    ``grad_accum_steps > 1`` splits each rank's local batch into that many
    microbatches and accumulates their gradients in fp32 via ``lax.scan``
    before the ONE cross-rank mean and optimizer update — activation memory
    drops by the factor while the wire traffic per update is unchanged
    (beyond-reference: large effective batches on small HBM).
    """
    if mesh is None:
        mesh = make_mesh(axis_name=axis_name)

    if grad_accum_steps < 1:
        raise ValueError(f"grad_accum_steps must be >= 1, got {grad_accum_steps}")
    if error_feedback and grad_reduce is not None:
        raise ValueError("error_feedback=True and grad_reduce are exclusive "
                         "(the optimizer owns the wire collective under EF)")
    # Under EF the builder must NOT pre-reduce: the optimizer's EF
    # transform is the one wire collective (it needs the still-local
    # grads to quantize WITH the residual correction).
    builder_reduce = (lambda g: g) if error_feedback else grad_reduce

    def spmd(params, opt_state, batch):
        def local_loss(p, b):
            out = loss_fn(p, b)
            if has_aux:
                return out
            return out, None

        if grad_accum_steps == 1:
            (loss, aux), grads = _value_and_global_grads(
                lambda p: local_loss(p, batch), params, axis_name,
                allreduce_grad_dtype, builder_reduce)
        else:
            (loss, aux), grads = _accumulated_local_grads(
                local_loss, params, batch, axis_name, grad_accum_steps)
            if builder_reduce is not None:
                grads = builder_reduce(grads)
            else:
                grads = compressed_mean(grads, axis_name, allreduce_grad_dtype)
            loss = _col.pmean(loss, axis_name)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        if has_aux:
            aux = _col.pmean(aux, axis_name)
            return params, opt_state, loss, aux
        return params, opt_state, loss

    if not error_feedback:
        out_specs = (P(), P(), P(), P()) if has_aux else (P(), P(), P())
        smapped = shard_map(
            spmd,
            mesh=mesh,
            in_specs=(P(), P(), P(axis_name)),
            out_specs=out_specs,
        )
        return jax.jit(smapped, donate_argnums=(0, 1) if donate else ())

    from .optimizers import opt_state_partition_specs

    # EF residual leaves shard per rank, so the opt-state specs depend on
    # the state's pytree STRUCTURE — bind shard_map lazily, one compiled
    # program per structure (value variants share it; jit caches by the
    # inner function identity held in `programs`).
    programs = {}

    def step(params, opt_state, batch):
        key = jax.tree_util.tree_structure(opt_state)
        fn = programs.get(key)
        if fn is None:
            ospecs = opt_state_partition_specs(opt_state, axis_name)
            out_specs = ((P(), ospecs, P(), P()) if has_aux
                         else (P(), ospecs, P()))
            smapped = shard_map(
                spmd, mesh=mesh,
                in_specs=(P(), ospecs, P(axis_name)),
                out_specs=out_specs)
            fn = jax.jit(smapped, donate_argnums=(0, 1) if donate else ())
            programs[key] = fn
        return fn(params, opt_state, batch)

    step._programs = programs  # the recompile probes read through this
    step._cache_size = lambda: sum(
        f._cache_size() for f in programs.values())
    return step


def make_flax_train_step(
    model,
    loss_and_metrics: Callable,
    optimizer: optax.GradientTransformation,
    mesh: Optional[Mesh] = None,
    axis_name: str = DEFAULT_AXIS_NAME,
    donate: bool = True,
    allreduce_grad_dtype=None,
    grad_reduce: Optional[Callable] = None,
    preprocess: Optional[Callable] = None,
):
    """Train step for flax modules with mutable ``batch_stats`` (BatchNorm).

    ``loss_and_metrics(logits, batch) -> (loss, metrics)`` over the local
    shard.  Returns ``step(variables, opt_state, batch) -> (variables,
    opt_state, loss, metrics)`` where ``variables = {'params': ...,
    'batch_stats': ...}``.  Running BN statistics are pmean-synced across
    ranks every step, the TPU analog of the reference's
    ``AllreducePersistent`` keeping eval-time BN consistent
    (extensions/allreduce_persistent.py [uv]) — but continuously, not as a
    pre-eval extension.

    ``grad_reduce``: custom wire collective replacing the default pmean —
    e.g. ``ops.collective.hierarchical_pmean`` for the two-tier ICI×DCN
    mean over a multislice mesh (see :func:`_value_and_global_grads`).

    ``preprocess(batch) -> batch`` runs INSIDE the jitted step, on the
    local shard, before the model sees it — the TPU-first input contract:
    upload the network's compact form (e.g. uint8 pixels, 4× fewer
    host→device bytes than float32) and cast/normalize on device, where
    XLA fuses it into the first conv's prologue.  The reference did the
    equivalent transform on CPU inside its iterator workers
    (SURVEY.md §2.9 ImageNet example); on TPU host-side float conversion
    would quadruple PCIe/DCN ingest bytes for zero benefit.
    """
    if mesh is None:
        mesh = make_mesh(axis_name=axis_name)

    def train_step(variables, opt_state, batch):
        if preprocess is not None:
            batch = preprocess(batch)
        params = variables["params"]
        batch_stats = variables.get("batch_stats", {})

        def local_loss(p):
            out, mutated = model.apply(
                {"params": p, "batch_stats": batch_stats},
                batch[0], train=True, mutable=["batch_stats"])
            loss, metrics = loss_and_metrics(out, batch)
            return loss, (mutated, metrics)

        with jax.named_scope("loss_grad"):
            (loss, (mutated, metrics)), grads = _value_and_global_grads(
                local_loss, params, axis_name, allreduce_grad_dtype,
                grad_reduce=grad_reduce)
        with jax.named_scope("optimizer"):
            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
        new_stats = _col.pmean(mutated["batch_stats"], axis_name)
        metrics = _col.pmean(metrics, axis_name)
        return ({"params": params, "batch_stats": new_stats},
                opt_state, loss, metrics)

    smapped = shard_map(
        train_step, mesh=mesh,
        in_specs=(P(), P(), P(axis_name)),
        out_specs=(P(), P(), P(), P()),
    )
    return jax.jit(smapped, donate_argnums=(0, 1) if donate else ())


def replicate(tree, mesh: Optional[Mesh] = None):
    """Place a pytree replicated over the mesh (params/opt_state)."""
    if mesh is None:
        mesh = make_mesh()
    return jax.device_put(tree, NamedSharding(mesh, P()))


def shard_batch(batch, mesh: Optional[Mesh] = None, axis_name: str = DEFAULT_AXIS_NAME):
    """Shard a host batch's leading axis across the mesh (rank-major).

    Single-controller face: every process holds the FULL global batch.
    Under multi-controller (one process per host), use
    :func:`shard_batch_local` instead — each host only loads its own rows.
    """
    if mesh is None:
        mesh = make_mesh(axis_name=axis_name)
    sharding = NamedSharding(mesh, P(axis_name))
    return jax.tree_util.tree_map(lambda x: jax.device_put(x, sharding), batch)


def _ring_mean(g, axis_name: str, world: int):
    """Cross-rank gradient mean as an EXPLICIT ring decomposition —
    ``all_gather(reduce_scatter(g)/P)`` when the leading dim divides by
    the world size, ``psum(g)/P`` otherwise.  Identical math to ``pmean``
    (an all-reduce IS reduce-scatter + all-gather), spelled out through
    the accounted collective face so a traced run books each wire leg
    separately — the demo/smoke path of ``python -m chainermn_tpu.train``.
    """
    if world > 1 and getattr(g, "ndim", 0) >= 1 and g.shape[0] % world == 0:
        return _col.all_gather(
            _col.reduce_scatter(g, axis_name) / world, axis_name)
    return _col.psum(g, axis_name) / world


def make_demo_step(optimizer, mesh: Optional[Mesh] = None,
                   axis_name: str = DEFAULT_AXIS_NAME):
    """Tiny-MLP classification step for the CLI smoke run.

    ``step(state, batch) -> (state, observation)`` with ``state =
    (params, opt_state)`` — the :class:`training.updaters.StandardUpdater`
    contract.  Differentiates the LOCAL loss under ``check_vma=False``
    (no autodiff-inserted cross-rank psum) so the hand-rolled
    :func:`_ring_mean` is the one wire collective, and reduces the
    metrics with accounted ``psum`` — a traced run therefore records
    byte/call counters for ``psum``, ``all_gather`` AND
    ``reduce_scatter``.
    """
    import jax.numpy as jnp

    if mesh is None:
        mesh = make_mesh(axis_name=axis_name)
    world = mesh.devices.size

    def spmd(state, batch):
        params, opt_state = state
        x, y = batch

        def local_loss(p):
            h = jnp.tanh(x @ p["w1"] + p["b1"])
            logits = h @ p["w2"] + p["b2"]
            logp = jax.nn.log_softmax(logits)
            nll = -jnp.take_along_axis(logp, y[:, None], axis=1).mean()
            correct = (logits.argmax(-1) == y).sum()
            return nll, correct

        (loss, correct), grads = jax.value_and_grad(
            local_loss, has_aux=True)(params)
        grads = jax.tree_util.tree_map(
            lambda g: _ring_mean(g, axis_name, world), grads)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        observation = {
            "main/loss": _col.psum(loss, axis_name) / world,
            "main/accuracy": (_col.psum(correct, axis_name)
                              / (x.shape[0] * world)),
        }
        return (params, opt_state), observation

    smapped = shard_map(
        spmd, mesh=mesh,
        in_specs=(P(), P(axis_name)),
        out_specs=(P(), P()),
        check_vma=False,
    )
    return jax.jit(smapped)


def main(argv=None) -> int:
    """``python -m chainermn_tpu.train``: a tiny self-contained training
    run wired through the whole observability stack — Trainer +
    StandardUpdater phase spans, collective accounting (psum /
    all_gather / reduce_scatter), step-time breakdown, and a
    ``--trace-out`` Chrome-trace artifact loadable in Perfetto.  Doubles
    as the CI smoke invocation (tests/test_observability.py).
    """
    import argparse
    import json
    import os

    parser = argparse.ArgumentParser(
        description="chainermn_tpu demo trainer + observability smoke")
    parser.add_argument("--devices", type=int, default=0,
                        help="fake an N-device CPU mesh (0 = real chips)")
    parser.add_argument("--steps", type=int, default=20)
    parser.add_argument("--batchsize", type=int, default=64,
                        help="GLOBAL batch (split across the mesh)")
    parser.add_argument("--hidden", type=int, default=64)
    parser.add_argument("--lr", type=float, default=1e-2)
    parser.add_argument("--n-train", type=int, default=512)
    parser.add_argument("--log-every", type=int, default=10)
    parser.add_argument("--out", default="result")
    parser.add_argument("--trace-out", default=None,
                        help="write a Chrome-trace/Perfetto JSON here "
                             "(also enables tracing); under "
                             "multi-controller each process writes a "
                             "rank shard and process 0 merges them into "
                             "this path (one Perfetto lane per rank)")
    parser.add_argument("--metrics-out", default=None,
                        help="append a versioned JSONL metrics stream "
                             "here (also enables tracing); a Prometheus "
                             "textfile lands next to it at <path>.prom "
                             "and the cross-rank skew report is appended "
                             "at exit")
    parser.add_argument("--watchdog-timeout", type=float, default=1800.0)
    parser.add_argument("--prefetch", action="store_true",
                        help="double-buffered host->device input "
                             "prefetch: batch k+1 assembles on a "
                             "background thread while step k runs "
                             "(exact-resume safe; see docs/ROBUSTNESS.md)")
    parser.add_argument("--checkpoint-dir", default=None,
                        help="enable v2 manifest checkpoints here "
                             "(periodic saves every --checkpoint-every "
                             "iters + auto-resume, including ELASTIC "
                             "resume from a different world size)")
    parser.add_argument("--checkpoint-every", type=int, default=5)
    parser.add_argument("--preemption-grace-s", type=float, default=None,
                        help="treat SIGTERM as a scheduler preemption: "
                             "final async checkpoint + flight bundle + "
                             "exit 0, all within this grace budget "
                             "(requires --checkpoint-dir for the save)")
    parser.add_argument("--self-heal", action="store_true",
                        help="run the rank health plane (ISSUE 13): a "
                             "per-rank heartbeat lease over the KV side "
                             "channel, a collective watchdog that NAMES "
                             "a lost rank instead of hanging, and the "
                             "gang_health /statusz provider; hand-rolled "
                             "loops add live shrink via "
                             "SelfHealingGang.heal() — see "
                             "docs/ROBUSTNESS.md 'Training failure "
                             "domains'")
    parser.add_argument("--self-heal-min-world", type=int, default=1,
                        help="live-shrink floor: below this many "
                             "survivors heal() refuses and the job falls "
                             "back to the PR 8 checkpoint restart")
    parser.add_argument("--self-heal-beat-s", type=float, default=0.05,
                        help="heartbeat interval; detection window is "
                             "beat * (miss_beats + 1) with miss_beats=4")
    parser.add_argument("--statusz-port", type=int, default=None,
                        help="live introspection HTTP server (/statusz "
                             "/metricsz /requestz /debugz) on this port; "
                             "0 picks a free port (printed to stderr)")
    parser.add_argument("--flight-dump-dir", default=None,
                        help="crash-bundle directory for the flight "
                             "recorder (SIGTERM/SIGUSR1/uncaught "
                             "exception/Watchdog dumps land here; "
                             "defaults to --out when --statusz-port or "
                             "an observability sink is active)")
    args = parser.parse_args(argv)

    if args.devices:
        jax.config.update("jax_platforms", "cpu")
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.devices}")

    import numpy as np

    # Local imports: chainermn_tpu's package face (circular at module
    # scope — train.py IS part of the package).
    from . import observability as obs
    from .communicators import create_communicator
    from .extensions.observation_aggregator import ObservationAggregator
    from .extensions.watchdog import Watchdog
    from .iterators import SerialIterator
    from .training.extensions import LogReport, PrintReport
    from .training.trainer import PRIORITY_EDITOR, Trainer
    from .training.updaters import StandardUpdater
    from .topology import enable_compile_cache

    enable_compile_cache()
    if args.trace_out or args.metrics_out:
        obs.enable()
    # flight recorder: bounded ring, always teed; crash bundles go to
    # --flight-dump-dir (explicit) or --out once any sink is active
    obs.install_tracer_tee()
    dump_dir = args.flight_dump_dir
    if dump_dir is None and (args.trace_out or args.metrics_out
                             or args.statusz_port is not None):
        dump_dir = args.out
    if dump_dir:
        from .global_except_hook import add_hook
        obs.install_signal_handlers(dump_dir)
        add_hook()

    comm = create_communicator("xla")
    mesh = comm.mesh
    world = comm.size
    # Rank-sharded artifact mode: one controller process per host means
    # per-PROCESS shards; single-controller writes plain files.
    multi = jax.process_count() > 1
    rank = jax.process_index() if multi else None
    if args.batchsize % world:
        raise SystemExit(
            f"--batchsize {args.batchsize} must divide by the {world}-chip mesh")

    # Learnable synthetic task (labels are a fixed linear map of the
    # inputs — same recipe as examples/mnist).
    in_dim, n_classes = 32, 10
    w_true = np.random.RandomState(42).randn(in_dim, n_classes)
    xs = np.random.RandomState(0).randn(args.n_train, in_dim).astype(np.float32)
    ys = (xs @ w_true).argmax(-1).astype(np.int32)
    dataset = list(zip(xs, ys))

    import optax as _optax

    rng = np.random.RandomState(1)
    params = {
        "w1": (rng.randn(in_dim, args.hidden) / np.sqrt(in_dim)
               ).astype(np.float32),
        "b1": np.zeros((args.hidden,), np.float32),
        "w2": (rng.randn(args.hidden, n_classes) / np.sqrt(args.hidden)
               ).astype(np.float32),
        "b2": np.zeros((n_classes,), np.float32),
    }
    optimizer = _optax.sgd(args.lr, momentum=0.9)
    step = make_demo_step(optimizer, mesh=mesh)
    state = replicate((params, optimizer.init(params)), mesh)

    updater = StandardUpdater(
        SerialIterator(dataset, args.batchsize, seed=0), step, state,
        mesh=mesh, prefetch=args.prefetch)
    trainer = Trainer(updater, (args.steps, "iteration"), out=args.out)
    trainer.extend(ObservationAggregator(comm), trigger=(1, "iteration"),
                   priority=PRIORITY_EDITOR)
    trainer.extend(obs.StepBreakdownReport(items_per_step=args.batchsize))
    monitor = None
    if args.trace_out or args.metrics_out:
        monitor = obs.HealthMonitor()
        trainer.extend(monitor)
    metrics_path = None
    if args.metrics_out:
        metrics_path = (obs.shard_path(args.metrics_out, rank)
                        if rank is not None else args.metrics_out)
        trainer.extend(obs.MetricsReport(
            metrics_path, prometheus_path=metrics_path + ".prom",
            monitor=monitor, rank=rank))
    # goodput attribution for the TRAIN loop: fold the updater's phase
    # stamps (data → host, compute → compute) + the extension pass
    # (host) into a ledger surfaced via /statusz and the final result
    goodput = obs.GoodputLedger()

    class _GoodputFold:
        trigger = (1, "iteration")
        priority = 331  # right after MetricsReport's 330 slot

        def observe(self, tr) -> None:
            phases = getattr(tr.updater, "phase_times", None) or {}
            goodput.add("host", phases.get("data", 0.0))
            goodput.add("compute", phases.get("compute", 0.0))
            ext = getattr(tr, "last_extension_time", None)
            if ext:
                goodput.add("host", ext)

        def __call__(self, tr) -> None:
            pass

        def state_dict(self):
            return {}

        def load_state_dict(self, state):
            pass

    trainer.extend(_GoodputFold(), name="goodput_fold")
    obs.register_provider("train", lambda: {
        "iteration": trainer.iteration,
        "last_phase": trainer.last_phase,
        "elapsed_time": trainer.elapsed_time,
        "goodput": goodput.report(),
    })
    statusz = None
    if args.statusz_port is not None:
        statusz = obs.start_status_server(
            args.statusz_port, dump_dir=dump_dir, rank=rank)
    log = LogReport(trigger=(args.log_every, "iteration"))
    trainer.extend(log)
    trainer.extend(PrintReport(
        ["iteration", "main/loss", "main/accuracy", "time/data",
         "time/compute", "comm/bytes", "throughput/items_per_sec"],
        log, trigger=(args.log_every, "iteration")))
    trainer.extend(Watchdog(timeout=args.watchdog_timeout,
                            dump_dir=args.out, monitor=monitor, rank=rank))
    # Elastic checkpointing + preemption (ISSUE 8, docs/ROBUSTNESS.md):
    # v2 manifest checkpoints resume across WORLD-SIZE changes; SIGTERM
    # inside the grace budget saves a final generation, books the save
    # into the goodput ledger's `checkpoint` bucket, dumps a `preempt`
    # bundle, and exits 0.
    checkpointer = None
    if args.checkpoint_dir:
        from .extensions.checkpoint import create_multi_node_checkpointer
        checkpointer = create_multi_node_checkpointer(
            "train", comm, cp_interval=args.checkpoint_every,
            path=args.checkpoint_dir)
        trainer.extend(checkpointer,
                       trigger=(args.checkpoint_every, "iteration"))
        loaded, it_resumed = checkpointer.maybe_load()
        if it_resumed is not None:
            trainer.load_checkpoint_state(loaded)
            print(f"[chainermn_tpu train] resumed from generation "
                  f"{it_resumed} in {args.checkpoint_dir}",
                  file=__import__("sys").stderr, flush=True)
    if args.preemption_grace_s is not None:
        from .extensions.preemption import PreemptionHandler
        # installed AFTER the flight handlers: SIGTERM now means
        # checkpoint-and-exit-0, SIGUSR1 stays dump-and-continue
        preempt = PreemptionHandler(
            checkpointer, grace_s=args.preemption_grace_s,
            dump_dir=dump_dir or args.out, ledger=goodput, rank=rank)
        trainer.extend(preempt)
    # Self-healing plane (ISSUE 13): heartbeat lease per rank over the
    # communicator's KV side channel + the collective watchdog threaded
    # through the accounted face — a rank death during any eager
    # collective aborts loudly NAMING the lost rank(s) (exit 44, with a
    # `rank_lost` bundle) instead of wedging the gang.  The min-world
    # floor is recorded so operators (and heal() callers) know where
    # live shrink hands back to the PR 8 checkpoint restart.
    gang = None
    if args.self_heal:
        from .extensions.gang import SelfHealingGang
        gang = SelfHealingGang(
            comm.gang_lease_store(),
            rank=jax.process_index(), world=jax.process_count(),
            name="train", beat_interval_s=args.self_heal_beat_s,
            min_world=args.self_heal_min_world,
            dump_dir=dump_dir or args.out)
        gang.start()
        # join barrier BEFORE arming any detector: gang processes boot
        # with arbitrary skew, and a peer that has not started yet must
        # not read as a death (the guard would exit-44 a healthy gang)
        gang.wait_for_members(timeout_s=120.0)
        # the guard bound tracks the GANG's op bound (4× the lease
        # window, ≥ 5 s), floored at 30 s so a legitimately slow eager
        # object collective (blocking KV get on a busy peer) is not
        # mistaken for a death — NOT the step watchdog's budget, which
        # would delay naming a dead rank by many minutes.  Sub-second
        # death detection itself comes from the lease window.
        gang.install_collective_guard(
            timeout_s=max(gang.op_timeout_s, 30.0))
    try:
        trainer.run()
    finally:
        if gang is not None:
            gang.stop()
    updater.close()  # stop the prefetch thread (no-op when not prefetching)

    final = log.log[-1] if log.log else {}
    result = {
        "steps": trainer.iteration,
        "world": world,
        "final_loss": final.get("main/loss"),
        "final_accuracy": final.get("main/accuracy"),
        "goodput": goodput.report(),
    }
    if gang is not None:
        st = gang.stats()
        result["self_heal"] = {
            k: st[k] for k in (
                "epoch", "world", "min_world", "detection_window_s",
                "rank_lost_events", "reconfigs", "fenced_refusals")}
    if statusz is not None:
        result["statusz_port"] = statusz.port
        statusz.stop()
    if args.trace_out:
        obs.export_chrome_trace(args.trace_out, rank=rank)
        result["trace_out"] = (args.trace_out if rank is None
                               else obs.shard_path(args.trace_out, rank))
        result["trace_events"] = len(obs.get_tracer().events())
        result["comm_totals"] = {
            k: {kk: vv for kk, vv in v.items() if kk != "host_time_s"}
            for k, v in obs.comm_report()["per_op"].items()}
        if multi:
            # barrier: every shard on disk before process 0 merges them
            comm.allgather_obj("trace-exported")
            if jax.process_index() == 0:
                merged = obs.merge_trace_shards(
                    args.trace_out, out_path=args.trace_out,
                    expected_ranks=jax.process_count())
                result["merged_trace"] = args.trace_out
                result["merged_ranks"] = merged["metadata"]["merged_ranks"]
    if args.trace_out or args.metrics_out:
        # Cross-rank skew report: collective over the DCN object lane.
        skew = obs.cross_rank_report(comm)
        result["straggler_rank"] = skew["straggler_rank"]
        result["step_time_skew"] = {
            k: round(v, 6) for k, v in skew["step_time"].items()
            if k != "per_rank"}
        if metrics_path and (rank is None or rank == 0):
            w = obs.MetricsWriter(metrics_path, rank=rank)
            w.write(skew, kind="skew_report")
            w.close()
    print(json.dumps(result))
    return 0


def shard_batch_local(local_batch, mesh: Optional[Mesh] = None,
                      axis_name: str = DEFAULT_AXIS_NAME):
    """Assemble a globally-sharded batch from per-process LOCAL rows.

    The multi-controller input path (reference analog: each MPI rank feeds
    its own ``scatter_dataset`` shard straight to its GPU — SURVEY.md §3.4):
    each process passes only the rows its own devices will hold (e.g. the
    output of ``scatter_dataset(...)`` + a local iterator), and the result
    is one global jax.Array whose leading axis is the concatenation over
    processes, without any cross-host data movement.

    Works single-process too (where it equals :func:`shard_batch`), so the
    same input code runs on a laptop mesh and a pod.
    """
    if mesh is None:
        mesh = make_mesh(axis_name=axis_name)
    sharding = NamedSharding(mesh, P(axis_name))
    return jax.tree_util.tree_map(
        lambda x: jax.make_array_from_process_local_data(sharding, x),
        local_batch)


if __name__ == "__main__":
    raise SystemExit(main())
