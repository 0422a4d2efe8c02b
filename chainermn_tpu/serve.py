#!/usr/bin/env python
"""CLI demo: continuous-batching serving over the toy-corpus LM.

``python -m chainermn_tpu.serve`` trains the same tiny
arithmetic-progression LM as ``examples/generate`` (each next token =
previous + step mod V — learnable, so correct serving output is
eyeballable), then stands up a :class:`chainermn_tpu.serving
.ServingEngine` and pushes a STAGGERED request schedule through it:
the first wave saturates the slot pool, later waves arrive while it is
still decoding, and the engine interleaves them at iteration level —
the thing the closed-batch generator cannot do.

Outputs: per-request streamed lines on stderr, ONE summary JSON line on
stdout (request outcomes + the serving metrics dict), optional
``--metrics-out`` JSONL stream (``chainermn_tpu.metrics.v1`` records,
kinds ``serving_step``/``serving_summary``) and ``--prom-out``
Prometheus textfile — both the formats the observability layer already
exports.

``--replicas N`` (ISSUE 7) stands up N engines behind the serving
router instead: least-loaded prefix-affine dispatch, SLO-aware
shedding, fleet-wide metrics/statusz — the summary then carries the
``router/*`` keys (per-reason rejection counters included) and the
JSONL stream gains ``router_rejection``/``router_summary`` records.

``--fleet-procs N`` (ISSUE 10) spawns N engine workers as separate
PROCESSES over the file lanes, supervised by the heartbeat/lease health
plane (death detection, in-flight failover, zombie fencing); the demo's
load generator honors ``retry_after_ms`` via ``submit_with_retry``, and
the run ends with a graceful rolling drain (every worker exits 0 —
asserted in the summary's ``fleet_exit_codes``).  ``--disagg P:D
--procs`` runs the role-split workers cross-process the same way.

Run:  python -m chainermn_tpu.serve --devices 8 --tp 2
      python -m chainermn_tpu.serve --steps-budget 40 --requests 8 \
          --metrics-out /tmp/serve.jsonl --prom-out /tmp/serve.prom
      python -m chainermn_tpu.serve --replicas 2 --requests 12
      python -m chainermn_tpu.serve --fleet-procs 2 --requests 8
"""

import argparse
import json
import os
import sys
import time


def make_corpus(rng, n, seq_len, vocab):
    """Arithmetic progressions mod vocab (examples/generate's corpus)."""
    import numpy as np

    starts = rng.randint(0, vocab, n)
    steps = rng.randint(1, 4, n)
    pos = np.arange(seq_len + 1)
    return ((starts[:, None] + steps[:, None] * pos[None]) % vocab
            ).astype("int32")


def main(argv=None):
    parser = argparse.ArgumentParser(
        description="ChainerMN-TPU serving demo: continuous-batching "
                    "inference over a slot-managed KV-cache pool")
    parser.add_argument("--devices", type=int, default=0,
                        help="force N virtual CPU devices (0 = leave the "
                             "backend alone; ignored once jax initialized)")
    parser.add_argument("--tp", type=int, default=1,
                        help="model-axis width for serving")
    parser.add_argument("--vocab", type=int, default=64)
    parser.add_argument("--d-model", type=int, default=32)
    parser.add_argument("--n-heads", type=int, default=4)
    parser.add_argument("--kv-heads", type=int, default=None)
    parser.add_argument("--n-layers", type=int, default=2)
    parser.add_argument("--seq-len", type=int, default=24)
    parser.add_argument("--pos-impl", default="learned",
                        choices=["learned", "rope"])
    parser.add_argument("--train-steps", type=int, default=60,
                        help="toy-LM training steps before serving")
    parser.add_argument("--seed", type=int, default=0,
                        help="PRNG seed for model init (spmd-lint: literal "
                             "PRNGKey seeds belong on the CLI, not in code)")
    parser.add_argument("--lr", type=float, default=1e-2)
    parser.add_argument("--replicas", type=int, default=1,
                        help="serving replicas behind the router (ISSUE "
                             "7): N engines, least-loaded prefix-affine "
                             "dispatch, SLO-aware shedding; 1 = the "
                             "single-engine path")
    parser.add_argument("--disagg", default=None, metavar="P:D",
                        help="disaggregated topology (ISSUE 9): P "
                             "prefill workers + D decode workers with "
                             "the KV-transfer plane between them "
                             "(e.g. --disagg 1:2); mutually exclusive "
                             "with --replicas > 1")
    parser.add_argument("--transport", default="local",
                        choices=["local", "lanes"],
                        help="disagg KV-transfer transport: 'local' = "
                             "the compiled reshard path, 'lanes' = the "
                             "DCN object lanes (ledger-booked bytes)")
    parser.add_argument("--fleet-procs", type=int, default=0,
                        help="cross-PROCESS fleet (ISSUE 10): spawn N "
                             "engine workers as separate processes over "
                             "the file lanes, supervised by the "
                             "heartbeat/lease health plane with "
                             "in-flight failover; mutually exclusive "
                             "with --replicas > 1 / --disagg")
    parser.add_argument("--procs", action="store_true",
                        help="with --disagg P:D: run the role workers "
                             "as separate PROCESSES over the lanes "
                             "instead of in-process (ISSUE 10)")
    parser.add_argument("--lane-dir", default=None,
                        help="directory for the cross-process file "
                             "lanes (default: a fresh temp dir)")
    parser.add_argument("--autoscale", default=None, metavar="MIN:MAX",
                        help="with --fleet-procs: attach the ISSUE 11 "
                             "load-driven autoscaler (scale-up spawns "
                             "worker processes, scale-down always "
                             "drains; e.g. --autoscale 1:4); decisions "
                             "land as autoscale_decision flight events "
                             "and in the summary")
    parser.add_argument("--tenants", action="store_true",
                        help="two-tenant QoS demo (ISSUE 11): even "
                             "requests bill to tenant 'gold' (paid), "
                             "odd to 'free' (best_effort, budgeted) — "
                             "the summary carries per-tenant "
                             "goodput/TTFT/shed attribution; needs a "
                             "router topology (--replicas/--disagg/"
                             "--fleet-procs)")
    parser.add_argument("--beat-interval-s", type=float, default=0.05,
                        help="worker heartbeat interval; the router "
                             "declares death after miss_beats=4 missed "
                             "beats (detection window "
                             "= beat * (4+1); docs/ROBUSTNESS.md)")
    parser.add_argument("--submit-retries", type=int, default=3,
                        help="client-side submit attempts: shed/full "
                             "rejections honor retry_after_ms with "
                             "jittered backoff before giving up "
                             "machine-readably (submit_with_retry)")
    parser.add_argument("--temperature", type=float, default=0.0,
                        help="per-request sampling temperature (0 = "
                             "greedy); >0 samples under the lm_generate "
                             "rng contract with per-request keys derived "
                             "from --seed")
    parser.add_argument("--n-slots", type=int, default=4)
    parser.add_argument("--max-total", type=int, default=None,
                        help="per-slot capacity (default: fits prompt + "
                             "max-new)")
    parser.add_argument("--queue-capacity", type=int, default=16)
    parser.add_argument("--requests", type=int, default=8)
    parser.add_argument("--prompt-len", type=int, default=6)
    parser.add_argument("--max-new-tokens", type=int, default=8)
    parser.add_argument("--stagger-every", type=int, default=2,
                        help="submit one later-wave request every N engine "
                             "steps after the first wave")
    parser.add_argument("--steps-budget", type=int, default=None,
                        help="hard cap on engine iterations (the run exits "
                             "cleanly with whatever finished)")
    parser.add_argument("--metrics-out", default=None,
                        help="JSONL metrics stream (serving_step records + "
                             "serving_summary roll-up)")
    parser.add_argument("--prom-out", default=None,
                        help="Prometheus textfile with the serving gauges")
    parser.add_argument("--trace-out", default=None,
                        help="enable the tracer; Chrome-trace JSON with the "
                             "per-request serving spans/instants")
    parser.add_argument("--statusz-port", type=int, default=None,
                        help="start the live introspection HTTP server "
                             "(/statusz /metricsz /requestz /debugz) on "
                             "this port; 0 picks a free port (printed to "
                             "stderr)")
    parser.add_argument("--flight-dump-dir", default=None,
                        help="enable the flight recorder's crash bundles: "
                             "SIGTERM/SIGUSR1/uncaught exceptions dump a "
                             "debug bundle into this directory")
    parser.add_argument("--ttft-slo-ms", type=float, default=None,
                        help="TTFT SLO target; enables the multi-window "
                             "burn-rate tracker")
    parser.add_argument("--tps-slo", type=float, default=None,
                        help="tokens/sec SLO target for the burn tracker")
    args = parser.parse_args(argv)

    if args.devices:
        import jax
        jax.config.update("jax_platforms", "cpu")
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.devices}")

    import jax
    import numpy as np
    import optax
    from functools import partial
    from jax.sharding import NamedSharding, PartitionSpec as P

    import chainermn_tpu as mn
    from chainermn_tpu import observability as obs
    from chainermn_tpu.parallel import (
        init_tp_transformer_lm, make_hybrid_shard_map_step, shard_pytree,
        state_specs_like, tp_transformer_lm_loss, transformer_lm_specs)
    from chainermn_tpu.serving import AdmissionError, ServingEngine
    from chainermn_tpu.topology import enable_compile_cache

    enable_compile_cache()
    if args.trace_out:
        obs.enable()
    # flight recorder: always on (bounded ring, negligible cost); crash
    # bundles + signal handlers only when a dump dir is configured
    obs.install_tracer_tee()
    if args.flight_dump_dir:
        from chainermn_tpu import global_except_hook
        obs.install_signal_handlers(args.flight_dump_dir)
        global_except_hook.add_hook()

    n = len(jax.devices())
    if n % args.tp:
        raise SystemExit(f"--tp {args.tp} does not divide {n} devices")
    dp = n // args.tp
    head_dim = args.d_model // args.n_heads
    total_len = args.prompt_len + args.max_new_tokens
    max_len = max(args.seq_len, total_len)

    # ---- train the toy LM (same recipe as examples/generate) ----
    params = init_tp_transformer_lm(
        jax.random.PRNGKey(args.seed), args.vocab, args.d_model, args.n_heads,
        args.n_layers, max_len=max_len, pos_impl=args.pos_impl,
        n_kv_heads=args.kv_heads)
    train_mesh = mn.make_nd_mesh(("data", "model"), (dp, args.tp))
    specs = transformer_lm_specs(params, "model")
    optimizer = optax.adam(args.lr)
    loss_fn = partial(tp_transformer_lm_loss, head_dim=head_dim,
                      axis_name="model")
    step = make_hybrid_shard_map_step(loss_fn, optimizer, train_mesh, params,
                                      specs, donate=False)
    p = shard_pytree(params, train_mesh, specs)
    st = shard_pytree(optimizer.init(params), train_mesh,
                      state_specs_like(optimizer, params, specs))
    rng = np.random.RandomState(0)
    for i in range(args.train_steps):
        tokens = make_corpus(rng, 8 * dp, args.seq_len, args.vocab)
        batch = (jax.device_put(tokens, NamedSharding(train_mesh, P("data"))),)
        p, st, loss = step(p, st, batch)
        if i % 30 == 0 or i == args.train_steps - 1:
            print(f"train step {i:3d}  loss {float(loss):.4f}",
                  file=sys.stderr)
    trained = jax.tree_util.tree_map(np.asarray, p)  # global host copy

    # ---- serve ----
    serve_mesh = mn.make_nd_mesh(("model",), (args.tp,),
                                 jax.devices()[: args.tp])
    writer = None
    if args.metrics_out:
        from chainermn_tpu.observability.export import MetricsWriter
        writer = MetricsWriter(args.metrics_out)
    slo = None
    if args.ttft_slo_ms is not None or args.tps_slo is not None:
        from chainermn_tpu.observability.slo import SLOTracker
        slo = SLOTracker(ttft_target_ms=args.ttft_slo_ms,
                         tokens_per_sec_target=args.tps_slo)
    eng_kwargs = dict(
        head_dim=head_dim, n_slots=args.n_slots,
        max_total=args.max_total or max(total_len, 8),
        mesh=serve_mesh, queue_capacity=args.queue_capacity)
    router = None
    disagg = None
    fleet = None
    autoscaler = None
    n_p = n_d = 0
    tenancy = None
    if args.tenants:
        if args.replicas <= 1 and not args.disagg and not args.fleet_procs:
            raise SystemExit("--tenants needs a router topology "
                             "(--replicas N / --disagg P:D / "
                             "--fleet-procs N) — the tenant plane lives "
                             "at the router's admission gate")
        from chainermn_tpu.serving import TenantTable
        tenancy = TenantTable()
        tenancy.register("gold", "paid")
        # the best-effort tenant carries a modest concurrency budget so
        # the demo shows budget sheds under the staggered burst
        tenancy.register("free", "best_effort",
                         max_inflight=max(args.n_slots // 2, 1))
    autoscale_range = None
    if args.autoscale:
        # validated BEFORE build_proc_fleet: failing after the spawn
        # would leak orphaned worker processes on the SystemExit
        if not args.fleet_procs:
            raise SystemExit("--autoscale drives the cross-process "
                             "fleet: combine it with --fleet-procs N")
        try:
            autoscale_range = tuple(
                int(x) for x in args.autoscale.split(":"))
        except ValueError:
            raise SystemExit(f"--autoscale wants MIN:MAX (e.g. 1:4), "
                             f"got {args.autoscale!r}")
        if len(autoscale_range) != 2 \
                or not 1 <= autoscale_range[0] <= autoscale_range[1]:
            raise SystemExit(f"--autoscale needs 1 <= MIN <= MAX, "
                             f"got {args.autoscale!r}")
    if args.disagg:
        if args.replicas > 1:
            raise SystemExit("--disagg and --replicas > 1 are mutually "
                             "exclusive topologies")
        try:
            n_p, n_d = (int(x) for x in args.disagg.split(":"))
        except ValueError:
            raise SystemExit(f"--disagg wants P:D (e.g. 1:2), got "
                             f"{args.disagg!r}")
        if n_p < 1 or n_d < 1:
            raise SystemExit(f"--disagg needs at least one worker per "
                             f"role, got {args.disagg!r}")
    if args.fleet_procs or (args.disagg and args.procs):
        # cross-PROCESS fleet (ISSUE 10): every worker a separate
        # process over the file lanes, supervised by the lease plane
        if args.fleet_procs and (args.replicas > 1 or args.disagg):
            raise SystemExit("--fleet-procs is mutually exclusive with "
                             "--replicas > 1 / --disagg")
        import tempfile
        from chainermn_tpu.serving.fleet import build_proc_fleet
        topology = ({"engine": args.fleet_procs} if args.fleet_procs
                    else {"prefill": n_p, "decode": n_d})
        lane_dir = args.lane_dir or tempfile.mkdtemp(
            prefix="chainermn_tpu_lanes_")
        fleet = build_proc_fleet(
            trained, topology, lane_dir, head_dim=head_dim,
            beat_interval_s=args.beat_interval_s,
            bundle_dir=args.flight_dump_dir,
            worker_kwargs=dict(
                n_slots=args.n_slots,
                max_total=eng_kwargs["max_total"],
                queue_capacity=args.queue_capacity),
            slo=slo, metrics_writer=writer, tenancy=tenancy)
        print(f"fleet: spawned {topology} worker process(es), lanes at "
              f"{lane_dir}", file=sys.stderr)
        if autoscale_range is not None:
            lo, hi = autoscale_range
            from chainermn_tpu.serving.autoscale import (
                AutoscalePolicy, FleetAutoscaler, proc_spawn_factory)
            autoscaler = FleetAutoscaler(
                fleet,
                proc_spawn_factory(
                    lane_dir, os.path.join(lane_dir, "fleet_params.pkl"),
                    beat_interval_s=args.beat_interval_s,
                    bundle_dir=args.flight_dump_dir),
                policies=[AutoscalePolicy(
                    role=role, min_workers=lo, max_workers=hi)
                    for role in topology],
                metrics_writer=writer)
            print(f"autoscale: {args.autoscale} attached "
                  f"(scale-down is always a drain)", file=sys.stderr)
        eng = None
    elif args.disagg:
        from chainermn_tpu.serving import build_disagg_fleet
        disagg = build_disagg_fleet(
            trained, n_p, n_d, head_dim=head_dim,
            max_total=eng_kwargs["max_total"],
            n_slots=args.n_slots, mesh=serve_mesh,
            queue_capacity=args.queue_capacity,
            transport_mode=args.transport, slo=slo,
            metrics_writer=writer, tenancy=tenancy,
            bundle_dir=args.flight_dump_dir)
        eng = None
    elif args.replicas > 1:
        from chainermn_tpu.serving import build_fleet
        # the fleet shares ONE SLO tracker (all replicas burn one
        # budget) and the router owns the JSONL writer (router_rejection
        # + router_summary records ride the serving stream)
        router = build_fleet(trained, args.replicas, slo=slo,
                             metrics_writer=writer, tenancy=tenancy,
                             **eng_kwargs)
        eng = None
    else:
        eng = ServingEngine(trained, metrics_writer=writer, slo=slo,
                            **eng_kwargs)
    service = fleet if fleet is not None else (
        disagg if disagg is not None else (
            router if router is not None else eng))
    statusz = None
    if args.statusz_port is not None:
        statusz = obs.start_status_server(
            args.statusz_port, extra_gauges=service.metrics,
            requests_fn=service.requests_table,
            dump_dir=args.flight_dump_dir)

    test = make_corpus(np.random.RandomState(99), args.requests,
                       max(args.seq_len, total_len), args.vocab)
    prompts = test[:, : args.prompt_len]
    want = test[:, args.prompt_len: args.prompt_len + args.max_new_tokens]

    def stream(tok, rid):
        print(f"request {rid}: token {tok}", file=sys.stderr)

    handles, rejected = {}, {}
    first_wave = min(args.n_slots, args.requests)
    # per-request sampling keys under the lm_generate contract: one key
    # per request derived from --seed, so a re-run with the same seed
    # samples the same sequences and two requests never share noise
    sample_kw = {}
    if args.temperature > 0:
        base_key = jax.random.PRNGKey(args.seed + 1)
        sample_kw = {i: {"temperature": args.temperature,
                         "rng": jax.random.fold_in(base_key, i)}
                     for i in range(args.requests)}

    # client-side honor of retry_after_ms (ISSUE 10 satellite): a shed/
    # full rejection backs off (jittered, bounded) and retries before
    # giving up machine-readably; while waiting the demo keeps DRIVING
    # the service, so in-process topologies can actually drain the
    # backlog the rejection named
    from chainermn_tpu.serving.fleet import submit_with_retry

    def driving_sleep(seconds):
        deadline = time.monotonic() + seconds
        while time.monotonic() < deadline:
            service.step()

    def submit(i):
        tenant_kw = {}
        if tenancy is not None:
            tenant_kw = {"tenant": "gold" if i % 2 == 0 else "free"}
        try:
            handles[i] = submit_with_retry(
                service.submit, prompts[i], args.max_new_tokens,
                max_attempts=max(args.submit_retries, 1),
                sleep=driving_sleep, on_token=stream,
                **tenant_kw, **sample_kw.get(i, {}))
        except AdmissionError as e:
            rejected[i] = e.to_dict()
            print(f"request {i} rejected after "
                  f"{max(args.submit_retries, 1)} attempt(s): {e}",
                  file=sys.stderr)

    def service_busy():
        if fleet is not None:
            return fleet.busy
        if disagg is not None:
            return (any(not w.idle for w in disagg.prefill_workers)
                    or any(not w.idle for w in disagg.decode_workers))
        if router is not None:
            return any(not rep.idle for rep in router.replicas)
        return (eng.scheduler.queue_depth > 0
                or eng.pool.busy_count > 0)

    for i in range(first_wave):
        submit(i)
    steps = 0
    nxt = first_wave
    budget = args.steps_budget

    def can_step():
        return budget is None or steps < budget

    while can_step() and (nxt < args.requests or service_busy()):
        service.step()
        steps += 1
        if nxt < args.requests and steps % max(args.stagger_every, 1) == 0:
            submit(nxt)
            nxt += 1

    # ---- report ----
    per_request = []
    correct = []
    for i in range(args.requests):
        if i in rejected:
            per_request.append(dict({"id": i, "status": "rejected"},
                                    **rejected[i]))
            continue
        h = handles.get(i)
        if h is None:
            per_request.append({"id": i, "status": "not_submitted"})
            continue
        toks = h.tokens
        row = {"id": h.id, "status": h.status,
               "finish_reason": h.finish_reason,
               "n_tokens": len(toks),
               "ttft_ms": (round(h.ttft_ms, 2)
                           if h.ttft_ms is not None else None)}
        if h.status == "done" and len(toks) == args.max_new_tokens:
            acc = float((np.asarray(toks) == want[i]).mean())
            row["continuation_accuracy"] = round(acc, 3)
            correct.append(acc)
        per_request.append(row)
        print(f"prompt {prompts[i].tolist()} -> {toks} "
              f"(true continuation {want[i].tolist()})", file=sys.stderr)

    fleet_exit_codes = None
    if autoscaler is not None:
        autoscaler.stop()
    if fleet is not None:
        # graceful ROLLING drain (the ISSUE 10 acceptance: in-flight
        # work finishes, nothing sheds, every worker exits 0)
        for name in list(fleet.workers):
            if fleet.workers[name].state in ("starting", "live"):
                fleet.drain(name)
                fleet.wait_drained(name, timeout_s=60)
        fleet_exit_codes = fleet.shutdown()
        print(f"fleet: drained; worker exit codes {fleet_exit_codes}",
              file=sys.stderr)
    metrics = service.metrics()
    if fleet is not None:
        goodput = fleet.goodput.report()
    elif disagg is not None:
        # per-worker wall-clock partitions: prefill ledgers carry the
        # transfer bucket, decode ledgers the tick compute/queue-wait
        # split (summing across workers double-counts wall)
        goodput = dict(
            {w.name: w.goodput.report()
             for w in disagg.prefill_workers},
            **{w.name: w.engine.goodput.report()
               for w in disagg.decode_workers})
    elif router is not None:
        # per-replica wall-clock partitions (each replica's ledger is
        # its own 5%-reconciled partition; summing them double-counts)
        goodput = {rep.name: rep.engine.goodput.report()
                   for rep in router.replicas}
    else:
        goodput = eng.goodput.report()
    if writer is not None:
        service.finalize_metrics()
        writer.close()
    if args.prom_out:
        service.write_prometheus(args.prom_out)
    if args.trace_out:
        obs.export_chrome_trace(args.trace_out)
    if statusz is not None:
        statusz.stop()
    service.close()
    dev = jax.devices()[0]
    summary = {
        "schema": "chainermn_tpu.serve.v1",
        # where every number below comes from: the parent's devices
        # (training + in-process engines) and, for a process fleet,
        # the platform each worker process was placed on
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())},
        "worker_platforms": (
            {name: getattr(w.proc, "jax_platforms", None)
             for name, w in fleet.workers.items()}
            if fleet is not None else None),
        "engine_steps": steps,
        "replicas": args.replicas,
        "disagg": args.disagg,
        "fleet_procs": args.fleet_procs or (
            sum(1 for _ in fleet.workers) if fleet is not None else 0),
        "fleet_exit_codes": fleet_exit_codes,
        "requests": per_request,
        "mean_continuation_accuracy": (
            round(float(np.mean(correct)), 3) if correct else None),
        "metrics": {k: round(float(v), 3) for k, v in metrics.items()},
        "goodput": goodput,
    }
    if slo is not None:
        summary["slo"] = slo.status()
    if tenancy is not None:
        summary["tenancy"] = tenancy.state()
    if autoscaler is not None:
        summary["autoscale"] = autoscaler.state()
    print(json.dumps(summary))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
