#!/usr/bin/env python
"""Scenario gate: replay ONE named seeded scenario against a tiny REAL
local fleet, verdict machine-readably.

The CLI face of the scenario plane (ISSUE 18, docs/SERVING.md
"Scenario engine & heterogeneous fleet"): ``chainermn_tpu.serving.
scenarios`` builds the deterministic event stream (same seed ⇒
byte-identical stream — checked here, every run), a 1-2 worker
loopback fleet replays it in scaled wall-clock, the run's HLC causal
journal replays through the PR 15 protocol models, and the verdict is
one JSON object on stdout.

Checks (any failure ⇒ exit 1):

* **repro** — the stream digest is identical when built twice;
* **terminal** — every ACCEPTED request reached exactly one outcome
  (``terminal_frac == 1``);
* **conformance** — the journal replay finds 0 protocol violations;
* optional operator bounds ``--max-shed-rate`` / ``--max-slo-burn``.

Exit codes: 0 = scenario ran and every check passed, 1 = a check
failed, 2 = inputs unusable (unknown scenario, no JAX backend, bad
arguments).

Usage::

    python scripts/run_scenario.py flash_crowd
    python scripts/run_scenario.py composed_chaos --seed 3 --workers 2
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)


def main(argv=None) -> int:
    from chainermn_tpu.serving import scenarios as sc

    p = argparse.ArgumentParser(
        prog="run_scenario.py",
        description="Replay a named seeded scenario against a tiny "
                    "local fleet and gate the outcome")
    p.add_argument("scenario",
                   help=f"one of {sorted(sc.SCENARIOS)}")
    p.add_argument("--seed", type=int, default=0,
                   help="scenario seed (same seed ⇒ identical stream)")
    p.add_argument("--workers", type=int, default=None,
                   help="engine workers (default 2 when the stream "
                        "carries faults, else 1)")
    p.add_argument("--time-scale", type=float, default=1.0,
                   help="virtual-clock scale (0 replays as fast as "
                        "admission allows)")
    p.add_argument("--max-shed-rate", type=float, default=None,
                   help="fail (exit 1) when shed_rate exceeds this")
    p.add_argument("--max-slo-burn", type=float, default=None,
                   help="fail (exit 1) when slo_burn exceeds this")
    args = p.parse_args(argv)

    if args.scenario not in sc.SCENARIOS:
        print(f"run_scenario: unknown scenario {args.scenario!r}; "
              f"known: {sorted(sc.SCENARIOS)}", file=sys.stderr)
        return 2

    # the stream first (jax-free): its determinism is a gated check
    stream = sc.build_scenario(args.scenario, seed=args.seed)
    repro_ok = (sc.stream_digest(stream) == sc.stream_digest(
        sc.build_scenario(args.scenario, seed=args.seed)))
    has_faults = any(e["kind"] == "fault" for e in stream)
    n_workers = args.workers or (2 if has_faults else 1)

    try:
        import jax
        import numpy as np

        import chainermn_tpu as mn
        from chainermn_tpu.parallel import init_tp_transformer_lm
        from chainermn_tpu.serving import TenantTable
        from chainermn_tpu.serving.fleet import build_local_fleet
    except Exception as e:  # no backend on this box: unusable inputs
        print(f"run_scenario: backend unavailable: {e!r}",
              file=sys.stderr)
        return 2

    vocab, d_model, n_heads, n_layers = 128, 32, 4, 2
    params = init_tp_transformer_lm(
        jax.random.PRNGKey(args.seed), vocab, d_model, n_heads, n_layers,
        max_len=64, pos_impl="rope")
    mesh = mn.make_nd_mesh(("model",), (1,), jax.devices()[:1])
    wk = dict(n_slots=4, max_total=64, queue_capacity=24, mesh=mesh)

    # tenancy straight off the stream: each tenant keeps the priority
    # class its first event declared
    tenancy = None
    classes = {}
    for ev in stream:
        if ev["kind"] == "request" and ev.get("tenant") is not None:
            classes.setdefault(str(ev["tenant"]), ev.get("priority"))
    if classes:
        tenancy = TenantTable()
        for tname, cls in sorted(classes.items()):
            tenancy.register(tname, cls)

    from chainermn_tpu.observability import journal as _journal
    from chainermn_tpu.observability.conform import (check_dir,
                                                     render_report)
    jdir = tempfile.mkdtemp(prefix="run-scenario-journal-")
    _journal.configure(jdir, "cli")

    import threading
    router, runtimes = build_local_fleet(
        params, {"engine": n_workers}, head_dim=d_model // n_heads,
        # wide lease window: in-process prefill compiles stall the GIL
        # for seconds (the scenario measures workload response, not
        # detection latency)
        beat_interval_s=0.05, miss_beats=16, worker_kwargs=wk,
        tenancy=tenancy)
    threads = [threading.Thread(target=rt.run, daemon=True)
               for rt in runtimes]
    for t in threads:
        t.start()
    router.start()
    try:
        # warm every prompt-length compile outside the measured window
        for plen in sorted({ev["prompt"]["len"] for ev in stream
                            if ev["kind"] == "request"}):
            h = router.submit(np.zeros(plen, np.int32), 2)
            t0 = time.time()
            while (h.status not in ("done", "evicted")
                   and time.time() - t0 < 30):
                time.sleep(0.005)
        router.reset_stats()
        matrix = sc.run_scenario(
            stream, router, vocab=vocab, time_scale=args.time_scale,
            runtimes=runtimes if has_faults else (), tenancy=tenancy,
            max_attempts=2, settle_timeout_s=60.0)
    finally:
        router.stop()
        for rt in runtimes:
            rt.finished = True
        for t in threads:
            t.join(timeout=5)
        router.close()
        _journal.reset()

    report = check_dir(jdir)
    if not report["ok"]:
        print(render_report(report), file=sys.stderr)
    shutil.rmtree(jdir, ignore_errors=True)

    checks = {
        "repro": repro_ok,
        "terminal": matrix["terminal_frac"] == 1.0,
        "conformance": bool(report["ok"]),
    }
    if args.max_shed_rate is not None:
        checks["shed_rate"] = matrix["shed_rate"] <= args.max_shed_rate
    if args.max_slo_burn is not None:
        checks["slo_burn"] = matrix["slo_burn"] <= args.max_slo_burn
    rc = 0 if all(checks.values()) else 1

    verdict = {
        "scenario": args.scenario,
        "seed": args.seed,
        "workers": n_workers,
        "ok": rc == 0,
        "checks": checks,
        "conformance_violations": len(report["violations"]),
        "conformance_checked": int(sum(report["checked"].values())),
        "repro_violations": int(not repro_ok),
        **{k: v for k, v in matrix.items()
           if k not in ("worker_trace", "fault_log")},
    }
    print(json.dumps(verdict, indent=2, sort_keys=True))
    return rc


if __name__ == "__main__":
    sys.exit(main())
