#!/usr/bin/env python
"""Collective-schedule gate: verify every fleet-reachable (src,dst)
spec pair end to end, verdict machine-readably.

The CLI face of the ISSUE 19 schedule plane (docs/ANALYSIS.md
"Schedule verifier"): every spec pair that elastic resume, ``heal()``
live shrink, and ``rolling_upgrade()`` actually push through
``reshard_host`` is lowered to candidate schedules (single / chunked /
pipelined / hierarchical), each candidate runs the FULL verifier
(structural + byte-coverage vs the array_split statics, exhaustive BFS
of the start/done machine, interpreter byte-exactness), and the
cheapest verified candidate under the r04 cost model is chosen.

Checks (any failure ⇒ exit 1):

* **verified** — every candidate for every pair passes the verifier;
* **hierarchical_win** — on the ICI+DCN fan-out pair the chosen
  schedule beats the single-collective baseline on the cost model;
* **fault_corpus** — the seeded-fault mutators (dropped chunk, double
  write, send/recv cycle, done-before-start, buffer overrun) are each
  caught on a representative schedule — 0 false negatives — while the
  clean candidates all pass — 0 false positives;
* **reconciled** (``--measure`` only) — every chosen schedule EXECUTES
  under the ``ScheduleExecProfile`` and the measured transfer bytes
  reconcile exactly against the IR's declared per-link wire bytes
  (ISSUE 20, docs/PERF.md "Cost-model calibration loop"); the pooled
  records are least-squares-fitted into a per-link (alpha, bw)
  calibration, reported per pair as measured wall + stock/calibrated
  relative error and optionally persisted via ``--calibration-out``
  for ``price_schedule(calibration=)`` /
  ``python -m chainermn_tpu.analysis --gate`` drift checking.

Exit codes: 0 = all pairs verified and checks passed, 1 = a violation
or a missed fault, 2 = inputs unusable.

No jax required: the analysis package is loaded standalone (same
importlib trick as ``lint_spmd.py``), numpy is the only dependency.

Usage::

    python scripts/check_schedules.py
    python scripts/check_schedules.py --shape 48,8 --chunks 2 --json
    python scripts/check_schedules.py --measure --calibration-out \
        calibration.json
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_PKG = os.path.join(_REPO, "chainermn_tpu", "analysis")


def _load_analysis():
    """Load chainermn_tpu.analysis WITHOUT importing chainermn_tpu
    (whose __init__ pulls in jax)."""
    name = "_check_schedules_analysis"
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(_PKG, "__init__.py"),
        submodule_search_locations=[_PKG])
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="check_schedules.py",
        description="Verify every fleet-reachable reshard spec pair "
                    "through the collective schedule verifier")
    p.add_argument("--shape", default="24,4",
                   help="array shape for the pair matrix (divisible "
                        "by worlds 1..4 on the sharded axis)")
    p.add_argument("--dtype", default="float32")
    p.add_argument("--chunks", type=int, default=2)
    p.add_argument("--depth", type=int, default=2)
    p.add_argument("--max-states", type=int, default=500_000)
    p.add_argument("--skip-fault-corpus", action="store_true",
                   help="skip the seeded-fault self-test (pair "
                        "verification only)")
    p.add_argument("--measure", action="store_true",
                   help="execute every chosen schedule under the "
                        "profiler, reconcile measured bytes against "
                        "the IR, and fit a per-link calibration")
    p.add_argument("--reps", type=int, default=3,
                   help="profiled executions per pair with --measure "
                        "(default 3; the median wall is reported)")
    p.add_argument("--calibration-out", default=None,
                   help="with --measure: persist the fitted "
                        "calibration artifact to this path")
    args = p.parse_args(argv)

    try:
        analysis = _load_analysis()
        import importlib
        S = importlib.import_module(analysis.__name__ + ".schedule")
        SC = importlib.import_module(analysis.__name__
                                     + ".schedule_check")
        shape = tuple(int(x) for x in args.shape.split(","))
    except Exception as e:
        print(f"check_schedules: unusable: {e!r}", file=sys.stderr)
        return 2

    pairs = {}
    chosen_scheds = {}
    violations = []
    hier_speedup = None
    try:
        for name, src, dst, sw, dw in SC.FLEET_PAIRS:
            topo = SC.fleet_pair_topology(sw, dw)
            cands = S.candidate_schedules(
                shape, args.dtype, src, dst, sw, dw, topo,
                n_chunks=args.chunks, depth=args.depth)
            rows = []
            best = None
            for sched in cands:
                vr = SC.verify_schedule(sched,
                                        max_states=args.max_states)
                if not vr.ok:
                    violations.append(vr.render())
                    continue
                row = SC.price_schedule(sched)
                row["n_states"] = vr.n_states
                rows.append(row)
                if best is None or row["cost_ms"] < best["cost_ms"]:
                    best = row
                    chosen_scheds[name] = sched
            ok = bool(rows) and len(rows) == len(cands)
            pairs[name] = {
                "ok": ok,
                "spec": [src, dst, sw, dw],
                "topology": [topo.slices, topo.per_slice],
                "chosen": best["kind"] if best else None,
                "cost_ms": best["cost_ms"] if best else None,
                "speedup_vs_single": (rows[0]["cost_ms"]
                                      / best["cost_ms"]
                                      if best and rows else None),
                "candidates": rows,
            }
            if name == "rolling_upgrade_fanout" and best and rows:
                hier_speedup = rows[0]["cost_ms"] / best["cost_ms"]
    except Exception as e:
        print(f"check_schedules: unusable: {e!r}", file=sys.stderr)
        return 2

    corpus = {"checked": 0, "caught": 0, "false_negatives": [],
              "false_positives": []}
    if not args.skip_fault_corpus:
        topo = S.Topology(2, 2)
        for sched in (
                S.lower_hierarchical(shape, args.dtype, 0, None, 4, 4,
                                     topo, n_chunks=args.chunks),
                S.lower_chunked(shape, args.dtype, 0, None, 4, 4,
                                topo, n_chunks=args.chunks)):
            if not SC.verify_schedule(sched).ok:
                corpus["false_positives"].append(sched.name)
            for fault in SC.SEEDED_FAULTS:
                try:
                    bad = SC.seed_fault(sched, fault)
                except ValueError:
                    continue  # fault class not expressible here
                corpus["checked"] += 1
                if SC.verify_schedule(bad).ok:
                    corpus["false_negatives"].append(bad.name)
                else:
                    corpus["caught"] += 1

    measured = None
    if args.measure:
        try:
            CA = importlib.import_module(analysis.__name__
                                         + ".calibrate")
            all_records = []
            reconcile_violations = []
            for name, sched in chosen_scheds.items():
                _, prof = SC.execute_profiled(sched,
                                              reps=max(1, args.reps))
                for run in prof.runs():
                    for v in prof.reconcile(run):
                        reconcile_violations.append(f"{name}: {v}")
                all_records.extend(prof.records)
                walls = sorted(prof.wall_us(r) for r in prof.runs())
                m = walls[len(walls) // 2]
                stock = SC.price_schedule(sched)["wall_us"]
                pairs[name]["measured"] = {
                    "wall_us": round(m, 1),
                    "predicted_stock_us": round(stock, 1),
                    "rel_err_stock": (round(abs(stock - m) / m, 4)
                                      if m else None),
                }
            cal = CA.fit_calibration(all_records)
            for name, sched in chosen_scheds.items():
                pc = S.price_schedule(sched, calibration=cal)["wall_us"]
                m = pairs[name]["measured"]["wall_us"]
                pairs[name]["measured"].update({
                    "predicted_calibrated_us": round(pc, 1),
                    "rel_err_calibrated": (round(abs(pc - m) / m, 4)
                                           if m else None),
                })
            measured = {
                "n_records": len(all_records),
                "reps": max(1, args.reps),
                "reconcile_violations": reconcile_violations,
                "calibration": {
                    link: {"alpha_us": round(fit["alpha_s"] * 1e6, 3),
                           "bw_gbps": round(fit["bw"] / 1e9, 4),
                           "fit_residual": round(fit["residual_rel"],
                                                 4),
                           "n": fit["n"]}
                    for link, fit in sorted(cal["links"].items())},
            }
            if args.calibration_out:
                CA.save_calibration(cal, args.calibration_out)
                measured["calibration_out"] = args.calibration_out
        except Exception as e:
            print(f"check_schedules: unusable: {e!r}", file=sys.stderr)
            return 2

    checks = {
        "verified": not violations and all(r["ok"]
                                           for r in pairs.values()),
        "hierarchical_win": (hier_speedup is not None
                             and hier_speedup > 1.0),
        "fault_corpus": (args.skip_fault_corpus
                         or (not corpus["false_negatives"]
                             and not corpus["false_positives"]
                             and corpus["checked"] > 0)),
    }
    if measured is not None:
        checks["reconciled"] = not measured["reconcile_violations"]
    rc = 0 if all(checks.values()) else 1

    verdict = {
        "ok": rc == 0,
        "checks": checks,
        "shape": list(shape),
        "dtype": args.dtype,
        "n_pairs": len(pairs),
        "hier_speedup": hier_speedup,
        "schedule_violations": len(violations),
        "fault_corpus": corpus,
        "measured": measured,
        "pairs": pairs,
    }
    print(json.dumps(verdict, indent=2, sort_keys=True))
    for v in violations:
        print(v, file=sys.stderr)
    return rc


if __name__ == "__main__":
    sys.exit(main())
