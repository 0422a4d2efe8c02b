#!/usr/bin/env python
"""Render a flight-recorder debug bundle into a human postmortem.

A bundle (``chainermn_tpu.observability.flight.dump_bundle``) is raw
evidence — ring JSONL, health snapshot, trace tail, provider state.
This script is the first responder's view: WHY did it die, WHAT was it
doing (the last completed phase, per rank when given several rank
shards of one gang), was a STRAGGLER involved, and what the SLO /
goodput state looked like at death.

Usage::

    python scripts/explain_bundle.py result/bundle-20260803-...-sigterm
    python scripts/explain_bundle.py result/            # newest bundle
    python scripts/explain_bundle.py result/ --all      # whole gang
    python scripts/explain_bundle.py <bundle> --json    # machine shape

No JAX import; runs on any box that can read JSON.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from chainermn_tpu.observability.flight import (  # noqa: E402
    find_bundles, read_bundle)


def last_phase_of(bundle: dict):
    """Most reliable "last completed phase" available: the ring's last
    ``phase`` event, falling back to the health snapshot's trainer
    stamp."""
    for ev in reversed(bundle.get("flight", [])):
        if ev.get("kind") == "phase":
            return ev.get("name"), ev
    health = bundle.get("health") or {}
    if health.get("last_phase"):
        return health["last_phase"], None
    wd = (bundle.get("manifest") or {}).get("extra") or {}
    if wd.get("last_phase"):
        return wd["last_phase"], None
    return None, None


def straggler_verdict(bundle: dict):
    """Anomaly/straggler evidence from the ring + health snapshot."""
    trips = [ev for ev in bundle.get("flight", [])
             if ev.get("kind") in ("anomaly", "slo_burn")]
    health = bundle.get("health") or {}
    counts = ((health.get("anomalies") or {}).get("counts")
              if isinstance(health.get("anomalies"), dict) else None)
    if not trips and not counts:
        return {"verdict": "clean",
                "detail": "no anomaly or SLO findings on record"}
    kinds = {}
    for ev in trips:
        k = ev.get("kind") if ev.get("kind") != "anomaly" \
            else ev.get("metric", "anomaly")
        kinds[k] = kinds.get(k, 0) + 1
    slow = [ev for ev in trips
            if "step_time" in str(ev.get("metric", ""))
            or ev.get("kind") == "slo_burn"]
    verdict = "degraded before death" if slow else "anomalous"
    return {"verdict": verdict, "finding_counts": kinds or counts,
            "last_finding": trips[-1] if trips else None}


def explain(bundle: dict) -> dict:
    man = bundle.get("manifest") or {}
    env = bundle.get("env") or {}
    health = bundle.get("health") or {}
    providers = bundle.get("providers") or {}
    phase, phase_ev = last_phase_of(bundle)
    out = {
        "bundle": bundle.get("path"),
        "reason": man.get("reason"),
        "utc": man.get("utc"),
        "pid": man.get("pid"),
        "rank": man.get("rank"),
        "last_completed_phase": phase,
        "last_phase_detail": phase_ev,
        "straggler": straggler_verdict(bundle),
        "ring": {"events": man.get("ring_events"),
                 "dropped_from_head": man.get("ring_dropped_from_head")},
        "iteration": health.get("iteration"),
        "devices": env.get("devices"),
        "jit_cache_size": env.get("jit_cache_size"),
    }
    # last few ring events: the literal final moments
    tail = bundle.get("flight", [])[-8:]
    out["final_events"] = [
        {k: v for k, v in ev.items() if k not in ("args",)}
        for ev in tail]
    serving = providers.get("serving")
    if isinstance(serving, dict):
        out["serving"] = {
            k: serving.get(k)
            for k in ("queue_depth", "busy_slots", "ticks",
                      "tokens_emitted", "rejected", "prefill_compiles")}
        if isinstance(serving.get("goodput"), dict):
            out["goodput"] = {
                "goodput_frac": serving["goodput"].get("goodput_frac"),
                "buckets_frac": serving["goodput"].get("buckets_frac")}
        if isinstance(serving.get("slo"), dict):
            out["slo_at_death"] = {
                "pages": serving["slo"].get("pages"),
                "last_finding": serving["slo"].get("last_finding"),
                "ttft": serving["slo"].get("ttft")}
        reqs = serving.get("requests") or {}
        out["requests_at_death"] = {
            "queued": len(reqs.get("queued", [])),
            "running": len(reqs.get("running", [])),
            "recent": len(reqs.get("recent", []))}
        if isinstance(serving.get("spill"), dict):
            sp = serving["spill"]
            out["spill_at_death"] = {
                k: sp.get(k)
                for k in ("entries", "bytes", "spills", "restores",
                          "crc_refusals", "evictions")}
    # collective truth plane (ISSUE 20): what the schedule interpreter
    # measured on the wire (schedule_exec/* counters) and which fitted
    # cost model the process was pricing schedules with at death
    cal = providers.get("calibration")
    if isinstance(cal, dict):
        counters = cal.get("counters") or {}
        if counters:
            out["schedule_exec"] = {
                "records": counters.get("schedule_exec/records"),
                "executions": counters.get("schedule_exec/executions"),
                "links": {
                    link: {
                        "ops": counters.get(f"schedule_exec/{link}/ops"),
                        "bytes": counters.get(
                            f"schedule_exec/{link}/bytes"),
                        "wall_us": counters.get(
                            f"schedule_exec/{link}/wall_us"),
                    }
                    for link in ("ici", "dcn", "copy")
                    if f"schedule_exec/{link}/ops" in counters},
            }
        if isinstance(cal.get("calibration"), dict):
            c = cal["calibration"]
            out["calibration"] = {
                "schema": c.get("schema"),
                "n_records": c.get("n_records"),
                "links": {
                    link: {"alpha_us": round(
                               float(fit.get("alpha_s", 0.0)) * 1e6, 3),
                           "bw_gbps": round(
                               float(fit.get("bw", 0.0)) / 1e9, 4),
                           "fit_residual": (
                               round(float(fit["residual_rel"]), 4)
                               if fit.get("residual_rel") is not None
                               else None),
                           "n": fit.get("n")}
                    for link, fit in sorted(
                        (c.get("links") or {}).items())
                    if isinstance(fit, dict)},
            }
    train = providers.get("train")
    if isinstance(train, dict):
        out["train"] = {k: train.get(k)
                        for k in ("iteration", "last_phase")}
        if isinstance(train.get("goodput"), dict):
            out["goodput"] = {
                "goodput_frac": train["goodput"].get("goodput_frac"),
                "buckets_frac": train["goodput"].get("buckets_frac")}
    # serving-fleet bundles (ISSUE 10): which worker, which lane, lease
    # age at detection, and every in-flight request's failover outcome
    extra = man.get("extra") or {}
    wl = extra.get("worker_lost")
    if isinstance(wl, dict):
        inflight = wl.get("in_flight") or []
        out["worker_lost"] = {
            "worker": wl.get("worker"),
            "role": wl.get("role"),
            "lane": wl.get("lane"),
            "why": wl.get("why"),
            "lease_age_s": wl.get("lease_age_s"),
            "detection_window_s": wl.get("detection_window_s"),
            "epoch_fenced": wl.get("epoch_fenced"),
            "in_flight": inflight,
            "redispatched": sum(1 for r in inflight
                                if r.get("outcome") == "redispatched"),
            "shed": sum(1 for r in inflight
                        if r.get("outcome") == "shed"),
        }
    drain = extra.get("drain")
    if isinstance(drain, dict):
        out["drain"] = {
            "worker": drain.get("worker"),
            "role": drain.get("role"),
            "lane": drain.get("lane"),
            "lease_age_s": drain.get("lease_age_s"),
            "shed": len(drain.get("in_flight") or []),
        }
    if man.get("reason") == "kv_transfer_fault" or (
            "worker" in extra and "lane" in extra):
        out["kv_transfer_fault"] = {
            "worker": extra.get("worker"),
            "lane": extra.get("lane"),
            "trace_id": extra.get("trace_id"),
        }
    # fleet KV economy (ISSUE 12): why a pull degraded, what spilled /
    # restored, which announces were fenced away, and the cache-index
    # view at death
    rpf = extra.get("remote_pull_fault")
    if isinstance(rpf, dict):
        out["remote_pull_fault"] = {
            k: rpf.get(k)
            for k in ("trace_id", "reason", "detail", "worker", "lane",
                      "owner", "dst", "prefix_len")}
    pulls = [ev for ev in bundle.get("flight", [])
             if ev.get("kind") == "fleet"
             and str(ev.get("event", "")).startswith("remote_pull")]
    if pulls:
        by_event = {}
        for ev in pulls:
            by_event[ev["event"]] = by_event.get(ev["event"], 0) + 1
        out["remote_pulls"] = {
            "events": by_event,
            "last": {k: pulls[-1].get(k)
                     for k in ("event", "trace_id", "owner", "dst",
                               "reason", "prefix_len", "pull_ms",
                               "gain_tokens", "price_tokens")
                     if pulls[-1].get(k) is not None},
        }
    spill_evs = [ev for ev in bundle.get("flight", [])
                 if ev.get("kind") == "serving"
                 and ev.get("event") in ("spill", "restore",
                                         "spill_crc_refused")]
    if spill_evs:
        counts = {}
        for ev in spill_evs:
            counts[ev["event"]] = counts.get(ev["event"], 0) + 1
        out["spill_tier"] = {
            "events": counts,
            "last": {k: spill_evs[-1].get(k)
                     for k in ("event", "prefix_len", "bytes", "slot",
                               "trace_id")
                     if spill_evs[-1].get(k) is not None},
        }
    dropped_announces = [
        ev for ev in bundle.get("flight", [])
        if ev.get("kind") == "fleet" and ev.get("event") == "fenced_refusal"
        and ev.get("msg_kind") == "cache_announce"]
    if dropped_announces:
        out.setdefault("spill_tier", {})
        out["cache_announce_drops"] = {
            "count": len(dropped_announces),
            "workers": sorted({ev.get("worker")
                               for ev in dropped_announces}),
        }
    fleet = providers.get("fleet_health")
    if isinstance(fleet, dict):
        ci = fleet.get("cache_index")
        if isinstance(ci, dict):
            out["cache_index"] = {
                "entries": ci.get("entries"),
                "per_worker": {w: len(v) for w, v in
                               (ci.get("per_worker") or {}).items()},
                "hits": ci.get("hits"),
                "misses": ci.get("misses"),
                "stale_fallbacks": ci.get("stale_fallbacks"),
                "remote_pulls": ci.get("remote_pulls"),
                "pending_pulls": ci.get("pending_pulls"),
                "orphan_tags_swept": ci.get("orphan_tags_swept"),
                "last_pull_fault": ci.get("last_pull_fault"),
            }
        out["fleet_at_death"] = {
            "workers": {n: {"state": w.get("state"),
                            "lease_age_s": w.get("lease_age_s"),
                            "in_flight": w.get("in_flight")}
                        for n, w in (fleet.get("workers") or {}).items()},
            "fenced_refusals": fleet.get("fenced_refusals"),
            "redispatched": fleet.get("redispatched"),
            "shed_inflight": fleet.get("shed_inflight"),
        }
        if isinstance(fleet.get("autoscale"), dict):
            out["autoscale_at_death"] = fleet["autoscale"]
    # autoscaling + overload-degradation evidence (ISSUE 11): the ring's
    # machine-readable autoscale_decision / degrade events answer "why
    # did the fleet resize" and "who got shed, at which rung" — and any
    # provider that carried a tenancy block names per-tenant shed counts
    decisions = [ev for ev in bundle.get("flight", [])
                 if ev.get("kind") == "autoscale_decision"]
    rungs = [ev for ev in bundle.get("flight", [])
             if ev.get("kind") == "degrade"]
    tenancy = None
    for prov in providers.values():
        if isinstance(prov, dict) and isinstance(prov.get("tenancy"),
                                                 dict):
            tenancy = prov["tenancy"]
    if isinstance((man.get("extra") or {}).get("tenancy"), dict):
        tenancy = man["extra"]["tenancy"]
    if decisions:
        out["autoscale"] = {
            "decisions": len(decisions),
            "ups": sum(1 for d in decisions
                       if d.get("direction") == "up"),
            "downs": sum(1 for d in decisions
                         if d.get("direction") == "down"),
            "last": {k: decisions[-1].get(k)
                     for k in ("role", "direction", "before", "target",
                               "reason", "signal", "threshold",
                               "spawned", "drained")
                     if decisions[-1].get(k) is not None},
            "recent": [
                {k: d.get(k) for k in ("role", "direction", "before",
                                       "target", "reason")}
                for d in decisions[-5:]],
        }
    if rungs:
        out["degradation"] = {
            "transitions": len(rungs),
            "max_rung": max(int(ev.get("rung", 0)) for ev in rungs),
            "last": {k: rungs[-1].get(k)
                     for k in ("rung", "name", "from_rung", "pressure")},
        }
    if tenancy is not None:
        out["tenants"] = {
            name: {"priority": t.get("priority"),
                   "shed": t.get("shed"),
                   "degraded": t.get("degraded"),
                   "admitted": t.get("admitted"),
                   "inflight": t.get("inflight")}
            for name, t in (tenancy.get("tenants") or {}).items()}
        if isinstance(tenancy.get("ladder"), dict):
            out.setdefault("degradation", {})["ladder"] = \
                tenancy["ladder"]
    # training-gang bundles (ISSUE 13): which rank died, how stale its
    # lease was when the watchdog named it, what the survivors agreed
    # the new gang is, what the reconfiguration cost, and whether the
    # decision was live shrink or the checkpoint-restart fallback
    rl = extra.get("rank_lost")
    if isinstance(rl, dict):
        out["rank_lost"] = {
            "missing": rl.get("missing"),
            "op": rl.get("op"),
            "epoch": rl.get("epoch"),
            "lease_age_s": rl.get("lease_age_s"),
            "detection_window_s": rl.get("detection_window_s"),
            "elapsed_s": rl.get("elapsed_s"),
            "gap_s": rl.get("gap_s"),
            "step": rl.get("step"),
            "world": rl.get("world"),
            "source": rl.get("source"),
        }
    gr = extra.get("gang_reconfig")
    if isinstance(gr, dict):
        out["gang_reconfig"] = {
            "decision": gr.get("decision"),
            "old_world": gr.get("old_world"),
            "new_world": gr.get("new_world"),
            "dead": gr.get("dead"),
            "members": gr.get("members"),
            "survivors": gr.get("survivors"),
            "min_world": gr.get("min_world"),
            "old_epoch": gr.get("old_epoch"),
            "epoch": gr.get("epoch"),
            "resume_iteration": gr.get("resume_iteration"),
            "detection_ms": gr.get("detection_ms"),
            "consensus_wall_ms": gr.get("consensus_wall_ms"),
            "reshard_wall_ms": gr.get("reshard_wall_ms"),
        }
    gang = providers.get("gang_health")
    if isinstance(gang, dict):
        out["gang_at_death"] = {
            k: gang.get(k)
            for k in ("member", "rank", "epoch", "members", "world",
                      "min_world", "suspects", "fenced_members",
                      "fenced_refusals", "rank_lost_events", "reconfigs",
                      "last_step")}
    # preemption bundles (ISSUE 8): the scheduler took the node, not a
    # bug — surface the grace accounting and the elastic resume hint
    pre = (man.get("extra") or {}).get("preempt")
    if isinstance(pre, dict):
        out["preempt"] = {
            "signal": pre.get("signal"),
            "grace_budget_s": pre.get("grace_budget_s"),
            "grace_used_s": pre.get("grace_used_s"),
            "save_s": pre.get("save_s"),
            "generation_saved": pre.get("generation_saved"),
            "why_not_saved": pre.get("why_not_saved"),
            "world_size": pre.get("world_size"),
            "checkpoint_dir": pre.get("checkpoint_dir"),
            "resume_hint": pre.get("resume_hint"),
        }
    return out


def render_text(rep: dict) -> str:
    lines = [
        f"POSTMORTEM  {rep['bundle']}",
        f"  died:        {rep['reason']}  (utc {rep['utc']}, "
        f"pid {rep['pid']}"
        + (f", rank {rep['rank']}" if rep.get("rank") is not None else "")
        + ")",
        f"  last completed phase: {rep['last_completed_phase']}",
    ]
    if rep.get("iteration") is not None:
        lines.append(f"  iteration:   {rep['iteration']}")
    st = rep.get("straggler") or {}
    lines.append(f"  straggler verdict: {st.get('verdict')}"
                 + (f" — {st['finding_counts']}"
                    if st.get("finding_counts") else ""))
    if rep.get("goodput"):
        g = rep["goodput"]
        lines.append(f"  goodput at death: {g.get('goodput_frac')} "
                     f"(buckets {g.get('buckets_frac')})")
    if rep.get("slo_at_death"):
        lines.append(f"  SLO at death: {json.dumps(rep['slo_at_death'])}")
    if rep.get("serving"):
        lines.append(f"  serving: {json.dumps(rep['serving'])}")
        lines.append(f"  requests at death: "
                     f"{json.dumps(rep['requests_at_death'])}")
    if rep.get("worker_lost"):
        wl = rep["worker_lost"]
        lines.append(
            f"  worker lost: {wl.get('worker')} ({wl.get('role')}) on "
            f"lane {wl.get('lane')}")
        lines.append(
            f"    lease age at detection: {wl.get('lease_age_s')}s "
            f"(window {wl.get('detection_window_s')}s, epoch "
            f"{wl.get('epoch_fenced')} fenced)")
        lines.append(
            f"    in-flight: {wl.get('redispatched')} re-dispatched, "
            f"{wl.get('shed')} shed")
        for row in wl.get("in_flight", []):
            lines.append(
                f"      {row.get('trace_id')}: {row.get('outcome')}"
                + (f" -> {row['to']}" if row.get("to") else ""))
    if rep.get("drain"):
        dr = rep["drain"]
        lines.append(
            f"  drain: {dr.get('worker')} ({dr.get('role')}) finished "
            f"in-flight work and exited (shed {dr.get('shed')})")
    if rep.get("kv_transfer_fault"):
        kv = rep["kv_transfer_fault"]
        lines.append(
            f"  kv transfer fault: worker {kv.get('worker')} on lane "
            f"{kv.get('lane')} (trace {kv.get('trace_id')})")
    if rep.get("remote_pull_fault"):
        rp = rep["remote_pull_fault"]
        lines.append(
            f"  remote pull fault: owner {rp.get('owner')} -> "
            f"{rp.get('dst')} (reason {rp.get('reason')}, lane "
            f"{rp.get('lane')}, trace {rp.get('trace_id')}, prefix "
            f"{rp.get('prefix_len')} tokens) — request fell back to "
            f"re-prefill")
    if rep.get("remote_pulls"):
        rp = rep["remote_pulls"]
        lines.append(
            f"  remote pulls: {json.dumps(rp.get('events'))}"
            + (f"; last {json.dumps(rp['last'])}" if rp.get("last")
               else ""))
    if rep.get("spill_tier"):
        sp = rep["spill_tier"]
        lines.append(f"  spill tier events: {json.dumps(sp.get('events'))}")
    if rep.get("spill_at_death"):
        lines.append(
            f"  spill store at death: {json.dumps(rep['spill_at_death'])}")
    if rep.get("cache_announce_drops"):
        ca = rep["cache_announce_drops"]
        lines.append(
            f"  fenced cache_announce drops: {ca.get('count')} "
            f"(workers {ca.get('workers')})")
    if rep.get("cache_index"):
        ci = rep["cache_index"]
        lines.append(
            f"  fleet cache index: {ci.get('entries')} entries over "
            f"{json.dumps(ci.get('per_worker'))} — hits "
            f"{ci.get('hits')}, misses {ci.get('misses')}, remote "
            f"pulls {ci.get('remote_pulls')}, stale fallbacks "
            f"{json.dumps(ci.get('stale_fallbacks'))}, orphan tags "
            f"swept {ci.get('orphan_tags_swept')}")
    if rep.get("fleet_at_death"):
        fl = rep["fleet_at_death"]
        lines.append(f"  fleet at death: {json.dumps(fl['workers'])}")
        if fl.get("fenced_refusals"):
            lines.append(
                f"    fenced refusals: {json.dumps(fl['fenced_refusals'])}")
    if rep.get("autoscale"):
        a = rep["autoscale"]
        last = a.get("last") or {}
        lines.append(
            f"  autoscale: {a.get('decisions')} decision(s) "
            f"({a.get('ups')} up / {a.get('downs')} down)")
        if last:
            lines.append(
                f"    last: {last.get('direction')} {last.get('role')} "
                f"{last.get('before')} -> {last.get('target')} "
                f"(signal {last.get('reason')}={last.get('signal')} vs "
                f"threshold {last.get('threshold')})"
                + (f", drained {last['drained']}"
                   if last.get("drained") else "")
                + (f", spawned {last['spawned']}"
                   if last.get("spawned") else ""))
    if rep.get("autoscale_at_death"):
        a = rep["autoscale_at_death"]
        lines.append(
            f"  autoscaler at death: targets {a.get('target_sizes')} "
            f"(spawn failures {a.get('spawn_failures')}, drains "
            f"requested {a.get('drains_requested')})")
    if rep.get("degradation"):
        dg = rep["degradation"]
        last = dg.get("last") or {}
        lines.append(
            f"  degradation ladder: max rung {dg.get('max_rung')} over "
            f"{dg.get('transitions')} transition(s); last "
            f"{last.get('from_rung')} -> {last.get('rung')} "
            f"({last.get('name')}) at pressure {last.get('pressure')}")
    if rep.get("tenants"):
        lines.append("  per-tenant overload outcome:")
        for name, t in sorted(rep["tenants"].items()):
            lines.append(
                f"    {name} ({t.get('priority')}): admitted "
                f"{t.get('admitted')}, degraded {t.get('degraded')}, "
                f"shed {json.dumps(t.get('shed') or {})}")
    if rep.get("schedule_exec"):
        se = rep["schedule_exec"]
        per_link = ", ".join(
            f"{link} {int(d.get('ops') or 0)} ops / "
            f"{int(d.get('bytes') or 0)} B / "
            f"{(d.get('wall_us') or 0.0):.0f}us"
            for link, d in sorted((se.get("links") or {}).items()))
        lines.append(
            f"  schedule exec: {int(se.get('records') or 0)} records "
            f"over {int(se.get('executions') or 0)} execution(s)"
            + (f" ({per_link})" if per_link else ""))
    if rep.get("calibration"):
        c = rep["calibration"]
        lines.append(
            f"  calibration in effect: {c.get('schema')} fitted from "
            f"{c.get('n_records')} record(s)")
        for link, fit in sorted((c.get("links") or {}).items()):
            lines.append(
                f"    {link}: alpha {fit.get('alpha_us')}us, bw "
                f"{fit.get('bw_gbps')} GB/s (fit residual "
                f"{fit.get('fit_residual')}, n={fit.get('n')})")
    if rep.get("rank_lost"):
        rl = rep["rank_lost"]
        lines.append(
            f"  rank lost: {rl.get('missing')} during collective "
            f"{rl.get('op')!r} (epoch {rl.get('epoch')}, step "
            f"{rl.get('step')}, world {rl.get('world')})")
        ages = rl.get("lease_age_s")
        lines.append(
            f"    lease age at detection: {json.dumps(ages)}s "
            f"(window {rl.get('detection_window_s')}s"
            + (f", op waited {rl['elapsed_s']}s"
               if rl.get("elapsed_s") is not None else "")
            + (f", guard gap {rl['gap_s']}s"
               if rl.get("gap_s") is not None else "")
            + ")")
    if rep.get("gang_reconfig"):
        gr = rep["gang_reconfig"]
        if gr.get("decision") == "checkpoint_restart":
            lines.append(
                f"  gang reconfig REFUSED: {len(gr.get('survivors') or [])} "
                f"survivor(s) {gr.get('survivors')} below min-world "
                f"{gr.get('min_world')} — decision: checkpoint restart "
                f"(PR 8 elastic resume)")
        else:
            lines.append(
                f"  gang reconfig: world {gr.get('old_world')} -> "
                f"{gr.get('new_world')} (epoch {gr.get('old_epoch')} -> "
                f"{gr.get('epoch')}), dead {gr.get('dead')} — decision: "
                f"live shrink, resume step "
                f"{gr.get('resume_iteration')} + 1 (0 steps lost, no "
                f"checkpoint read)")
            lines.append(
                f"    detection {gr.get('detection_ms')}ms, consensus "
                f"{gr.get('consensus_wall_ms')}ms, reshard "
                f"{gr.get('reshard_wall_ms')}ms")
    if rep.get("gang_at_death"):
        ga = rep["gang_at_death"]
        lines.append(
            f"  gang at death: member {ga.get('member')} (rank "
            f"{ga.get('rank')}) of {ga.get('members')} at epoch "
            f"{ga.get('epoch')}; fenced {ga.get('fenced_members')}, "
            f"refusals {json.dumps(ga.get('fenced_refusals'))}, "
            f"rank_lost events {ga.get('rank_lost_events')}, reconfigs "
            f"{ga.get('reconfigs')}")
    if rep.get("preempt"):
        pre = rep["preempt"]
        used = pre.get("grace_used_s")
        budget = pre.get("grace_budget_s")
        lines.append(
            f"  preemption: {pre.get('signal')} — grace used "
            f"{used if used is not None else '?'}s of "
            f"{budget if budget is not None else '?'}s"
            + (f" (final save {pre['save_s']}s)"
               if pre.get("save_s") is not None else ""))
        if pre.get("generation_saved") is not None:
            lines.append(
                f"    generation saved: {pre['generation_saved']} "
                f"(world size {pre.get('world_size')}, "
                f"{pre.get('checkpoint_dir')})")
        else:
            lines.append(
                f"    NOTHING saved: {pre.get('why_not_saved')}")
        if pre.get("resume_hint"):
            lines.append(f"    resume: {pre['resume_hint']}")
    if rep.get("final_events"):
        lines.append("  final ring events:")
        for ev in rep["final_events"]:
            lines.append(f"    {json.dumps(ev, sort_keys=True)}")
    return "\n".join(lines)


def explain_request(path: str, trace_id: str, *,
                    as_json: bool = False) -> int:
    """The ``--request`` face: the full causal story of one request —
    submit → dispatch → [pull] → prefill → ticks → done/shed, with any
    failover hop — from a merged HLC journal (ISSUE 17)."""
    from chainermn_tpu.observability.journal import (
        MERGE_SCHEMA, find_journals, merge_journals, render_critical_path,
        render_request_story, request_critical_path, request_story)

    if os.path.isdir(path):
        if not find_journals(path):
            print(f"explain_bundle: no journal.*.jsonl files under "
                  f"{path!r}", file=sys.stderr)
            return 2
        merged = merge_journals(path)
    else:
        try:
            with open(path) as f:
                merged = json.load(f)
        except (OSError, ValueError) as e:
            print(f"explain_bundle: cannot read merged journal "
                  f"{path!r}: {e}", file=sys.stderr)
            return 2
        if merged.get("schema") != MERGE_SCHEMA:
            print(f"explain_bundle: {path!r} has schema "
                  f"{merged.get('schema')!r}, expected {MERGE_SCHEMA}",
                  file=sys.stderr)
            return 2
    story = request_story(merged, trace_id)
    if not story["events"]:
        print(f"explain_bundle: no journaled events for request "
              f"{trace_id!r}", file=sys.stderr)
        return 2
    cp = request_critical_path(merged, trace_id)
    if as_json:
        story = dict(story)
        story["critical_path"] = cp
        print(json.dumps(story, indent=2, sort_keys=True, default=str))
    else:
        print(render_request_story(story))
        if cp.get("segments"):
            print()
            print(render_critical_path(cp))
    return 0


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        description="render a chainermn_tpu debug bundle into a "
                    "postmortem")
    parser.add_argument("path",
                        help="a bundle directory, or a directory holding "
                             "bundles (the newest is used)")
    parser.add_argument("--all", action="store_true",
                        help="when PATH holds several bundles (one per "
                             "rank of a gang), render every one")
    parser.add_argument("--json", action="store_true",
                        help="machine-readable output")
    parser.add_argument("--request", default=None, metavar="TRACE_ID",
                        help="render ONE request's cross-process causal "
                             "story from a merged HLC journal; PATH is "
                             "then a journal directory (journal.*.jsonl "
                             "files) or a merged journal JSON")
    args = parser.parse_args(argv)

    if args.request is not None:
        return explain_request(args.path, args.request,
                               as_json=args.json)

    if os.path.exists(os.path.join(args.path, "MANIFEST.json")):
        paths = [args.path]
    else:
        found = find_bundles(args.path)
        if not found:
            print(f"explain_bundle: no bundles under {args.path!r}",
                  file=sys.stderr)
            return 2
        paths = found if args.all else [found[-1]]

    reports = []
    for p in paths:
        try:
            reports.append(explain(read_bundle(p)))
        except (FileNotFoundError, ValueError, OSError) as e:
            # a torn bundle (killed mid-dump) must not take down the
            # postmortem of its intact siblings
            print(f"explain_bundle: skipping {p!r}: {e}", file=sys.stderr)
    if not reports:
        print("explain_bundle: no readable bundles", file=sys.stderr)
        return 2
    if args.json:
        print(json.dumps(reports if args.all else reports[0], indent=2,
                         sort_keys=True, default=str))
    else:
        for rep in reports:
            print(render_text(rep))
            print()
        if len(reports) > 1:
            # gang view: name the rank whose last phase lags the others
            phases = {r.get("rank"): r.get("last_completed_phase")
                      for r in reports}
            print(f"gang: last completed phase per rank: {phases}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
