"""Serving-fleet chaos acceptance against REAL worker processes
(ISSUE 10, slow tier; docs/ROBUSTNESS.md "Serving failure domains").

One gang, three phases (spawning a worker process costs a jax boot, so
the phases share it):

* **SIGKILL** a worker mid-decode under live load: the router detects
  death within the configured lease window, every in-flight request
  either completes TOKEN-EXACT on a survivor (greedy decoding is
  deterministic — the failover result matches an uninterrupted run) or
  is shed with a machine-readable ``worker_lost`` + ``retry_after_ms``,
  no thread or gang member hangs (every wait is deadline-bounded), and
  a flight bundle names the dead worker and lane.
* **SIGSTOP/SIGCONT** makes a real zombie: while paused it misses the
  lease window and is fenced; resumed, its stale-epoch leases are
  REFUSED AND COUNTED; the circuit breaker then re-admits it under a
  fresh epoch and it serves again.
* **Graceful drain**: ``drain(worker)`` finishes in-flight work, sheds
  nothing, and the worker process EXITS 0.

Plus the ``serve --fleet-procs`` CLI smoke (schema-checked summary,
rolling drain, per-worker exit code 0).
"""

import json
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")

VOCAB, D, HEADS, LAYERS = 32, 16, 4, 2
HEAD_DIM = D // HEADS


def _worker_env():
    # workers get ONE cpu device (the parent test process forces 8
    # virtual devices; an inherited flag would build a TP=8 engine)
    flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
             if "host_platform_device_count" not in f]
    return {"XLA_FLAGS": " ".join(flags), "JAX_PLATFORMS": "cpu",
            "PYTHONPATH": os.path.abspath(ROOT)}


def _oracle_fn(params, devices, max_new):
    import chainermn_tpu as mn
    from chainermn_tpu.parallel import make_lm_generator

    mesh = mn.make_nd_mesh(("model",), (1,), devices[:1])
    gen = make_lm_generator(mesh, "model", head_dim=HEAD_DIM,
                            max_new_tokens=max_new)
    return lambda p: np.asarray(gen(params, np.asarray(p)[None]))[0].tolist()


def _pump_until(router, pred, timeout, what):
    t0 = time.time()
    while not pred():
        assert time.time() - t0 < timeout, f"hang waiting for {what}"
        router.step()
        time.sleep(0.01)


@pytest.mark.slow
def test_sigkill_zombie_and_drain_against_real_processes(devices,
                                                         tmp_path):
    import jax

    from chainermn_tpu.parallel import init_tp_transformer_lm
    from chainermn_tpu.serving.fleet import build_proc_fleet

    params = init_tp_transformer_lm(
        jax.random.PRNGKey(0), VOCAB, D, HEADS, LAYERS, max_len=64,
        pos_impl="rope")
    bundles = str(tmp_path / "bundles")
    journal_dir = str(tmp_path / "journal")
    router = build_proc_fleet(
        params, {"engine": 3}, str(tmp_path / "lanes"),
        head_dim=HEAD_DIM, beat_interval_s=0.05, miss_beats=4,
        bundle_dir=bundles, journal_dir=journal_dir, env=_worker_env(),
        worker_kwargs=dict(n_slots=2, max_total=24, queue_capacity=16))
    oracle = _oracle_fn(params, devices, 8)
    try:
        _pump_until(router,
                    lambda: all(w.state == "live"
                                for w in router.workers.values()),
                    timeout=120, what="worker boot leases")

        # ---- phase 1: SIGKILL engine0 mid-decode under live load ----
        rng = np.random.RandomState(0)
        prompts = [rng.randint(0, VOCAB, 5).astype(np.int32)
                   for _ in range(8)]
        handles = [router.submit(p, 8) for p in prompts]
        victim = router.workers["engine0"]
        # wait until the victim actually carries in-flight work and
        # has streamed at least one token (mid-decode, not mid-queue)
        _pump_until(
            router,
            lambda: any(e["worker"] == "engine0" and e["req"].tokens
                        for e in router._inflight.values()),
            timeout=60, what="in-flight decode on the victim")
        t_kill = time.monotonic()
        os.kill(victim.proc.pid, signal.SIGKILL)
        _pump_until(router,
                    lambda: all(h.status in ("done", "evicted")
                                for h in handles),
                    timeout=120, what="failover to survivors")
        detect_s = time.monotonic() - t_kill
        det = router.last_detection
        assert det is not None and det["worker"] == "engine0"
        assert "out.engine0" in det["lane"]
        # detection within the window — detect_s is measured at the
        # END of failover (kill -> every handle terminal), so the slack
        # must absorb the survivors' re-decode of the whole batch under
        # CI load, not just the supervisor poll cadence
        assert detect_s < router.lease_window_s + 10.0, detect_s
        done = shed = 0
        for p, h in zip(prompts, handles):
            if h.status == "done":
                done += 1
                assert h.shed_payload is None
                assert h.tokens == oracle(p), (h.tokens, oracle(p))
            else:
                shed += 1
                pay = h.shed_payload
                assert pay is not None
                assert pay["reason"] == "worker_lost"
                assert pay["retry_after_ms"] >= 1.0
        assert done + shed == len(handles)
        assert done > 0          # survivors actually picked up work
        # the bundle names the dead worker + lane; explain renders it
        from chainermn_tpu.observability.flight import find_bundles
        wl_bundles = [b for b in find_bundles(bundles)
                      if "worker_lost" in os.path.basename(b)]
        assert wl_bundles
        out = subprocess.run(
            [sys.executable,
             os.path.join(ROOT, "scripts", "explain_bundle.py"),
             wl_bundles[-1], "--json"],
            capture_output=True, text=True, timeout=60)
        assert out.returncode == 0, out.stderr
        rep = json.loads(out.stdout)
        assert rep["worker_lost"]["worker"] == "engine0"
        assert "out.engine0" in rep["worker_lost"]["lane"]
        assert rep["worker_lost"]["lease_age_s"] is not None
        for row in rep["worker_lost"]["in_flight"]:
            assert row["outcome"] in ("redispatched", "shed")

        # ---- phase 2: SIGSTOP/SIGCONT -> a real zombie ----
        zombie = router.workers["engine1"]
        os.kill(zombie.proc.pid, signal.SIGSTOP)
        try:
            _pump_until(router, lambda: zombie.state == "dead",
                        timeout=60, what="zombie lease-window death")
        finally:
            os.kill(zombie.proc.pid, signal.SIGCONT)
        old_epoch = zombie.epoch
        baseline = dict(router.fence.refusal_counts())
        # resumed: its stale-epoch leases must be refused and counted
        _pump_until(
            router,
            lambda: router.fence.refusal_counts().get("lease", 0)
            > baseline.get("lease", 0),
            timeout=60, what="fenced zombie lease refusals")
        # breaker-governed re-admission under a FRESH epoch
        _pump_until(router,
                    lambda: zombie.state == "live"
                    and zombie.epoch > old_epoch,
                    timeout=60, what="breaker re-admission")
        h = router.submit(prompts[0], 6)
        _pump_until(router, lambda: h.status in ("done", "evicted"),
                    timeout=120, what="post-readmission request")
        assert h.status == "done"

        # ---- phase 3: graceful drain -> worker exits 0 ----
        pre = router.metrics()
        target = "engine2" if router.workers["engine2"].state == "live" \
            else "engine1"
        hs = [router.submit(p, 6) for p in prompts[:2]]
        router.drain(target)
        assert router.wait_drained(target, timeout_s=120), \
            "drain hung"
        _pump_until(router,
                    lambda: all(h.status in ("done", "evicted")
                                for h in hs),
                    timeout=120, what="drain-overlapped requests")
        assert all(h.status == "done" for h in hs), \
            [(h.status, h.finish_reason) for h in hs]
        post = router.metrics()
        assert post["fleet/shed_inflight_total"] == \
            pre["fleet/shed_inflight_total"]      # drain sheds NOTHING
        rc = router.workers[target].proc.wait(timeout=60)
        assert rc == 0, f"drained worker exited {rc}, want 0"
    finally:
        codes = router.shutdown(timeout_s=60)
        router.close()
        from chainermn_tpu.observability import journal as _journal
        _journal.reset()
    # every surviving member terminated (no gang member hangs)
    for name, wc in router.workers.items():
        if wc.proc is not None:
            assert wc.proc.poll() is not None, f"{name} still running"

    # ---- the causal journal of the WHOLE run replays cleanly through
    # the protocol models (ISSUE 17): SIGKILL failover, fenced-zombie
    # refusals, breaker readmission, and the drain — zero violations
    from chainermn_tpu.observability.conform import (check_dir,
                                                     render_report)
    report = check_dir(journal_dir)
    assert report["ok"], render_report(report)
    assert report["checked"]["done_xor_shed"] >= len(prompts)
    assert report["checked"]["lease_fence"] >= 3

    # ---- one failed-over request's cross-process causal story:
    # submit -> dispatch -> worker receive -> failover hop -> terminal,
    # rendered by `explain_bundle.py --request <trace_id>`
    from chainermn_tpu.observability.journal import merge_journals
    merged = merge_journals(journal_dir)
    redis = [e for e in merged["events"]
             if e.get("kind") == "fleet"
             and e.get("event") == "redispatched"]
    assert redis, "SIGKILL under load must force at least one failover"
    tid = redis[0]["trace_id"]
    out = subprocess.run(
        [sys.executable,
         os.path.join(ROOT, "scripts", "explain_bundle.py"),
         journal_dir, "--request", tid],
        capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    story = out.stdout
    assert tid in story and "failover hop" in story
    assert "event=submitted" in story and "event=redispatched" in story
    assert "mbx_recv" in story         # the worker-side receive
    assert "happens-after" in story    # cross-process edges called out
    assert "outcome:" in story


@pytest.mark.slow
def test_autoscale_real_process_scale_down_is_drain(devices, tmp_path):
    """ISSUE 11 chaos acceptance against REAL worker processes: a
    burst drives the autoscaler to SPAWN a worker process; the idle
    tail drives a scale-down that is a DRAIN — the victim process
    finishes in-flight work, sheds NOTHING (``drain_shed == 0``
    asserted from the fleet counters), and its exit payload is code
    0.  Every decision is a machine-readable ``autoscale_decision``."""
    import jax

    from chainermn_tpu.parallel import init_tp_transformer_lm
    from chainermn_tpu.serving.autoscale import (AutoscalePolicy,
                                                 FleetAutoscaler,
                                                 proc_spawn_factory)
    from chainermn_tpu.serving.fleet import build_proc_fleet

    params = init_tp_transformer_lm(
        jax.random.PRNGKey(0), VOCAB, D, HEADS, LAYERS, max_len=64,
        pos_impl="rope")
    lane_dir = str(tmp_path / "lanes")
    journal_dir = str(tmp_path / "journal")
    router = build_proc_fleet(
        params, {"engine": 1}, lane_dir,
        head_dim=HEAD_DIM, beat_interval_s=0.05, miss_beats=4,
        bundle_dir=str(tmp_path / "bundles"), journal_dir=journal_dir,
        env=_worker_env(),
        worker_kwargs=dict(n_slots=2, max_total=24, queue_capacity=16))
    autoscaler = FleetAutoscaler(
        router,
        proc_spawn_factory(
            lane_dir, os.path.join(lane_dir, "fleet_params.pkl"),
            beat_interval_s=0.05, journal_dir=journal_dir,
            env=_worker_env()),
        policies=[AutoscalePolicy(
            role="engine", min_workers=1, max_workers=2,
            up_backlog_tokens_per_worker=24.0,
            down_backlog_tokens_per_worker=4.0,
            up_queue_depth_per_worker=2.0,
            down_queue_depth_per_worker=0.5,
            up_cooldown_s=0.5, down_cooldown_s=1.0,
            down_stable_s=1.0)],
        interval_s=0.1)
    policy = autoscaler.policies["engine"]
    try:
        _pump_until(router,
                    lambda: all(w.state == "live"
                                for w in router.workers.values()),
                    timeout=120, what="worker boot lease")
        rng = np.random.RandomState(0)
        prompts = [rng.randint(0, VOCAB, 5).astype(np.int32)
                   for _ in range(8)]
        handles = [router.submit(p, 8) for p in prompts]
        _pump_until(router,
                    lambda: any(d["direction"] == "up"
                                and d.get("spawned")
                                for d in policy.decisions),
                    timeout=60, what="burst-driven scale-up")
        up = next(d for d in policy.decisions
                  if d["direction"] == "up" and d.get("spawned"))
        spawned = up["spawned"][0]
        assert router.workers[spawned].proc is not None, \
            "scale-up must spawn a real process"
        _pump_until(router,
                    lambda: all(h.status in ("done", "evicted")
                                for h in handles),
                    timeout=180, what="burst drain")
        assert all(h.status == "done" for h in handles)
        # idle tail: scale-down must be a drain, never a kill
        _pump_until(router,
                    lambda: any(d["direction"] == "down"
                                and d.get("drained")
                                for d in policy.decisions),
                    timeout=60, what="idle-tail scale-down")
        down = next(d for d in policy.decisions
                    if d["direction"] == "down" and d.get("drained"))
        victim = down["drained"][0]
        _pump_until(router,
                    lambda: router.workers[victim].state == "drained",
                    timeout=120, what="drain handshake")
        # the worker EXIT PAYLOAD: a drained autoscale victim exits 0
        rc = router.workers[victim].proc.wait(timeout=60)
        assert rc == 0, f"drained worker exited {rc}, want 0"
        m = router.metrics()
        assert m.get("fleet/shed_inflight_total", 0) == 0   # drain_shed
        assert m.get("fleet/rejected/worker_lost", 0) == 0
        assert policy.flap_count() == 0
        assert m["autoscale/engine/flap"] == 0
    finally:
        router.shutdown(timeout_s=60)
        router.close()
        from chainermn_tpu.observability import journal as _journal
        _journal.reset()
    for name, wc in router.workers.items():
        if wc.proc is not None:
            assert wc.proc.poll() is not None, f"{name} still running"
    # scale-up spawn, burst, and drain-down all conform (ISSUE 17)
    from chainermn_tpu.observability.conform import (check_dir,
                                                     render_report)
    report = check_dir(journal_dir)
    assert report["ok"], render_report(report)
    assert report["checked"]["done_xor_shed"] >= len(handles)


@pytest.mark.slow
def test_serve_cli_fleet_procs_subprocess(tmp_path):
    """`serve --fleet-procs 2` end to end in a fresh interpreter:
    schema-checked summary, every request terminal, rolling drain with
    per-worker exit code 0, submit_with_retry wired into the demo."""
    env = dict(os.environ, **_worker_env())
    out = subprocess.run(
        [sys.executable, "-m", "chainermn_tpu.serve",
         "--fleet-procs", "2", "--requests", "6", "--train-steps", "30",
         "--prompt-len", "5", "--max-new-tokens", "6",
         "--lane-dir", str(tmp_path / "lanes")],
        capture_output=True, text=True, timeout=420, env=env,
        cwd=ROOT)
    assert out.returncode == 0, out.stderr[-3000:]
    summary = json.loads(out.stdout.strip().splitlines()[-1])
    assert summary["schema"] == "chainermn_tpu.serve.v1"
    assert summary["fleet_procs"] == 2
    assert summary["fleet_exit_codes"] == {"engine0": 0, "engine1": 0}
    statuses = {r["status"] for r in summary["requests"]}
    assert statuses <= {"done", "rejected"}
    assert sum(r["status"] == "done" for r in summary["requests"]) >= 4
    assert summary["metrics"]["fleet/shed_rate"] == 0.0
    assert summary["goodput"]["buckets_s"]["supervise"] >= 0.0


@pytest.mark.slow
def test_sigkill_slab_owner_mid_remote_pull_real_processes(devices,
                                                           tmp_path):
    """The ISSUE 12 chaos acceptance against REAL processes: the slab-
    owning worker is frozen (SIGSTOP) so a planned remote pull cannot
    complete, then SIGKILL'd mid-pull — the puller's request completes
    TOKEN-EXACT via local re-prefill, the fallback is counted, a
    ``remote_pull_fault`` bundle names the owner and its lane, and no
    process or thread hangs (every wait is deadline-bounded)."""
    import jax

    from chainermn_tpu.parallel import init_tp_transformer_lm
    from chainermn_tpu.serving.fleet import build_proc_fleet

    params = init_tp_transformer_lm(
        jax.random.PRNGKey(0), VOCAB, D, HEADS, LAYERS, max_len=64,
        pos_impl="rope")
    bundles = str(tmp_path / "bundles")
    journal_dir = str(tmp_path / "journal")
    router = build_proc_fleet(
        params, {"engine": 2}, str(tmp_path / "lanes"),
        head_dim=HEAD_DIM, beat_interval_s=0.1, miss_beats=3,
        bundle_dir=bundles, journal_dir=journal_dir, env=_worker_env(),
        worker_kwargs=dict(n_slots=3, max_total=24, queue_capacity=16))
    oracle = _oracle_fn(params, devices, 6)
    try:
        _pump_until(router,
                    lambda: all(w.state == "live"
                                for w in router.workers.values()),
                    timeout=120, what="worker boot leases")
        prompt = (np.arange(10) % VOCAB).astype(np.int32)
        leader = router.submit(prompt, 6)
        _pump_until(router, lambda: leader.status == "done",
                    timeout=120, what="leader prefill")
        assert leader.tokens == oracle(prompt)
        _pump_until(router,
                    lambda: router.cache_index.n_entries >= 1,
                    timeout=60, what="cache announce in the index")
        owner = router.cache_index.workers()[0]
        victim = router.workers[owner]

        # freeze the owner so the pull can NEVER complete, then plan it
        os.kill(victim.proc.pid, signal.SIGSTOP)
        h = router.submit(prompt, 6)
        with router._lock:
            entry = router._inflight[h.trace_id]
            assert entry.get("pull"), "no pull planned — premise broke"
            assert entry["pull"]["owner"] == owner
        os.kill(victim.proc.pid, signal.SIGKILL)     # mid-pull death
        _pump_until(router, lambda: h.status in ("done", "evicted"),
                    timeout=120, what="fallback re-prefill")
        assert h.status == "done"
        assert h.tokens == oracle(prompt)            # token-exact
        m = router.metrics()
        assert m["fleet/cache/stale_fallbacks/owner_lost"] == 1
        assert router.workers[owner].state == "dead"
        assert router.cache_index.entries_for(owner) == {}
        from chainermn_tpu.observability.flight import (find_bundles,
                                                        read_bundle)
        rp_bundles = [b for b in find_bundles(bundles)
                      if "remote_pull_fault" in os.path.basename(b)]
        assert rp_bundles, "no remote_pull_fault bundle dumped"
        rpf = (read_bundle(rp_bundles[-1])["manifest"]["extra"]
               or {})["remote_pull_fault"]
        assert rpf["owner"] == owner and owner in rpf["lane"]
        out = subprocess.run(
            [sys.executable,
             os.path.join(ROOT, "scripts", "explain_bundle.py"),
             rp_bundles[-1], "--json"],
            capture_output=True, text=True, timeout=60)
        assert out.returncode == 0, out.stderr
        assert json.loads(out.stdout)["remote_pull_fault"]["owner"] \
            == owner
    finally:
        codes = router.shutdown()
        router.close()
        from chainermn_tpu.observability import journal as _journal
        _journal.reset()
    # the survivor exits cleanly; the SIGKILL'd owner reports -9
    assert codes.get(owner) == -signal.SIGKILL
    assert all(c == 0 for w, c in codes.items() if w != owner), codes
    # mid-pull owner death conforms end to end (ISSUE 17): the pull
    # cancellation, the counted fallback, and the slot churn all replay
    # through the protocol models with zero violations
    from chainermn_tpu.observability.conform import (check_dir,
                                                     render_report)
    report = check_dir(journal_dir)
    assert report["ok"], render_report(report)
    assert report["checked"]["slot_lifecycle"] >= 1
