"""Flight recorder / SLO / introspection tests (ISSUE 5).

Four layers, cheapest first:

* **Ring + bundle units** (no jax): bounded ring semantics, tracer tee,
  atomic bundle dump/read, explain_bundle rendering.
* **SLO math** (no jax, fake clocks): reservoir percentile fidelity,
  goodput partition reconciliation, multi-window burn-rate firing and
  debouncing.
* **Prometheus round-trip**: the exposition text ``export.py`` emits
  parses strictly (# HELP/# TYPE per family, escaped labels) and
  round-trips values.
* **Death tests** (subprocess, the acceptance gate): a REAL tiny
  serving run killed by an injected Watchdog abort AND by SIGTERM each
  leaves a COMPLETE debug bundle on disk, which
  ``scripts/explain_bundle.py`` renders, naming the last completed
  phase.  A slow-tier test drives the live /statusz HTTP surface of a
  serving subprocess.
"""

import json
import os
import signal
import subprocess
import sys
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from chainermn_tpu import observability as obs
from chainermn_tpu.observability import flight
from chainermn_tpu.observability.slo import (
    GoodputLedger, ReservoirSample, SLOTracker)

ROOT = os.path.join(os.path.dirname(__file__), "..")
WORKER = os.path.join(os.path.dirname(__file__), "_flight_worker.py")


@pytest.fixture(autouse=True)
def _clean_observability():
    obs.reset_all()
    flight.get_flight_recorder().clear()
    yield
    obs.disable()
    flight.uninstall_tracer_tee()
    flight.get_flight_recorder().clear()
    flight.set_crash_dump_dir(None)


# ---------------------------------------------------------------------------
# ring + tee
# ---------------------------------------------------------------------------

def test_ring_bounded_and_ordered():
    rec = flight.FlightRecorder(capacity=8)
    for i in range(20):
        rec.record("tick", i=i)
    evs = rec.events()
    assert len(evs) == 8                      # bounded hard
    assert [e["i"] for e in evs] == list(range(12, 20))  # newest kept
    assert rec.total_seen == 20
    assert rec.last("tick")["i"] == 19
    assert rec.last("nope") is None


def test_tracer_tee_captures_spans_and_instants():
    obs.enable()
    flight.install_tracer_tee()
    with obs.span("step", cat="phase", iteration=3):
        pass
    obs.instant("anomaly/x", cat="anomaly")
    obs.add_counter("comm/psum/bytes", 4096)   # counters NOT teed
    kinds = [e["kind"] for e in flight.get_flight_recorder().events()]
    assert kinds == ["span", "instant"]
    span_ev = flight.get_flight_recorder().events()[0]
    assert span_ev["name"] == "step" and span_ev["cat"] == "phase"
    assert span_ev["args"]["iteration"] == 3


def test_comm_accounting_tees_into_ring():
    obs.enable()
    from chainermn_tpu.observability.comm import get_accountant
    get_accountant().record("psum", "mn", 1024, "float32", in_jit=False)
    ev = flight.get_flight_recorder().last("comm")
    assert ev is not None
    assert ev["op"] == "psum" and ev["bytes"] == 1024


# ---------------------------------------------------------------------------
# bundles
# ---------------------------------------------------------------------------

def test_dump_bundle_complete_and_readable(tmp_path):
    obs.enable()
    flight.install_tracer_tee()
    with obs.span("step", cat="phase"):
        pass
    flight.note("phase", name="update", iteration=5)
    flight.register_provider("unit", lambda: {"hello": 1})
    try:
        path = flight.dump_bundle(str(tmp_path), "unit_test",
                                  extra={"why": "test"})
    finally:
        flight.unregister_provider("unit")
    assert os.path.isdir(path)
    for f in flight.BUNDLE_REQUIRED_FILES:
        assert os.path.exists(os.path.join(path, f)), f
    b = flight.read_bundle(path)
    assert b["manifest"]["schema"] == flight.BUNDLE_SCHEMA
    assert b["manifest"]["reason"] == "unit_test"
    assert b["manifest"]["extra"] == {"why": "test"}
    assert any(e["kind"] == "phase" for e in b["flight"])
    assert b["providers"]["unit"] == {"hello": 1}
    assert "traceEvents" in b["trace_tail"]
    assert flight.find_bundles(str(tmp_path)) == [path]
    assert flight.last_bundle() == path
    # no torn bundles: the only entry is the complete one
    assert [d for d in os.listdir(tmp_path) if ".tmp" in d] == []


def test_find_bundles_skips_torn_tmp_dirs(tmp_path):
    """A dump killed mid-write leaves ``<name>.tmp-<pid>``; it must
    never be listed as a complete bundle (real pids have >1 digit)."""
    good = flight.dump_bundle(str(tmp_path), "good")
    torn = tmp_path / "bundle-20260101-000000-killed.tmp-31337"
    torn.mkdir()
    (torn / "MANIFEST.json").write_text('{"truncat')   # torn JSON
    assert flight.find_bundles(str(tmp_path)) == [good]


def test_install_signal_handlers_idempotent(tmp_path):
    """A second install must NOT record the dump handler as the
    'previous' SIGTERM handler (that would loop dump→resend forever
    instead of dying)."""
    import signal as _signal
    prev = _signal.getsignal(_signal.SIGTERM)
    try:
        flight.install_signal_handlers(str(tmp_path))
        flight.install_signal_handlers(str(tmp_path))
        assert flight._prev_handlers[_signal.SIGTERM] is not \
            flight._signal_dump
        assert flight._prev_handlers[_signal.SIGTERM] == prev
    finally:
        _signal.signal(_signal.SIGTERM, prev)
        _signal.signal(_signal.SIGUSR1,
                       flight._prev_handlers.get(_signal.SIGUSR1,
                                                 _signal.SIG_DFL))


def test_broken_provider_never_breaks_the_dump(tmp_path):
    flight.register_provider("boom", lambda: 1 / 0)
    try:
        path = flight.dump_bundle(str(tmp_path), "resilience")
    finally:
        flight.unregister_provider("boom")
    b = flight.read_bundle(path)
    assert "error" in b["providers"]["boom"]


def test_explain_bundle_names_last_phase(tmp_path, capsys):
    flight.note("phase", name="serving/step", tick=12)
    path = flight.dump_bundle(str(tmp_path), "unit")
    sys.path.insert(0, ROOT)
    try:
        from scripts.explain_bundle import main as explain_main
    finally:
        sys.path.remove(ROOT)
    assert explain_main([path, "--json"]) == 0
    rep = json.loads(capsys.readouterr().out)
    assert rep["last_completed_phase"] == "serving/step"
    assert rep["reason"] == "unit"
    # text mode renders without crashing and names the phase
    assert explain_main([str(tmp_path)]) == 0   # dir → newest bundle
    text = capsys.readouterr().out
    assert "last completed phase: serving/step" in text


# ---------------------------------------------------------------------------
# SLO math
# ---------------------------------------------------------------------------

def test_reservoir_bounded_with_faithful_percentiles():
    res = ReservoirSample(capacity=512, seed=0)
    rng = np.random.RandomState(0)
    stream = rng.lognormal(3.0, 0.5, 20_000)
    for v in stream:
        res.add(float(v))
    assert len(res) == 512
    assert res.total_seen == 20_000
    for q in (50, 99):
        true = float(np.percentile(stream, q))
        got = res.percentile(q)
        assert abs(got - true) / true < 0.15, (q, got, true)
    # tiny cases
    one = ReservoirSample(4)
    assert one.percentile(50) is None
    one.add(7.0)
    assert one.percentile(99) == 7.0


def test_goodput_ledger_partitions_wall_time():
    t = [0.0]
    led = GoodputLedger(wall_clock=lambda: t[0])
    with led.measure("compute"):
        t[0] += 3.0
    with led.measure("comm"):
        t[0] += 1.0
    led.add("stall", 0.5)
    t[0] += 0.5
    rep = led.report()
    assert rep["wall_s"] == pytest.approx(4.5)
    assert rep["attributed_s"] == pytest.approx(4.5)
    assert rep["coverage_frac"] == pytest.approx(1.0)
    assert rep["goodput_frac"] == pytest.approx(3.0 / 4.5, abs=1e-3)
    with pytest.raises(ValueError, match="unknown goodput bucket"):
        led.add("naps", 1.0)
    g = led.gauges("x")
    assert g["x/goodput_frac"] == rep["goodput_frac"]
    assert g["x/compute_s"] == pytest.approx(3.0)


def test_goodput_ledger_overlap_attribution():
    # ISSUE 20: comm overlap is ATTRIBUTION metadata, not a bucket —
    # hidden wire time overlaps compute that is already booked, so
    # adding it to the partition would double-count the wall
    t = [0.0]
    led = GoodputLedger(wall_clock=lambda: t[0])
    with led.measure("compute"):
        t[0] += 4.0
    led.add_overlap(wire_s=2.0, hidden_s=1.5)
    rep = led.report()
    assert rep["wall_s"] == pytest.approx(4.0)
    assert rep["attributed_s"] == pytest.approx(4.0)  # partition intact
    assert rep["comm_wire_s"] == pytest.approx(2.0)
    assert rep["comm_hidden_s"] == pytest.approx(1.5)
    assert rep["comm_exposed_s"] == pytest.approx(0.5)
    assert rep["overlap_frac"] == pytest.approx(0.75)
    assert led.gauges("x")["x/overlap_frac"] == pytest.approx(0.75)
    # hidden can never exceed wire (clamped), and no wire -> 0.0
    led.add_overlap(wire_s=1.0, hidden_s=5.0)
    assert led.report()["comm_hidden_s"] == pytest.approx(2.5)
    led.reset()
    assert led.report()["overlap_frac"] == 0.0


def test_slo_burn_fires_only_on_both_windows_and_debounces():
    t = [0.0]
    pages = []
    slo = SLOTracker(ttft_target_ms=100.0, objective=0.9,
                     windows_s=(10.0, 100.0), burn_threshold=2.0,
                     min_observations=5, escalate=pages.append,
                     clock=lambda: t[0])
    # long window filled with GOOD observations: short-window burn alone
    # must not page
    for _ in range(50):
        t[0] += 1.0
        slo.observe_ttft(50.0)
    for _ in range(8):
        t[0] += 1.0
        slo.observe_ttft(500.0)       # short window burning...
    assert pages == []                # ...but the long window is healthy
    # keep violating until the long window burns too
    for _ in range(40):
        t[0] += 1.0
        slo.observe_ttft(500.0)
    assert len(pages) >= 1
    first = pages[0]
    assert first["kind"] == "slo_burn" and first["metric"] == "ttft"
    assert first["burn_rate_short"] > 2.0
    # debounce: one page per short window, not one per observation
    n_pages = len(pages)
    t[0] += 1.0
    slo.observe_ttft(500.0)
    assert len(pages) == n_pages
    st = slo.status()
    assert st["pages"] == len(pages)
    assert st["ttft"]["burn_rate_short"] > 2.0
    # findings reach the flight ring (the PR 2 escalation surface)
    assert flight.get_flight_recorder().last("slo_burn") is not None


def test_slo_throughput_target_direction():
    t = [0.0]
    slo = SLOTracker(tokens_per_sec_target=100.0, objective=0.5,
                     windows_s=(5.0, 50.0), burn_threshold=1.5,
                     min_observations=3, clock=lambda: t[0])
    for _ in range(60):
        t[0] += 1.0
        slo.observe_throughput(10.0)  # far below target
    assert len(slo.findings) >= 1
    assert slo.findings[0]["metric"] == "throughput"


def test_request_flow_events_survive_shard_merge(tmp_path):
    """Acceptance: per-request spans/flows keyed by trace id appear in
    the MERGED Perfetto doc — the async b/n/e events and the trace_id
    args must survive `merge_trace_shards` re-homing pids."""
    obs.enable()
    tid = "req-abc-00000001"
    obs.async_event("b", "request", tid, cat="serving_request")
    obs.complete_event("request/queue_wait", 10, 40,
                       cat="serving_request", trace_id=tid)
    obs.complete_event("request/decode_tick", 60, 5,
                       cat="serving_request", trace_id=tid)
    obs.async_event("e", "request", tid, cat="serving_request")
    shard = tmp_path / "trace.json"
    obs.export_chrome_trace(str(shard), rank=0)
    merged = obs.merge_trace_shards(str(shard),
                                    out_path=str(tmp_path / "m.json"))
    evs = [e for e in merged["traceEvents"]
           if e.get("cat") == "serving_request"]
    assert {e.get("ph") for e in evs} == {"b", "e", "X"}
    assert all(e["pid"] == 0 for e in evs)          # rank lane
    keyed = [e for e in evs
             if e.get("id") == tid
             or (e.get("args") or {}).get("trace_id") == tid]
    assert len(keyed) == len(evs) == 4


# ---------------------------------------------------------------------------
# prometheus round-trip (satellite)
# ---------------------------------------------------------------------------

def test_prometheus_help_type_and_label_escaping_roundtrip():
    from chainermn_tpu.observability.export import (
        parse_prometheus_text, prometheus_text)

    obs.enable()
    obs.add_counter("serving/tokens_total", 3)
    obs.set_gauge("serving/queue_depth", 2.0)
    nasty = 'we"ird\\span\nname'
    with obs.span(nasty):
        pass
    from chainermn_tpu.observability.comm import get_accountant
    get_accountant().record("psum", "mn", 256, "float32", in_jit=False)
    text = prometheus_text({"extra/g": 1.5})

    parsed = parse_prometheus_text(text)    # raises on malformed output
    fams = parsed["families"]
    for fam in ("chainermn_tpu_serving_tokens_total_total",
                "chainermn_tpu_serving_queue_depth",
                "chainermn_tpu_span_seconds_total",
                "chainermn_tpu_comm_bytes_total",
                "chainermn_tpu_extra_g"):
        assert fam in fams, fam
        assert fams[fam].get("type"), fam         # TYPE present
        assert fams[fam].get("help"), fam         # HELP present
    # exactly ONE TYPE line per family (the old emitter repeated them)
    assert text.count("# TYPE chainermn_tpu_comm_bytes_total ") == 1
    # escaped label value round-trips to the original nasty string
    span_labels = [labels for name, labels, _ in parsed["samples"]
                   if name == "chainermn_tpu_span_count_total"]
    assert {"name": nasty} in span_labels
    # values round-trip
    vals = {(n, tuple(sorted(lab.items()))): v
            for n, lab, v in parsed["samples"]}
    assert vals[("chainermn_tpu_serving_tokens_total_total", ())] == 3.0
    assert vals[("chainermn_tpu_comm_bytes_total",
                 (("axis", "mn"), ("op", "psum")))] == 256.0


def test_parse_prometheus_rejects_malformed():
    from chainermn_tpu.observability.export import parse_prometheus_text

    with pytest.raises(ValueError, match="no preceding # TYPE"):
        parse_prometheus_text("orphan_metric 1.0\n")
    with pytest.raises(ValueError, match="malformed TYPE"):
        parse_prometheus_text("# TYPE x bogus\nx 1\n")
    with pytest.raises(ValueError, match="non-numeric"):
        parse_prometheus_text("# TYPE x gauge\nx banana\n")


# ---------------------------------------------------------------------------
# status server (in-process smoke; the subprocess test is slow-tier)
# ---------------------------------------------------------------------------

def _get(url):
    with urllib.request.urlopen(url, timeout=10) as r:
        return r.status, r.read().decode()


def test_status_server_endpoints(tmp_path):
    obs.enable()
    flight.note("phase", name="unit/phase")
    flight.register_provider("unit", lambda: {"n": 42})
    srv = obs.StatusServer(
        0, requests_fn=lambda: {"requests": [{"id": 1}]},
        extra_gauges=lambda: {"extra/x": 2.5},
        dump_dir=str(tmp_path)).start()
    try:
        base = f"http://127.0.0.1:{srv.port}"
        code, body = _get(base + "/statusz")
        assert code == 200
        statusz = json.loads(body)
        assert statusz["schema"] == "chainermn_tpu.statusz.v1"
        assert statusz["uptime_s"] >= 0
        assert statusz["last_phase"] == "unit/phase"
        assert statusz["providers"]["unit"] == {"n": 42}

        code, body = _get(base + "/metricsz")
        assert code == 200
        from chainermn_tpu.observability.export import (
            parse_prometheus_text)
        parsed = parse_prometheus_text(body)   # valid exposition text
        assert any(n == "chainermn_tpu_extra_x"
                   for n, _, _ in parsed["samples"])

        code, body = _get(base + "/requestz")
        assert json.loads(body)["requests"] == [{"id": 1}]

        code, body = _get(base + "/healthz")
        assert (code, body) == (200, "ok\n")

        code, body = _get(base + "/debugz?dump=1")
        bundle = json.loads(body)["bundle"]
        assert os.path.isdir(bundle)
        flight.read_bundle(bundle)             # complete
        code, body = _get(base + "/debugz")
        assert json.loads(body)["last_bundle"] == bundle

        with pytest.raises(urllib.error.HTTPError) as exc:
            _get(base + "/nope")
        assert exc.value.code == 404
    finally:
        flight.unregister_provider("unit")
        srv.stop()


# ---------------------------------------------------------------------------
# death tests (the acceptance gate): subprocess serving runs
# ---------------------------------------------------------------------------

def _spawn_worker(mode, dump_dir, timeout=240):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)     # 1 device is enough and compiles fast
    proc = subprocess.Popen(
        [sys.executable, WORKER, mode, str(dump_dir)],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        env=env, cwd=ROOT)
    t0 = time.time()
    line = ""
    while time.time() - t0 < timeout:
        line = proc.stdout.readline()
        if "READY" in line or "STATUSZ_PORT" in line:
            return proc, line
        if proc.poll() is not None:
            break
    err = proc.stderr.read() if proc.stderr else ""
    proc.kill()
    raise AssertionError(f"worker {mode} never became ready: "
                         f"{line!r}\n{err[-2000:]}")


def _assert_complete_bundle(dump_dir, reason_substr):
    bundles = flight.find_bundles(str(dump_dir))
    assert bundles, f"no bundle in {dump_dir}: {os.listdir(dump_dir)}"
    b = flight.read_bundle(bundles[-1])        # raises if incomplete
    assert reason_substr in b["manifest"]["reason"]
    # genuine serving state rode along
    assert b["providers"]["serving"]["tokens_emitted"] > 0
    assert b["providers"]["serving"]["requests"]["recent"]
    assert any(e["kind"] == "phase" for e in b["flight"])
    return bundles[-1]


def _explain(bundle_path):
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "scripts",
                                      "explain_bundle.py"),
         bundle_path, "--json"],
        capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, (out.stdout, out.stderr)
    return json.loads(out.stdout)


def test_sigterm_produces_complete_bundle(tmp_path):
    proc, _ = _spawn_worker("sigterm", tmp_path)
    proc.send_signal(signal.SIGTERM)
    proc.wait(timeout=60)
    assert proc.returncode == -signal.SIGTERM  # default disposition kept
    bundle = _assert_complete_bundle(tmp_path, "signal_sigterm")
    rep = _explain(bundle)
    assert rep["last_completed_phase"] == "serving/step"
    assert rep["reason"] == "signal_sigterm"


def test_watchdog_abort_produces_complete_bundle(tmp_path):
    proc, _ = _spawn_worker("watchdog", tmp_path)
    _, err = proc.communicate(timeout=120)
    assert proc.returncode == 43, err[-2000:]  # the watchdog's abort code
    assert "watchdog" in err
    bundle = _assert_complete_bundle(tmp_path, "watchdog_abort")
    b = flight.read_bundle(bundle)
    assert b["manifest"]["extra"]["timeout_s"] == 1.0
    # the stub trainer's position made it into the health snapshot
    assert b["health"]["iteration"] == 7
    rep = _explain(bundle)
    assert rep["last_completed_phase"] == "serving/step"
    # watchdog_health.json (the PR 2 evidence) coexists with the bundle
    assert os.path.exists(tmp_path / "watchdog_health.json")


def test_uncaught_exception_produces_bundle(tmp_path):
    proc, _ = _spawn_worker("crash", tmp_path)
    _, err = proc.communicate(timeout=60)
    assert proc.returncode != 0
    assert "injected uncaught exception" in err
    bundle = _assert_complete_bundle(tmp_path, "uncaught_exception")
    b = flight.read_bundle(bundle)
    crash = b["flight"][-1]
    assert crash["kind"] == "crash"
    assert crash["exc_type"] == "RuntimeError"


@pytest.mark.slow
def test_statusz_live_subprocess(tmp_path):
    """The acceptance endpoint check against a REAL serving process:
    /statusz /metricsz /requestz /debugz all answer over HTTP, and
    /metricsz parses as valid Prometheus exposition text."""
    from chainermn_tpu.observability.export import parse_prometheus_text

    proc, line = _spawn_worker("statusz", tmp_path)
    try:
        port = int(line.strip().split("=", 1)[1])
        base = f"http://127.0.0.1:{port}"
        code, body = _get(base + "/statusz")
        assert code == 200
        statusz = json.loads(body)
        assert statusz["providers"]["serving"]["tokens_emitted"] > 0
        assert statusz["last_phase"] == "serving/step"

        code, body = _get(base + "/metricsz")
        parsed = parse_prometheus_text(body)
        names = {n for n, _, _ in parsed["samples"]}
        assert "chainermn_tpu_serving_tokens_total_total" in names

        code, body = _get(base + "/requestz")
        table = json.loads(body)
        assert table["schema"] == "chainermn_tpu.requestz.v1"
        assert len(table["recent"]) == 3       # the worker's 3 requests
        for row in table["recent"]:
            assert row["trace_id"].startswith("req-")
            assert row["status"] == "done"
            # tenancy columns (ISSUE 17 satellite): ALWAYS present —
            # None for requests that never crossed a tenant-aware
            # router, so the table schema is stable
            for col in ("tenant", "priority", "rung"):
                assert col in row, (col, row)

        code, body = _get(base + "/debugz?dump=1")
        bundle = json.loads(body)["bundle"]
        flight.read_bundle(bundle)
    finally:
        proc.kill()
        proc.wait(timeout=30)
