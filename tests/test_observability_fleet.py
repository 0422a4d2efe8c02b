"""Fleet-level observability (ISSUE 2): shard merge, cross-rank skew,
anomaly detection, machine-readable export.

Covers the ISSUE-2 acceptance surface: a 2-rank multiprocess run whose
trace shards merge into one Perfetto document with one lane per rank and
whose skew report NAMES the injected straggler; injected slow-step /
NaN-loss anomalies tripping the corresponding detectors; the JSONL
metrics stream (schema-validated); the watchdog's pre-abort evidence
flush; and the accounting-completeness guard that keeps new collectives
from silently bypassing the byte ledger.
"""

import inspect
import json
import os
import socket
import subprocess
import sys
import time

import numpy as np
import pytest

import chainermn_tpu as mn
from chainermn_tpu import observability as obs
from chainermn_tpu.observability import anomaly, export

ROOT = os.path.join(os.path.dirname(__file__), "..")
_WORKER = os.path.join(os.path.dirname(__file__), "_mp_worker.py")


@pytest.fixture
def tracing():
    obs.reset_all()
    obs.enable()
    yield obs.get_tracer()
    obs.disable()
    obs.reset_all()


# ------------------------------------------------- shard export + merge

def test_rank_sharded_export_and_merge(tmp_path, tracing):
    base = str(tmp_path / "trace.json")
    tr0, tr1 = obs.Tracer(), obs.Tracer()
    for rank, tr in enumerate((tr0, tr1)):
        tr.enable()
        with tr.span("step", cat="step"):
            time.sleep(0.001)
        tr.add_counter("comm/psum/bytes", 32)
        doc = tr.export_chrome_trace(base, rank=rank)
        assert doc["metadata"]["rank"] == rank
        # every event re-homed to pid=rank; shard itself a valid trace
        assert {e["pid"] for e in doc["traceEvents"]} == {rank}
    shards = obs.find_shards(base)
    assert sorted(shards) == [0, 1]

    merged = obs.merge_trace_shards(base, out_path=base)
    assert os.path.exists(base)
    events = merged["traceEvents"]
    assert {e["pid"] for e in events} == {0, 1}  # one lane per rank
    names = {(e["pid"], e["args"]["name"]) for e in events
             if e["ph"] == "M" and e["name"] == "process_name"}
    assert names == {(0, "rank 0"), (1, "rank 1")}
    # non-meta events sorted by timestamp
    ts = [e["ts"] for e in events if e["ph"] != "M"]
    assert ts == sorted(ts)
    assert merged["metadata"]["merged_ranks"] == [0, 1]


def test_merge_tolerates_missing_and_unreadable_shards(tmp_path, capsys):
    ok = tmp_path / "t.rank00000.json"
    ok.write_text(json.dumps({
        "traceEvents": [
            # deliberately out-of-order timestamps
            {"name": "b", "ph": "X", "ts": 50, "dur": 1, "pid": 9, "tid": 0},
            {"name": "a", "ph": "X", "ts": 10, "dur": 1, "pid": 9, "tid": 0},
        ],
        "metadata": {"rank": 0}}))
    bad = tmp_path / "t.rank00001.json"
    bad.write_text("{not json")
    merged = obs.merge_trace_shards(
        [str(ok), str(bad), str(tmp_path / "t.rank00002.json")],
        expected_ranks=3)
    err = capsys.readouterr().err
    assert "unreadable" in err
    assert "missing ranks" in err
    evs = merged["traceEvents"]
    assert [e["name"] for e in evs] == ["a", "b"]  # sorted despite input
    assert {e["pid"] for e in evs} == {0}
    assert merged["metadata"]["merged_ranks"] == [0]


class _FakeComm:
    """allgather_obj stub returning pre-baked per-rank summaries."""

    def __init__(self, per_rank):
        self.per_rank = per_rank
        self.rank = 0

    def allgather_obj(self, obj):
        return list(self.per_rank)


def test_cross_rank_report_names_straggler():
    per_rank = [
        {"rank": 0, "steps": 3, "step_time_s": [0.1, 0.1, 0.1],
         "comm_bytes": 100, "comm_calls": 3, "comm_wait_s": 0.30},
        {"rank": 1, "steps": 3, "step_time_s": [0.1, 0.11, 0.1],
         "comm_bytes": 100, "comm_calls": 3, "comm_wait_s": 0.29},
        {"rank": 2, "steps": 3, "step_time_s": [0.3, 0.31, 0.32],
         "comm_bytes": 100, "comm_calls": 3, "comm_wait_s": 0.01},
    ]
    rep = obs.cross_rank_report(_FakeComm(per_rank))
    assert rep["ranks"] == [0, 1, 2]
    assert rep["straggler_rank"] == 2
    assert rep["straggler_slowdown"] == pytest.approx(3.1, rel=0.05)
    st = rep["step_time"]
    assert st["min"] == pytest.approx(0.1, rel=0.05)
    assert st["max"] == pytest.approx(0.31, rel=0.05)
    assert st["per_rank"]["2"] == pytest.approx(0.31, rel=0.05)
    # the rank everyone waits FOR waits least itself; imbalance = max/mean
    assert rep["comm_wait"]["imbalance"] == pytest.approx(1.5, rel=0.05)


def test_local_rank_summary_reads_step_spans(tracing):
    with obs.span("step", cat="step"):
        time.sleep(0.002)
    with obs.span("step", cat="step"):
        time.sleep(0.002)
    s = obs.local_rank_summary(rank=3)
    assert s["rank"] == 3 and s["steps"] == 2
    assert all(v >= 0.002 for v in s["step_time_s"])


# ------------------------------------------------------- anomaly layer

class _ListIterator:
    def __init__(self, batches):
        self.batches = batches
        self.i = 0
        self.epoch = 0
        self.is_new_epoch = False

    def next(self):
        b = self.batches[self.i % len(self.batches)]
        self.i += 1
        return b

    @property
    def epoch_detail(self):
        return self.i / len(self.batches)


def _toy_trainer(step_fn, n_iter, extensions=()):
    from chainermn_tpu.training.trainer import Trainer
    from chainermn_tpu.training.updaters import StandardUpdater

    batches = [[(np.ones((4, 2), np.float32), np.zeros(4, np.int32))]]
    updater = StandardUpdater(_ListIterator(batches), step_fn, state=0,
                              shard=False)
    trainer = Trainer(updater, (n_iter, "iteration"),
                      out="/tmp/_obs_fleet_out")
    for ext in extensions:
        trainer.extend(ext)
    return trainer


def test_injected_slow_step_trips_spike_detector(tracing):
    det = anomaly.StepTimeSpikeDetector(warmup=3, threshold_z=3.0)
    finding = None
    for i, v in enumerate([0.1, 0.1, 0.11, 0.1, 0.1, 0.1, 1.5]):
        finding = det.update(v, i) or finding
    assert finding is not None and finding["kind"] == "step_time_spike"
    assert finding["value"] == pytest.approx(1.5)
    # the spike is NOT folded into the baseline: a second spike re-fires
    assert det.update(1.5, 99) is not None


def test_injected_nan_loss_trips_loss_detector_in_trainer(tracing, capsys):
    escalated = []

    def step_fn(state, batch):
        loss = float("nan") if state >= 3 else 1.0 / (state + 1)
        return state + 1, {"main/loss": loss}

    monitor = anomaly.HealthMonitor(escalate=escalated.append)
    trainer = _toy_trainer(step_fn, 5, extensions=[monitor])
    trainer.run()
    kinds = [f["kind"] for f in monitor.findings]
    assert "loss_nonfinite" in kinds
    assert monitor.counts["loss_nonfinite"] >= 1
    assert escalated and escalated[0]["kind"] == "loss_nonfinite"
    # structured log line on stderr
    err = capsys.readouterr().err
    assert "[chainermn_tpu health]" in err
    line = next(l for l in err.splitlines()
                if l.startswith("[chainermn_tpu health]"))
    parsed = json.loads(line.split("] ", 1)[1])
    assert parsed["kind"] == "loss_nonfinite"
    # ... and an instant event on the trace timeline
    assert any(e["ph"] == "i" and e["name"] == "anomaly/loss_nonfinite"
               for e in tracing.events())


def test_loss_divergence_and_comm_drift_detectors():
    det = anomaly.LossAnomalyDetector(warmup=3, divergence_factor=3.0)
    finding = None
    for i, v in enumerate([1.0, 0.9, 0.8, 0.85, 42.0]):
        finding = det.update(v, i) or finding
    assert finding is not None and finding["kind"] == "loss_anomaly"

    drift = anomaly.CommBytesDriftDetector(warmup=3, rel_tol=0.25)
    f = None
    for i, v in enumerate([1000, 1000, 1000, 1001, 2500]):
        f = drift.update(v, i) or f
    assert f is not None and f["kind"] == "comm_bytes_drift"
    assert drift.baseline == 1000


def test_mfu_drop_needs_patience():
    det = anomaly.MFUDropDetector(warmup=2, patience=3, frac=0.5)
    for i, v in enumerate([0.5, 0.52, 0.5]):
        assert det.update(v, i) is None
    # two low steps: not yet; the third fires
    assert det.update(0.1, 3) is None
    assert det.update(0.1, 4) is None
    f = det.update(0.1, 5)
    assert f is not None and f["kind"] == "mfu_drop"


def test_escalation_failure_does_not_kill_detection(capsys):
    def bad_escalate(finding):
        raise RuntimeError("pager down")

    monitor = anomaly.HealthMonitor(escalate=bad_escalate)
    monitor._emit({"kind": "loss_nonfinite", "metric": "loss",
                   "iteration": 1, "value": 0.0, "expected": None,
                   "detail": "x"})
    assert monitor.counts["loss_nonfinite"] == 1
    assert "escalation callback failed" in capsys.readouterr().err


# ------------------------------------------------- machine-readable export

def test_metrics_report_streams_jsonl_and_prometheus(tracing, tmp_path):
    mpath = str(tmp_path / "metrics.jsonl")
    ppath = str(tmp_path / "metrics.prom")

    def step_fn(state, batch):
        return state + 1, {"main/loss": 0.5 - 0.01 * state,
                           "note": "not-a-number"}

    monitor = anomaly.HealthMonitor()
    report = export.MetricsReport(mpath, prometheus_path=ppath,
                                  monitor=monitor, prom_every=1)
    trainer = _toy_trainer(step_fn, 3, extensions=[monitor, report])
    trainer.run()

    recs = obs.read_metrics_jsonl(mpath)
    steps = [r for r in recs if r["kind"] == "step"]
    assert len(steps) == 3
    assert all(r["schema"] == obs.METRICS_SCHEMA for r in recs)
    assert steps[0]["iteration"] == 1
    assert steps[0]["main/loss"] == pytest.approx(0.5)
    assert "note" not in steps[0]  # non-numeric observation not exported
    assert "time/data" in steps[0]
    # clean finalize appends the health-snapshot summary record last
    assert recs[-1]["kind"] == "summary"
    assert "spans" in recs[-1] and "comm" in recs[-1]
    assert recs[-1]["anomalies"]["counts"] == {}
    # prometheus textfile present and namespaced
    with open(ppath) as f:
        prom = f.read()
    assert "# TYPE chainermn_tpu_" in prom


def test_read_metrics_jsonl_rejects_unknown_schema(tmp_path):
    p = tmp_path / "bad.jsonl"
    p.write_text(json.dumps({"schema": "somebody.else.v9", "x": 1}) + "\n")
    with pytest.raises(ValueError, match="unknown metrics schema"):
        obs.read_metrics_jsonl(str(p))
    assert obs.read_metrics_jsonl(str(p), strict=False) == []


def test_read_metrics_jsonl_tolerates_torn_final_line(tmp_path):
    p = tmp_path / "torn.jsonl"
    good = json.dumps({"schema": obs.METRICS_SCHEMA, "kind": "step",
                       "t": 0, "iteration": 1})
    p.write_text(good + "\n" + good[: len(good) // 2])
    recs = obs.read_metrics_jsonl(str(p))
    assert len(recs) == 1


def test_health_snapshot_contents(tracing):
    with obs.span("step", cat="step"):
        pass
    obs.add_counter("comm/psum/bytes", 64)
    snap = obs.health_snapshot()
    assert snap["schema"] == obs.METRICS_SCHEMA
    assert snap["kind"] == "health_snapshot"
    assert snap["counters"]["comm/psum/bytes"] == 64
    assert "step" in snap["spans"]
    assert "per_op" in snap["comm"]


# ------------------------------------------------- watchdog evidence flush

def test_watchdog_flushes_evidence_before_action(tracing, tmp_path):
    from chainermn_tpu.extensions.watchdog import Watchdog

    with obs.span("step", cat="step"):
        pass

    class T:
        last_progress = None
        last_phase = "update"
        iteration = 3
        out = str(tmp_path)

    fired = []
    monitor = anomaly.HealthMonitor()
    w = Watchdog(timeout=0.05, poll_interval=0.01,
                 action=lambda gap, to: fired.append(gap),
                 monitor=monitor)
    t = T()
    w.initialize(t)
    try:
        w.observe(t)
        deadline = time.time() + 5
        while not fired and time.time() < deadline:
            time.sleep(0.01)
    finally:
        w.finalize()
    assert fired, "watchdog did not fire"
    health = json.load(open(tmp_path / "watchdog_health.json"))
    assert health["watchdog"]["timeout_s"] == pytest.approx(0.05)
    assert health["watchdog"]["last_phase"] == "update"
    assert health["iteration"] == 3
    assert "comm" in health and "spans" in health
    assert health["anomalies"]["counts"] == {}
    # tracing was on → the trace buffer survived the (simulated) abort
    trace_doc = json.load(open(tmp_path / "watchdog_trace.json"))
    assert any(e.get("name") == "step" for e in trace_doc["traceEvents"])


# --------------------------------------------- accounting completeness

def test_every_collective_wrapper_books_through_accountant():
    """New collectives cannot silently bypass observability: every public
    callable in ops/collective.py must route through the accounting entry
    point (observability.comm.collective, imported there as ``_acc``),
    and every CommunicatorBase subclass's eager collectives must carry
    the ``_obs_wrapped`` stamp the auto-wrapper applies."""
    from chainermn_tpu.communicators.base import (
        _ACCOUNTED_OPS, CommunicatorBase)
    from chainermn_tpu.ops import collective as col

    # in-jit face: public functions must call _acc(...) (or be on the
    # explicit non-collective allowlist)
    non_collectives = {"axis_index", "axis_size", "zeros_like_vma",
                       "pmean_if_bound",  # delegates to pmean
                       # pure-arithmetic cost-model faces (ISSUE 6/14):
                       # consumed by analysis/shardflow.py, they never
                       # touch the wire
                       "collective_wire_cost", "quantized_ring_cost",
                       "quantized_ring_static_groups",
                       "choose_pipeline_depth",
                       # the block quantizer pair (ISSUE 14): the ring's
                       # and the EF residual's shared operator — pure
                       # elementwise arithmetic
                       "block_quantize", "block_dequantize"}
    for name, fn in vars(col).items():
        if name.startswith("_") or not inspect.isfunction(fn):
            continue
        if fn.__module__ != col.__name__ or name in non_collectives:
            continue
        src = inspect.getsource(fn)
        assert "_acc(" in src, (
            f"ops.collective.{name} does not book through the "
            f"accountant — route it through observability.comm.collective")

    # eager face: every concrete subclass collective is auto-wrapped
    def all_subclasses(cls):
        out = set()
        for sub in cls.__subclasses__():
            out.add(sub)
            out |= all_subclasses(sub)
        return out

    subclasses = all_subclasses(CommunicatorBase)
    assert subclasses, "no communicator backends registered?"
    for cls in subclasses:
        for op in _ACCOUNTED_OPS:
            fn = cls.__dict__.get(op)
            if fn is None:
                continue  # inherited (wrapped where defined)
            assert getattr(fn, "_obs_wrapped", False), (
                f"{cls.__name__}.{op} escaped the accounting wrapper")
        # any override of a base array collective must be in the
        # accounted set — a new backend cannot rename its way around it
        array_collectives = {"allreduce", "bcast", "gather", "allgather",
                             "alltoall", "scatter", "send", "recv",
                             "broadcast_data", "multi_node_mean_grad"}
        for op in array_collectives & set(cls.__dict__):
            assert op in _ACCOUNTED_OPS


def test_naive_backend_books_every_collective_functionally(tracing):
    """Beyond introspection: actually CALL each eager collective on the
    numpy loopback backend and assert a ledger row lands."""
    comm = mn.NaiveCommunicator(size=4)
    stack = comm.stack([np.full((2,), float(r), np.float32)
                        for r in range(4)])
    a2a = comm.stack([np.zeros((4, 2), np.float32) for _ in range(4)])
    calls = [
        ("allreduce", lambda: comm.allreduce(stack)),
        ("bcast", lambda: comm.bcast(stack, root=1)),
        ("gather", lambda: comm.gather(stack, root=0)),
        ("allgather", lambda: comm.allgather(stack)),
        ("alltoall", lambda: comm.alltoall(a2a)),
        ("scatter", lambda: comm.scatter(stack, root=0)),
        ("send", lambda: comm.send(stack, dest=1, source=0)),
        ("recv", lambda: comm.recv(stack, source=0, dest=1)),
        ("multi_node_mean_grad",
         lambda: comm.multi_node_mean_grad({"w": stack})),
    ]
    for op, thunk in calls:
        before = obs.comm_report()["per_op"].get(
            f"{op}@world", {"calls": 0})["calls"]
        thunk()
        row = obs.comm_report()["per_op"].get(f"{op}@world")
        assert row is not None and row["calls"] == before + 1, op
        assert row["bytes"] > 0, op


# ------------------------------------------------- 2-rank acceptance run

def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def _clean_env() -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if "host_platform_device_count" not in f]
    env["XLA_FLAGS"] = " ".join(flags)
    return env


def test_two_rank_run_shards_merge_and_name_straggler(tmp_path):
    """ISSUE-2 acceptance: 2 multiprocess CPU ranks produce 2 trace
    shards that merge into one Perfetto JSON with one lane per rank, a
    skew report naming the (injected) straggler rank, and a JSONL
    metrics stream whose every line is a versioned record."""
    n = 2
    port = _free_port()
    procs = [
        subprocess.Popen(
            [sys.executable, _WORKER, str(n), str(i), str(port),
             str(tmp_path), "obs"],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            env=_clean_env())
        for i in range(n)
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=300)
            outs.append(out)
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        pytest.fail("obs gang deadlocked:\n" + "\n".join(
            o or "" for o in outs))
    for i, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"worker {i} failed:\n{out[-4000:]}"
        assert f"WORKER_OK {i}" in out

    # N shards on disk, merged to one valid Perfetto doc, one lane/rank
    base = str(tmp_path / "trace.json")
    shards = obs.find_shards(base)
    assert sorted(shards) == [0, 1]
    merged = obs.merge_trace_shards(base, out_path=base,
                                    expected_ranks=n)
    with open(base) as f:
        doc = json.load(f)
    assert doc["traceEvents"] and doc["displayTimeUnit"] == "ms"
    pids = {e["pid"] for e in doc["traceEvents"]}
    assert pids == {0, 1}
    assert merged["metadata"]["merged_ranks"] == [0, 1]
    for rank in (0, 1):
        steps = [e for e in doc["traceEvents"]
                 if e.get("name") == "step" and e["pid"] == rank]
        assert len(steps) == 4, f"rank {rank} lane missing step spans"

    # the skew report NAMES the injected straggler (rank N-1)
    skew = json.load(open(tmp_path / "skew.json"))
    assert skew["straggler_rank"] == n - 1
    assert skew["straggler_slowdown"] > 1.5
    assert skew["step_time"]["per_rank"]["1"] > \
        skew["step_time"]["per_rank"]["0"]

    # the metrics stream is schema-valid, down to its last line on disk
    mpath = obs.shard_path(str(tmp_path / "metrics.jsonl"), 0)
    recs = obs.read_metrics_jsonl(mpath)
    assert recs and all(r["rank"] == 0 for r in recs)
    assert recs[-1]["kind"] == "skew_report"
    with open(mpath) as f:
        last = json.loads(f.read().splitlines()[-1])
    assert last["schema"] == obs.METRICS_SCHEMA
    assert (last["kind"], last["rank"]) == ("skew_report", 0)


# ------------------------------------------------------------------ CI

def test_cli_smoke_metrics_out_schema(tmp_path):
    """CI satellite: ``python -m chainermn_tpu.train --steps 2
    --metrics-out ...`` in a subprocess; the JSONL stream validates
    against the versioned schema."""
    mpath = str(tmp_path / "metrics.jsonl")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-m", "chainermn_tpu.train",
         "--devices", "2", "--steps", "2", "--batchsize", "16",
         "--out", str(tmp_path / "result"), "--metrics-out", mpath],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["steps"] == 2
    assert result["straggler_rank"] is not None
    recs = obs.read_metrics_jsonl(mpath)  # strict: schema-validated
    kinds = [r["kind"] for r in recs]
    assert kinds.count("step") == 2
    assert "summary" in kinds and "skew_report" in kinds
    assert all(r["schema"] == obs.METRICS_SCHEMA for r in recs)
    step = next(r for r in recs if r["kind"] == "step")
    assert "time/data" in step and "comm/bytes" in step
    assert os.path.exists(mpath + ".prom")
    # the stream's last line on disk is a whole, versioned record
    with open(mpath) as f:
        last = json.loads(f.read().splitlines()[-1])
    assert last["schema"] == obs.METRICS_SCHEMA
    assert last["kind"] in ("summary", "skew_report")


def test_pytest_ini_registers_slow_tier():
    """CI satellite: the two-tier marker config must stay in place — the
    default run excludes @slow and the marker is registered."""
    import configparser

    cfg = configparser.ConfigParser()
    cfg.read(os.path.join(ROOT, "pytest.ini"))
    assert cfg.has_section("pytest")
    assert 'not slow' in cfg.get("pytest", "addopts")
    markers = cfg.get("pytest", "markers")
    assert any(line.strip().startswith("slow:")
               for line in markers.splitlines())


# ------------------------------------------ aggregator non-numeric fix

def test_observation_aggregator_passes_through_non_numeric():
    from chainermn_tpu.extensions.observation_aggregator import (
        aggregate_observations)

    comm = mn.NaiveCommunicator(size=2)
    out = aggregate_observations(
        {"main/loss": 2.0, "status": "warming-up",
         "vec": np.ones((3,), np.float32)}, comm)
    assert out["main/loss"] == pytest.approx(2.0)
    assert out["status"] == "warming-up"  # passed through, not crashed
    np.testing.assert_allclose(out["vec"], np.ones(3))


def test_observation_aggregator_names_mismatched_key():
    from chainermn_tpu.extensions.observation_aggregator import (
        aggregate_observations)

    class MismatchComm:
        def allgather_obj(self, obj):
            return [{"grad/norm": np.ones((2,))},
                    {"grad/norm": np.ones((3,))}]

    with pytest.raises(ValueError, match="grad/norm"):
        aggregate_observations({"grad/norm": np.ones((2,))},
                               MismatchComm())
