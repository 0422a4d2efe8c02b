"""Schedule-exec profiler + cost-model calibration loop (ISSUE 20,
``analysis/schedule_check.ScheduleExecProfile`` +
``analysis/calibrate.py``).

Contracts under test:

* **Fit recovery** — synthetic records generated from known per-link
  (alpha, bw) constants are recovered by the least-squares fit within
  tolerance, and the fit is DETERMINISTIC (same records in,
  byte-identical artifact out — no timestamps, no host salt).
* **Ingestion discipline** — torn trailing lines, partial records and
  foreign schemas are dropped, not fatal; journal-enveloped records
  (the ``reshard_host`` tee) unwrap to the same samples as raw lines.
* **Versioned artifact** — a stale/foreign schema is REFUSED by
  ``load_calibration`` and by ``price_schedule(calibration=)``; a
  valid artifact changes pricing and re-ranks ``compile_verified``.
* **Critical path** — the longest start/done + program-order chain is
  named with its dominant link/op, and the overlap fraction
  (wire hidden behind other work / total wire) matches hand math.
* **Gates** — ``calibrate.main`` keeps the 0/1/2 contract (0 ok or
  gate-skip, 1 drift, 2 unusable/stale) and the ``calibration`` stage
  rides ``python -m chainermn_tpu.analysis --gate``.
"""

import json
import os
import subprocess
import sys

import pytest

from chainermn_tpu.analysis import calibrate as C
from chainermn_tpu.analysis import schedule as S
from chainermn_tpu.analysis import schedule_check as SC
from chainermn_tpu.analysis.schedule import (
    CALIBRATION_SCHEMA,
    CostModel,
    Topology,
    calibrated_cost_model,
    price_schedule,
)
from chainermn_tpu.analysis.schedule_check import (
    SCHEDULE_EXEC_SCHEMA,
    ScheduleExecProfile,
    execute_profiled,
)

pytestmark = pytest.mark.lint

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _rec(op, arg, link, nbytes, wall_us, rank=0, run="run0", seq=0):
    return {"schema": SCHEDULE_EXEC_SCHEMA, "fingerprint": "f" * 16,
            "schedule": "synthetic", "sched_kind": "chunked",
            "run": run, "seq": seq, "op": op, "arg": arg, "rank": rank,
            "link": link, "bytes": int(nbytes), "t_us": 0.0,
            "wall_us": float(wall_us)}


def _wire_records(link, alpha_s, bw, sizes, run="run0"):
    """One start+done pair per size, walls generated EXACTLY from
    wall = alpha + bytes/bw (start carries it all, done is free)."""
    recs = []
    for i, b in enumerate(sizes):
        w_us = (alpha_s + b / bw) * 1e6
        recs.append(_rec("start", f"t_{link}_{i}", link, b, w_us,
                         rank=0, run=run, seq=2 * i))
        recs.append(_rec("done", f"t_{link}_{i}", link, b, 0.0,
                         rank=1, run=run, seq=2 * i + 1))
    return recs


SIZES = [1 << 10, 1 << 12, 1 << 14, 1 << 16, 1 << 18]
TRUE = {"ici": (2e-6, 8e9), "dcn": (30e-6, 1.5e9), "copy": (1e-6, 20e9)}


def _synthetic_records():
    recs = []
    recs += _wire_records("ici", *TRUE["ici"], SIZES)
    recs += _wire_records("dcn", *TRUE["dcn"], SIZES)
    for i, b in enumerate(SIZES):
        alpha, bw = TRUE["copy"]
        recs.append(_rec("copy", f"c{i}", "copy", b,
                         (alpha + b / bw) * 1e6, seq=100 + i))
    return recs


# ==========================================================================
# the least-squares fit
# ==========================================================================

class TestFit:
    def test_recovers_known_constants(self):
        cal = C.fit_calibration(_synthetic_records())
        assert cal["schema"] == CALIBRATION_SCHEMA
        for link, (alpha, bw) in TRUE.items():
            fit = cal["links"][link]
            assert fit["alpha_s"] == pytest.approx(alpha, rel=0.05)
            assert fit["bw"] == pytest.approx(bw, rel=0.05)
            assert fit["residual_rel"] < 1e-6  # noiseless input
            assert fit["n"] == len(SIZES)

    def test_fit_is_deterministic(self):
        recs = _synthetic_records()
        a = json.dumps(C.fit_calibration(recs), sort_keys=True)
        b = json.dumps(C.fit_calibration(list(recs)), sort_keys=True)
        assert a == b

    def test_uniform_sizes_fall_back_to_pure_bandwidth(self):
        recs = _wire_records("ici", 0.0, 4e9, [1 << 16] * 4)
        fit = C.fit_calibration(recs)["links"]["ici"]
        assert fit["alpha_s"] == 0.0
        assert fit["bw"] == pytest.approx(4e9, rel=1e-6)

    def test_unpaired_start_contributes_nothing(self):
        recs = _wire_records("dcn", *TRUE["dcn"], SIZES)
        recs.append(_rec("start", "torn", "dcn", 1 << 20, 999.0,
                         seq=999))  # done never recorded: torn run
        samples = C.transfer_samples(recs)
        assert len(samples["dcn"]) == len(SIZES)


# ==========================================================================
# record ingestion (journal tee + torn tails)
# ==========================================================================

class TestIngestion:
    def test_torn_partial_and_foreign_lines_are_dropped(self, tmp_path):
        good = _synthetic_records()
        path = tmp_path / "records.jsonl"
        lines = [json.dumps(r) for r in good]
        lines.insert(3, json.dumps({"schema": "foreign.v9", "op": "x",
                                    "link": "ici", "bytes": 1,
                                    "wall_us": 1.0}))
        partial = dict(good[0])
        del partial["wall_us"]
        lines.insert(5, json.dumps(partial))
        lines.append('{"schema": "chainermn_tpu.schedule_exec')  # torn
        path.write_text("\n".join(lines) + "\n")
        recs = C.read_exec_records(str(path))
        assert len(recs) == len(good)
        assert C.fit_calibration(recs)["links"].keys() == \
            C.fit_calibration(good)["links"].keys()

    def test_journal_enveloped_records_unwrap(self, tmp_path):
        raw = _synthetic_records()
        path = tmp_path / "journal.w0.jsonl"
        with path.open("w") as f:
            for r in raw:
                env = {k: v for k, v in r.items() if k != "schema"}
                env.update({"schema": "chainermn_tpu.journal.v1",
                            "kind": "schedule_exec", "hlc": [1, 0],
                            "proc": "w0"})
                f.write(json.dumps(env) + "\n")
            # a journal line of another kind is not ours
            f.write(json.dumps({"schema": "chainermn_tpu.journal.v1",
                                "kind": "beat", "hlc": [2, 0]}) + "\n")
        recs = C.read_exec_records(str(tmp_path))
        assert len(recs) == len(raw)
        assert json.dumps(C.fit_calibration(recs)["links"],
                          sort_keys=True) == \
            json.dumps(C.fit_calibration(raw)["links"], sort_keys=True)


# ==========================================================================
# versioned artifact + calibrated pricing
# ==========================================================================

class TestArtifact:
    def test_save_load_round_trip(self, tmp_path):
        cal = C.fit_calibration(_synthetic_records())
        out = tmp_path / "calibration.json"
        C.save_calibration(cal, str(out))
        assert C.load_calibration(str(out)) == cal

    def test_stale_schema_is_refused(self, tmp_path):
        cal = C.fit_calibration(_synthetic_records())
        cal["schema"] = "chainermn_tpu.calibration.v0"
        out = tmp_path / "stale.json"
        C.save_calibration(cal, str(out))
        with pytest.raises(ValueError, match="stale/foreign"):
            C.load_calibration(str(out))
        with pytest.raises(ValueError, match="stale/foreign"):
            calibrated_cost_model(cal)
        sched = SC.verified_schedule("chunked", (24, 4), "float32",
                                     0, 0, 4, 2, Topology(2, 2))
        with pytest.raises(ValueError, match="stale/foreign"):
            price_schedule(sched, calibration=cal)

    def test_calibrated_model_substitutes_fitted_constants(self):
        cal = C.fit_calibration(_synthetic_records())
        cm = calibrated_cost_model(cal)
        stock = CostModel()
        assert cm.bw("ici") == pytest.approx(TRUE["ici"][1], rel=0.05)
        assert cm.alpha("dcn") == pytest.approx(TRUE["dcn"][0],
                                                rel=0.05)
        assert cm.bw("ici") != stock.bw("ici")
        # links absent from the artifact keep the stock constants
        partial = dict(cal)
        partial["links"] = {"ici": cal["links"]["ici"]}
        cm2 = calibrated_cost_model(partial)
        assert cm2.bw("dcn") == stock.bw("dcn")
        assert cm2.alpha("dcn") == stock.alpha("dcn")

    def test_calibration_changes_pricing_and_reranking(self):
        cal = C.fit_calibration(_synthetic_records())
        sched = SC.verified_schedule("hierarchical", (24, 4),
                                     "float32", 0, None, 4, 4,
                                     Topology(2, 2))
        stock_row = price_schedule(sched)
        cal_row = price_schedule(sched, calibration=cal)
        assert cal_row["wall_us"] != stock_row["wall_us"]
        # compile_verified accepts the artifact and re-prices the
        # candidate table with it (cache-keyed by calibration identity)
        _, rep_stock = SC.compile_verified((24, 4), "float32", 0, None,
                                           4, 4, Topology(2, 2))
        _, rep_cal = SC.compile_verified((24, 4), "float32", 0, None,
                                         4, 4, Topology(2, 2),
                                         calibration=cal)
        assert rep_cal["cost_ms"] != rep_stock["cost_ms"]


# ==========================================================================
# profiler truth: reconciliation + byte-exactness under profiling
# ==========================================================================

class TestProfiler:
    def test_profiled_execution_reconciles_and_matches(self):
        import numpy as np
        sched, _ = SC.compile_verified((24, 4), "float32", 0, None,
                                       4, 4, Topology(2, 2))
        outs, prof = execute_profiled(sched, reps=2)
        assert prof.runs() and len(prof.runs()) == 2
        for run in prof.runs():
            assert prof.reconcile(run) == []
            measured = prof.measured_wire_bytes(run)
            assert measured == sched.wire_bytes()
        # profiling must not perturb the data path
        plain = SC.run_schedule(sched, SC.make_input_blocks(sched))
        assert all(np.array_equal(a, b) for a, b in zip(outs, plain))

    def test_every_fleet_pair_reconciles_exactly(self):
        for name, src, dst, sw, dw in SC.FLEET_PAIRS:
            topo = SC.fleet_pair_topology(sw, dw)
            sched, _ = SC.compile_verified((24, 4), "float32", src,
                                           dst, sw, dw, topo)
            _, prof = execute_profiled(sched)
            assert prof.reconcile() == [], name
            assert prof.measured_wire_bytes() == sched.wire_bytes(), \
                name

    def test_record_shape_and_run_ids(self):
        sched = SC.verified_schedule("chunked", (24, 4), "float32",
                                     0, 0, 4, 2, Topology(2, 2))
        _, prof = execute_profiled(sched, reps=2)
        r = prof.records[0]
        assert r["schema"] == SCHEDULE_EXEC_SCHEMA
        assert r["fingerprint"] == sched.fingerprint()
        for field in ("run", "seq", "op", "arg", "rank", "link",
                      "bytes", "t_us", "wall_us"):
            assert field in r
        assert len({rec["run"] for rec in prof.records}) == 2

    def test_on_op_cost_is_bounded(self):
        # pin the per-record cost to an order of magnitude that cannot
        # dominate ms-scale transfers.
        import time
        sched = SC.verified_schedule("chunked", (24, 4), "float32",
                                     0, 0, 4, 2, Topology(2, 2))
        prof = ScheduleExecProfile(sched)
        op = next(op for r in sorted(sched.programs)
                  for op in sched.programs[r])
        t0 = time.perf_counter()
        for _ in range(2000):
            tb = prof.now_ns()
            prof.on_op(op, 0, tb, prof.now_ns())
        per_record = (time.perf_counter() - t0) / 2000
        assert per_record < 50e-6  # generous CI bound


# ==========================================================================
# critical path + overlap attribution
# ==========================================================================

class TestCriticalPath:
    def test_hand_built_chain_and_dominants(self):
        recs = [
            _rec("copy", "c0", "copy", 64, 10.0, rank=0, seq=0),
            _rec("start", "t0", "ici", 64, 5.0, rank=0, seq=1),
            _rec("done", "t0", "ici", 64, 20.0, rank=1, seq=2),
            _rec("copy", "c1", "copy", 64, 1.0, rank=1, seq=3),
        ]
        cp = C.schedule_critical_path(recs)
        assert cp["critical_path_us"] == pytest.approx(36.0)
        assert cp["chain"] == ["r0.copy(c0)[copy]", "r0.start(t0)[ici]",
                               "r1.done(t0)[ici]", "r1.copy(c1)[copy]"]
        assert cp["dominant_link"] == "ici"
        assert "r1.done(t0)[ici] 20.0us" == cp["dominant_op"]
        # every wire microsecond sits on the chain: nothing hidden
        assert cp["wire_total_us"] == pytest.approx(25.0)
        assert cp["wire_exposed_frac"] == pytest.approx(1.0)
        assert cp["overlap_frac"] == pytest.approx(0.0)

    def test_overlap_fraction_counts_hidden_wire(self):
        # r0's long copy hides the done landing on r1: of 10us wire,
        # only the start's 5us is exposed on the critical path.
        recs = [
            _rec("start", "t0", "dcn", 64, 5.0, rank=0, seq=0),
            _rec("copy", "c0", "copy", 64, 50.0, rank=0, seq=1),
            _rec("done", "t0", "dcn", 64, 5.0, rank=1, seq=2),
        ]
        cp = C.schedule_critical_path(recs)
        assert cp["critical_path_us"] == pytest.approx(55.0)
        assert cp["wire_total_us"] == pytest.approx(10.0)
        assert cp["wire_hidden_us"] == pytest.approx(5.0)
        assert cp["overlap_frac"] == pytest.approx(0.5)
        assert cp["wire_exposed_frac"] == pytest.approx(0.5)

    def test_last_run_is_attributed(self):
        recs = [_rec("copy", "c0", "copy", 64, 99.0, run="old"),
                _rec("copy", "c0", "copy", 64, 7.0, run="new")]
        cp = C.schedule_critical_path(recs)
        assert cp["run"] == "new"
        assert cp["critical_path_us"] == pytest.approx(7.0)

    def test_executed_schedule_names_a_dominant_segment(self):
        sched, _ = SC.compile_verified((24, 4), "float32", 0, None,
                                       4, 4, Topology(2, 2))
        _, prof = execute_profiled(sched)
        cp = C.schedule_critical_path(prof.records)
        assert cp["n_ops"] == len(prof.run_records())
        assert cp["dominant_link"] in ("ici", "dcn", "copy")
        assert cp["dominant_op"] and cp["chain"]
        assert 0.0 <= cp["overlap_frac"] <= 1.0
        assert cp["overlap_frac"] + cp["wire_exposed_frac"] == \
            pytest.approx(1.0)


# ==========================================================================
# drift gate + CLIs (the 0/1/2 contract)
# ==========================================================================

class TestGates:
    def test_drift_report_ok_on_self_fit(self):
        recs = _synthetic_records()
        rep = C.drift_report(recs, C.fit_calibration(recs))
        assert rep["ok"] and rep["median_rel_err"] < 1e-6
        assert set(rep["links"]) == {"ici", "dcn"}

    def test_drift_report_flags_rotten_artifact(self):
        recs = _synthetic_records()
        cal = C.fit_calibration(recs)
        for link in ("ici", "dcn"):        # a much faster machine:
            cal["links"][link]["bw"] *= 1e3    # predictions collapse
            cal["links"][link]["alpha_s"] = 0.0
        rep = C.drift_report(recs, cal)
        assert not rep["ok"]
        assert rep["median_rel_err"] > rep["threshold"]

    def test_cli_exit_contract(self, tmp_path, monkeypatch):
        monkeypatch.chdir(tmp_path)
        monkeypatch.delenv("CHAINERMN_SCHEDULE_EXEC_RECORDS",
                           raising=False)
        monkeypatch.delenv("CHAINERMN_CALIBRATION", raising=False)
        # 0: gate mode with nothing measured yet (the skip)
        assert C.main(["--gate"]) == 0
        # 2: non-gate mode with nothing to fit
        assert C.main([]) == 2
        recs = tmp_path / "records.jsonl"
        recs.write_text("\n".join(json.dumps(r)
                                  for r in _synthetic_records()) + "\n")
        # 0: fresh fit checks itself, artifact persisted
        out = tmp_path / "calibration.json"
        assert C.main([str(recs), "--fit-out", str(out),
                       "--gate"]) == 0
        assert C.load_calibration(str(out))["links"]
        # 1: drift against a rotten artifact
        cal = C.load_calibration(str(out))
        for link in ("ici", "dcn"):
            cal["links"][link]["bw"] *= 1e3
            cal["links"][link]["alpha_s"] = 0.0
        rotten = tmp_path / "rotten.json"
        C.save_calibration(cal, str(rotten))
        assert C.main([str(recs), "--calibration", str(rotten),
                       "--gate"]) == 1
        # 2: stale schema artifact is unusable, not silently consumed
        cal["schema"] = "chainermn_tpu.calibration.v0"
        C.save_calibration(cal, str(rotten))
        assert C.main([str(recs), "--calibration", str(rotten)]) == 2

    def test_gate_stage_rides_analysis_gate(self, tmp_path,
                                            monkeypatch):
        from chainermn_tpu.analysis.cli import GATE_STAGES, gate_main
        assert "calibration" in GATE_STAGES
        monkeypatch.chdir(tmp_path)
        monkeypatch.delenv("CHAINERMN_SCHEDULE_EXEC_RECORDS",
                           raising=False)
        monkeypatch.delenv("CHAINERMN_CALIBRATION", raising=False)
        assert gate_main(["--stages", "calibration"]) == 0

    def test_check_schedules_measure_cli(self, tmp_path):
        out = tmp_path / "calibration.json"
        r = subprocess.run(
            [sys.executable,
             os.path.join(REPO, "scripts", "check_schedules.py"),
             "--measure", "--reps", "2", "--skip-fault-corpus",
             "--calibration-out", str(out)],
            capture_output=True, text=True, timeout=300)
        assert r.returncode == 0, r.stderr
        verdict = json.loads(r.stdout)
        assert verdict["checks"]["reconciled"] is True
        assert verdict["measured"]["reconcile_violations"] == []
        assert verdict["measured"]["calibration"]
        pair = verdict["pairs"]["rolling_upgrade_fanout"]
        assert "rel_err_calibrated" in pair["measured"]
        assert C.load_calibration(str(out))["n_records"] == \
            verdict["measured"]["n_records"]
