"""Collective schedule IR + exhaustive static verifier (ISSUE 19,
``analysis/schedule.py`` + ``analysis/schedule_check.py``).

Contracts under test:

* **IR as artifact** — JSON round-trip is fingerprint-stable, the
  ``send``/``recv`` aliases parse, ``reduce`` is parsed but REFUSED by
  the verifier (reserved for the allreduce plane), junk is rejected.
* **Statics oracle** — ``expected_flow`` agrees with the same
  ``np.array_split`` block math ``reshard_host`` uses, so the coverage
  proof and the runtime can never disagree about where a byte lives.
* **Verifier** — every generator's candidate passes all three proofs;
  the checked-in fixture corpus (``tests/fixtures/schedules/``) pins
  the seeded-fault classes at 0 false negatives / 0 false positives
  with REPLAYABLE minimal counterexamples.
* **Fleet matrix** — every (src,dst) spec pair reachable from elastic
  resume / live shrink / rolling upgrade compiles to a verified
  schedule; on the ICI+DCN fan-out pair the hierarchically staged
  candidate beats the single-collective baseline on the r04 cost model.
* **Runtime swap** — ``reshard_host(..., schedule=)`` is byte-exact
  against the direct path for every kind, and the gate CLIs keep the
  0/1/2 exit contract.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from chainermn_tpu.analysis import schedule as S
from chainermn_tpu.analysis import schedule_check as SC
from chainermn_tpu.analysis.schedule import (
    Op,
    Schedule,
    Topology,
    block_global_indices,
    candidate_schedules,
    expected_flow,
    lower_hierarchical,
    price_schedule,
)

pytestmark = pytest.mark.lint

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures",
                        "schedules")
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SHAPE, DTYPE = (24, 4), "float32"
TOPO22 = Topology(2, 2)


def _hier():
    return lower_hierarchical(SHAPE, DTYPE, 0, None, 4, 4, TOPO22,
                              n_chunks=2)


# ==========================================================================
# the IR as a compiled, checkable artifact
# ==========================================================================

class TestScheduleIR:
    @pytest.mark.parametrize("kind", sorted(S.GENERATORS))
    def test_json_round_trip_is_fingerprint_stable(self, kind):
        sched = SC.verified_schedule(kind, SHAPE, DTYPE, 0, 0, 4, 2,
                                     TOPO22)
        doc = json.loads(json.dumps(sched.to_json()))  # wire trip
        back = Schedule.from_json(doc)
        assert back.fingerprint() == sched.fingerprint()
        assert back.stats() == sched.stats()

    def test_send_recv_aliases_parse_to_start_done(self):
        doc = _hier().to_json()
        for prog in doc["programs"].values():
            for op in prog:
                op[0] = {"start": "send", "done": "recv"}.get(op[0],
                                                              op[0])
        back = Schedule.from_json(doc)
        kinds = {op.kind for prog in back.programs.values()
                 for op in prog}
        assert "send" not in kinds and "recv" not in kinds
        assert SC.verify_schedule(back).ok

    def test_reduce_is_parsed_but_refused_as_reserved(self):
        doc = _hier().to_json()
        chunk = doc["chunks"][0]["name"]
        doc["programs"]["0"].append(["reduce", chunk])
        back = Schedule.from_json(doc)   # parse side accepts it...
        res = SC.verify_schedule(back)   # ...the verifier refuses
        assert not res.ok
        assert any("reserved" in v for v in res.violations)

    def test_unknown_op_kind_rejected_at_parse(self):
        doc = _hier().to_json()
        doc["programs"]["0"].append(["teleport", "c0"])
        with pytest.raises(ValueError, match="unknown op kind"):
            Schedule.from_json(doc)

    def test_wrong_schema_rejected(self):
        with pytest.raises(ValueError, match="schema"):
            Schedule.from_json({"schema": "something.else.v9"})


# ==========================================================================
# statics oracle: expected_flow vs the array_split block math
# ==========================================================================

class TestExpectedFlow:
    @pytest.mark.parametrize("src,dst,sw,dw", [
        (0, 0, 4, 2), (0, 0, 2, 4), (0, None, 4, 1), (None, 0, 1, 4),
        (0, 1, 2, 2), (None, None, 4, 2),
    ])
    def test_flows_reconcile_with_global_indices(self, src, dst, sw,
                                                 dw):
        flows = expected_flow(SHAPE, src, dst, sw, dw)
        gsrc = {s: block_global_indices(SHAPE, src, s, sw)
                for s in range(sw)}
        gdst = {d: block_global_indices(SHAPE, dst, d, dw)
                for d in range(dw)}
        covered = {d: np.zeros(len(gdst[d]), dtype=int)
                   for d in range(dw)}
        for (s, d), segs in flows.items():
            for so, do, n in segs:
                assert np.array_equal(gsrc[s][so:so + n],
                                      gdst[d][do:do + n]), (s, d)
                covered[d][do:do + n] += 1
        for d in range(dw):
            assert (covered[d] == 1).all(), f"dst {d} not exactly-once"

    def test_replicated_source_uses_the_local_copy_policy(self):
        # replicated -> anything must be zero-wire where a local copy
        # exists: source rank is d (or d % src_world) by construction,
        # matching reshard_host's "shard 0 bit-for-bit" lowering
        flows = expected_flow(SHAPE, None, 0, 4, 2)
        assert set(flows) == {(0, 0), (1, 1)}
        flows = expected_flow(SHAPE, None, None, 2, 4)
        assert set(flows) == {(0, 0), (1, 1), (0, 2), (1, 3)}


# ==========================================================================
# the verifier: three proofs + the seeded-fault fixture corpus
# ==========================================================================

class TestVerifier:
    def test_all_candidates_verify_on_a_hierarchical_pair(self):
        for sched in candidate_schedules(SHAPE, DTYPE, 0, None, 4, 4,
                                         TOPO22, n_chunks=2, depth=2):
            res = SC.verify_schedule(sched)
            assert res.ok, res.render()
            assert res.complete and res.n_states > 10
            assert res.phases == {"structural": "ok", "coverage": "ok",
                                  "model": "ok", "interpreter": "ok"}

    def test_interpreter_byte_exact_on_random_base(self):
        sched = _hier()
        rng = np.random.RandomState(7)
        base = rng.randn(*SHAPE).astype(DTYPE)
        got = SC.run_schedule(sched, SC.make_input_blocks(sched, base))
        want = SC.expected_output_blocks(sched, base)
        for d in range(sched.dst_world):
            assert np.array_equal(got[d], want[d]), f"dst {d}"

    def test_truncated_model_check_is_a_violation_not_a_pass(self):
        res = SC.verify_schedule(_hier(), max_states=5)
        assert not res.ok
        assert any("truncated" in v for v in res.violations)


#: fault class -> (verifier phase that must catch it, message needle).
FAULT_PHASES = {
    "dropped_chunk": ("coverage", "never written"),
    "double_write": ("coverage", "more than once"),
    "send_recv_cycle": ("model", "deadlock"),
    "done_before_start": ("model", "fence"),
    "buffer_overrun": ("model", "buffer"),
}


class TestSeededFaultCorpus:
    def _files(self, prefix):
        return sorted(f for f in os.listdir(FIXTURES)
                      if f.startswith(prefix) and f.endswith(".json"))

    def _load(self, fname):
        with open(os.path.join(FIXTURES, fname)) as f:
            return Schedule.from_json(json.load(f))

    def test_corpus_is_big_enough(self):
        assert len(self._files("clean_")) >= 3
        faults = self._files("fault_")
        assert len(faults) == len(FAULT_PHASES)
        for fault in FAULT_PHASES:
            assert any(f.startswith(f"fault_{fault}") for f in faults)

    def test_clean_fixtures_all_pass(self):        # 0 false positives
        for fname in self._files("clean_"):
            res = SC.verify_schedule(self._load(fname))
            assert res.ok, f"{fname}: {res.render()}"

    def test_fault_fixtures_all_caught_in_their_phase(self):  # 0 FN
        for fname in self._files("fault_"):
            fault = next(k for k in FAULT_PHASES
                         if fname.startswith(f"fault_{k}"))
            phase, needle = FAULT_PHASES[fault]
            res = SC.verify_schedule(self._load(fname))
            assert not res.ok, f"{fname} escaped the verifier"
            assert res.phases[phase] == "violated", (fname, res.phases)
            assert any(needle in v for v in res.violations), \
                (fname, res.violations)
            if phase == "model":
                assert res.counterexample, fname

    def test_model_counterexamples_are_minimal_and_replayable(self):
        # BFS guarantees shortest traces; the checked-in fixtures pin
        # the exact minimal lengths so a checker regression that finds
        # only LONGER (or no) paths fails loudly.  Each trace must also
        # replay: every named transition enabled in order from the
        # initial state, ending in a violated state.
        minimal = {"send_recv_cycle": 20, "done_before_start": 13,
                   "buffer_overrun": 30}
        for fault, want_len in minimal.items():
            (fname,) = [f for f in self._files(f"fault_{fault}")]
            sched = self._load(fname)
            res = SC.verify_schedule(sched)
            assert len(res.counterexample) == want_len, fname
            model = SC.make_schedule_model(sched)
            by_name = {t.name: t for t in model.transitions}
            s = model.initial
            for tname in res.counterexample:
                t = by_name[tname]
                assert t.guard(s), f"{fname}: {tname} not enabled"
                s = t.apply(s)
            assert (model.invariant(s) is not None
                    or model.terminal_invariant(s) is not None), fname

    def test_fresh_mutators_match_the_corpus(self):
        # regenerate the corpus logic live: every expressible fault on
        # the hierarchical and flat chunked schedules is caught
        for base in (_hier(),
                     S.lower_chunked(SHAPE, DTYPE, 0, None, 4, 4,
                                     TOPO22, n_chunks=2)):
            expressible = 0
            for fault in SC.SEEDED_FAULTS:
                try:
                    bad = SC.seed_fault(base, fault)
                except ValueError:
                    continue
                expressible += 1
                assert not SC.verify_schedule(bad).ok, \
                    f"{base.kind}+{fault} escaped"
            assert expressible >= 4

    def test_unknown_fault_name_rejected(self):
        with pytest.raises(KeyError):
            SC.seed_fault(_hier(), "gamma_ray")


# ==========================================================================
# the fleet matrix + the cost-model win
# ==========================================================================

class TestFleetPairs:
    @pytest.mark.parametrize(
        "name,src,dst,sw,dw",
        SC.FLEET_PAIRS, ids=[p[0] for p in SC.FLEET_PAIRS])
    def test_every_fleet_pair_compiles_verified(self, name, src, dst,
                                                sw, dw):
        topo = SC.fleet_pair_topology(sw, dw)
        # compile_verified raises if ANY candidate fails verification
        sched, report = SC.compile_verified(SHAPE, DTYPE, src, dst,
                                            sw, dw, topo)
        assert report["speedup_vs_single"] >= 1.0
        assert report["cost_ms"] > 0
        assert len(report["candidates"]) >= 2

    def test_hierarchical_beats_single_on_the_fanout_pair(self):
        # the ICI+DCN acceptance pair: gateway staging halves the DCN
        # egress per source rank, so the staged candidate must win on
        # the r04 cost model and be the one compile_verified chooses
        sched, report = SC.compile_verified(
            SHAPE, DTYPE, 0, None, 4, 4, SC.fleet_pair_topology(4, 4))
        assert report["kind"] == "hierarchical"
        assert report["speedup_vs_single"] > 1.0
        single = report["candidates"][0]
        assert single["kind"] == "single"
        assert report["dcn_bytes"] < single["dcn_bytes"]

    def test_price_schedule_orders_links_sanely(self):
        # the same all-to-all over DCN must cost more than over ICI
        a = price_schedule(S.lower_single(SHAPE, DTYPE, 0, 1, 4, 4,
                                          Topology.flat(4)))
        b = price_schedule(S.lower_single(SHAPE, DTYPE, 0, 1, 4, 4,
                                          Topology(4, 1)))
        assert a["ici_bytes"] == b["dcn_bytes"] > 0
        assert b["cost_ms"] > a["cost_ms"]


# ==========================================================================
# reshard_host swaps schedules with token-exact results
# ==========================================================================

class TestReshardIntegration:
    def _shards(self, sw, seed=0):
        rng = np.random.RandomState(seed)
        full = {"w": rng.randn(*SHAPE).astype(np.float32),
                "b": rng.randn(SHAPE[0]).astype(np.float32)}
        return [{"w": blk, "b": bb}
                for blk, bb in zip(np.array_split(full["w"], sw,
                                                  axis=0),
                                   np.array_split(full["b"], sw,
                                                  axis=0))], full

    @pytest.mark.parametrize("kind", ["auto", "single", "chunked",
                                      "pipelined", "hierarchical"])
    @pytest.mark.parametrize("sw,dw", [(4, 1), (4, 2), (2, 4)])
    def test_schedule_path_byte_exact_vs_direct(self, kind, sw, dw):
        from chainermn_tpu.parallel.reshard import reshard_host
        shards, _ = self._shards(sw)
        layout = {"w": 0, "b": 0}
        direct = reshard_host(shards, layout, layout, dw)
        via = reshard_host(shards, layout, layout, dw, schedule=kind)
        for d in range(dw):
            for k in ("w", "b"):
                assert np.array_equal(direct[d][k], via[d][k]), \
                    (kind, d, k)

    def test_replicated_leaves_keep_the_direct_path(self):
        # schedule= only reroutes sharded int-spec sources; replicated
        # leaves keep the shard-0-bit-for-bit contract either way
        from chainermn_tpu.parallel.reshard import reshard_host
        shards, _ = self._shards(2)
        reps = [{"r": np.full((3, 3), float(i))} for i in range(2)]
        out = reshard_host(reps, {"r": None}, {"r": None}, 4,
                           schedule="auto")
        for d in range(4):
            assert np.array_equal(out[d]["r"], reps[0]["r"])

    def test_lower_schedule_returns_verified_artifact(self):
        from chainermn_tpu.parallel.reshard import lower_schedule
        sched = lower_schedule(SHAPE, DTYPE, 0, 0, 4, 2,
                               kind="chunked", topology=TOPO22)
        assert isinstance(sched, Schedule)
        assert (sched.src_world, sched.dst_world) == (4, 2)
        assert SC.verify_schedule(sched).ok


# ==========================================================================
# gate CLIs: the 0/1/2 exit contract
# ==========================================================================

class TestGateCLI:
    def test_schedule_check_fleet_matrix_exits_zero(self, capsys):
        assert SC.main([]) == 0
        out = capsys.readouterr().out
        assert "rolling_upgrade_fanout" in out

    def test_artifact_violation_exits_one(self, capsys):
        bad = os.path.join(FIXTURES, "fault_dropped_chunk_hier.json")
        assert SC.main([bad]) == 1
        clean = os.path.join(FIXTURES, "clean_hierarchical.json")
        assert SC.main([clean]) == 0

    def test_unusable_artifact_exits_two(self, tmp_path, capsys):
        p = tmp_path / "junk.json"
        p.write_text("{not json")
        assert SC.main([str(p)]) == 2

    def test_analysis_gate_runs_the_schedule_stage(self, capsys):
        from chainermn_tpu.analysis import cli
        assert cli.gate_main(["--stages", "schedules"]) == 0
        cap = capsys.readouterr()
        assert "schedules=0" in cap.out + cap.err

    def test_check_schedules_script_end_to_end(self):
        proc = subprocess.run(
            [sys.executable,
             os.path.join(REPO, "scripts", "check_schedules.py")],
            capture_output=True, text=True, timeout=300)
        assert proc.returncode == 0, proc.stderr[-2000:]
        verdict = json.loads(proc.stdout)
        assert verdict["ok"] and verdict["checks"]["hierarchical_win"]
        assert verdict["fault_corpus"]["false_negatives"] == []
        assert verdict["hier_speedup"] > 1


# ==========================================================================
# schedule execution truth plane (ISSUE 20): reshard_host tees
# schedule_exec records into the journal, counters ride /metricsz, and
# the calibrate reader recovers the records for the fit
# ==========================================================================

class TestScheduleTruth:
    def test_reshard_emits_journal_records_and_counters(self, tmp_path):
        from chainermn_tpu.analysis import calibrate as CA
        from chainermn_tpu.observability import comm
        from chainermn_tpu.observability import journal as jr
        from chainermn_tpu.observability.introspect import StatusServer
        from chainermn_tpu.parallel.reshard import reshard_host
        comm.reset_schedule_exec()
        jr.reset()
        try:
            jr.configure(str(tmp_path), "w0")
            rng = np.random.RandomState(0)
            full = rng.randn(*SHAPE).astype(np.float32)
            shards = [{"w": blk}
                      for blk in np.array_split(full, 4, axis=0)]
            out = reshard_host(shards, {"w": 0}, {"w": 0}, 2,
                               schedule="auto")
            assert np.array_equal(np.concatenate(
                [o["w"] for o in out], axis=0), full)
            events = [e for e in jr.read_journal(jr.get_journal().path)
                      if e.get("kind") == "schedule_exec"]
            assert events, "no schedule_exec events journaled"
            for e in events:
                assert e["fingerprint"] and e["run"]
                assert e["link"] in ("ici", "dcn", "copy")
                assert e["op"] in ("copy", "start", "done", "unstage")
            # one run id spans the whole execution; starts balance dones
            assert len({e["run"] for e in events}) == 1
            assert (sum(1 for e in events if e["op"] == "start")
                    == sum(1 for e in events if e["op"] == "done"))
            # the calibrate reader unwraps the journal envelope
            recs = CA.read_exec_records(str(tmp_path))
            assert len(recs) == len(events)
            assert CA.fit_calibration(recs)["links"]
            # counters ride /metricsz (prometheus text face)
            gauges = comm.schedule_exec_gauges()
            assert gauges["schedule_exec/records"] == len(events)
            assert gauges["schedule_exec/executions"] == 1.0
            text = StatusServer().metricsz()
            assert "schedule_exec" in text
        finally:
            jr.reset()
            comm.reset_schedule_exec()

    def test_no_journal_no_profiler_overhead_path(self):
        # zero-overhead-off: without journal/trace enabled the reshard
        # path must not construct a profiler at all
        from chainermn_tpu.observability import journal as jr
        from chainermn_tpu.observability import trace as tr
        from chainermn_tpu.parallel.reshard import reshard_host
        assert not jr.enabled()
        assert not tr.get_tracer().enabled
        rng = np.random.RandomState(1)
        full = rng.randn(*SHAPE).astype(np.float32)
        shards = [{"w": blk}
                  for blk in np.array_split(full, 4, axis=0)]
        out = reshard_host(shards, {"w": 0}, {"w": 0}, 2,
                           schedule="auto")
        assert np.array_equal(np.concatenate(
            [o["w"] for o in out], axis=0), full)
