"""The DeepSeek-V3 family end to end through ``main(_allow_cpu=...)`` at a
tiny size (CPU: the einsum and dense-loop paths; the kernels have their own
parity tests under ``tests/``), its control in lower precision, a broken
path, and the new readers on hand-made inputs."""

import json
import time

import pytest

from benchmark import control, run
from benchmark.harness import loader

CELL = "deepseek-v3-ep16-serve-steady"
TINY_DS = {"hidden_size": 64, "num_hidden_layers": 3,
           "first_k_dense_replace": 1, "num_attention_heads": 4,
           "q_lora_rank": 24, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
           "qk_rope_head_dim": 8, "v_head_dim": 16, "intermediate_size": 96,
           "moe_intermediate_size": 32, "n_routed_experts": 16,
           "n_routed_experts_held": 4, "n_group": 4, "topk_group": 2,
           "num_experts_per_tok": 4, "vocab_size": 200,
           "rope_scaling": {"original_max_position_embeddings": 16}}
TINY_TRAFFIC = {"rate_per_s": 8.0,
                "prompt_len": {"median": 12, "sigma": 0.8, "min": 4,
                               "max": 40},
                "output_len": {"median": 6, "sigma": 0.6, "min": 2,
                               "max": 16},
                "max_total": 64,
                "engine": {"n_slots": 4, "max_total": 64,
                           "prefill_bucket": 16, "queue_capacity": 16},
                "warm_prompts": [10, 20, 40], "check_requests": 4,
                "trace_seconds": 0.5, "min_tail_samples": 0}
SIZES = {"config": TINY_DS, "traffic": TINY_TRAFFIC}
# a mean gap and a share of flipped routes scale with the model: the tiny
# one (float32-accumulating bf16 on the CPU) reads 4e-4 .. 3.2e-3 and 0.014
# .. 0.021 for the program, 0.044 .. 0.053 and 0.24 .. 0.26 for the fp8
# control (three seeds each)
TINY_LIMITS = {"served_logit_gap": 0.012, "route_disagreement": 0.08}


@pytest.fixture
def tiny_limits(monkeypatch):
    """The tiny model's limits; yields the rows its comparison made."""
    real, rows = loader.module, []

    def module(kind, name):
        mod = real(kind, name)
        if (kind, name) == ("families", "deepseek_v3"):
            mod.ref.LIMITS.update(TINY_LIMITS)
            compare = mod.serve_compare

            def keeping(*args, **kw):
                out = compare(*args, **kw)
                rows.extend(out)
                return out

            mod.serve_compare = keeping
        return mod

    monkeypatch.setattr(loader, "module", module)
    return rows


def _run(capsys, trace=0, seconds=2, seed=3_000_000_019):
    rc = run.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                   str(seconds), "--trace", str(trace)], _allow_cpu=True,
                  _sizes=SIZES, _t0=time.perf_counter())
    lines = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    return json.loads(lines[-1]), lines


@pytest.mark.parametrize("trace", [0, 1])
def test_cell_runs_and_prints_the_contracts_last_line(capsys, tiny_limits,
                                                      trace):
    out, lines = _run(capsys, trace)
    assert out["correct"] is True and out["failed"] == 0, lines
    assert out["attempted"] > 0 and out["device"]["platform"] == "cpu"
    man = loader.manifest()
    known = {m["name"]: m["unit"]
             for m in man["per_layer" if trace else "end_to_end"]}
    for name, m in out["metrics"].items():
        assert m["unit"] == known[name] and isinstance(m["value"], float)
    if trace:
        # the counters' readers find their counters (the device-trace ones
        # find no TPU kernel on the CPU and leave their metric out)
        assert 0.0 < out["metrics"]["moe_held_share"]["value"] < 100.0
        assert out["metrics"]["moe_expert_load_max_over_mean"]["value"] >= 1.0
        assert "tick_cache_read_share" in out["metrics"]
    else:
        assert {"setup_s", "serve_tokens_per_s", "gap_p95_ms"} <= set(
            out["metrics"])


def test_a_token_altered_where_it_is_produced_is_not_correct(
        capsys, monkeypatch, tiny_limits):
    from chainermn_tpu.serving import frontend

    emit = frontend.ServingEngine._emit

    def wrong(self, req, token, now):
        emit(self, req, (token + 1) % 200 if len(req.tokens) % 3 == 2
             else token, now)

    monkeypatch.setattr(frontend.ServingEngine, "_emit", wrong)
    out, _ = _run(capsys)
    assert out["correct"] is False


def test_routes_altered_where_they_are_read_back_are_not_correct(
        capsys, monkeypatch, tiny_limits):
    """The routes compared are the window's own: what the timed prefills
    and ticks read back beside each token, not a second pass's."""
    from chainermn_tpu.serving import frontend

    keep = frontend.ServingEngine._keep_routes

    def wrong(self, req, routes):
        keep(self, req, (routes + 1) % 16 if len(req.tokens) % 3 == 2
             else routes)

    monkeypatch.setattr(frontend.ServingEngine, "_keep_routes", wrong)
    out, _ = _run(capsys)
    assert out["correct"] is False
    assert {r["name"]: r["ok"] for r in tiny_limits} == {
        "route_disagreement": False, "served_logit_gap": True,
        "argmax_disagreement": True}


def test_the_control_in_lower_precision_fails_a_limit(capsys, tiny_limits):
    sizes = loader.merge(SIZES, {"traffic": {"check_requests": 64}})
    rc = control.main(["--workload", CELL, "--seeds", "5,6,7", "--seconds",
                       "6"], _allow_cpu=True, _sizes=sizes)
    lines = capsys.readouterr().out.strip().splitlines()
    assert rc == 0, lines[-1]          # no control passed
    assert json.loads(lines[-1])["controls_that_passed"] == 0
    for line in lines[:-1]:
        if "] control {" in line:
            rows = json.loads(line.split("] control ", 1)[1])["rows"]
            for r in rows:           # ... while the program itself does
                if r["name"].startswith("program."):
                    assert r["ok"], r


def test_serve_kernel_costs_from_counters():
    """One tick: 30 held assignments on 14 experts, 10,000 live rows."""
    from benchmark.harness import serve_kernel_costs as costs

    cfg = loader.cell(loader.manifest(), CELL)["config"]
    run_ = {"engine_metrics": {
        "serving/tick_calls": 10.0,
        "serving/moe_tick_experts_hit": 140.0,
        "serving/moe_tick_assignments_held": 300.0,
        "serving/tick_cache_rows_live": 100000.0}}
    per_expert = 3 * 7168 * 2048
    assert costs.moe_gmm(cfg, run_) == {
        "flops": 30 * 2 * per_expert, "bytes": 14 * per_expert * 2}
    mla = costs.decode_attn_mla(cfg, run_)
    assert mla["bytes"] == 5 * 10000 * 1152
    assert mla["flops"] == 5 * 10000 * 2 * 128 * 1088
    # about the v5e's ridge, as ISSUE 27 reckons (242 FLOP/B)
    assert 240 < mla["flops"] / mla["bytes"] < 243
    assert costs.moe_gmm(cfg, {"engine_metrics": {}}) is None
    assert costs.seconds_per_tick({"window_s": 1.0}, "moe_gmm") is None


def test_the_two_copies_of_the_reference_are_one_text():
    import os

    a = os.path.join(loader.BENCH, "reference", "deepseek_v3.py")
    b = os.path.join(loader.ROOT, "tests", "deepseek_v3_reference.py")
    assert open(a).read() == open(b).read()
