"""The Kimi Linear family end to end through ``main(_allow_cpu=...)`` at a
tiny size (CPU: the one-token step's plain twin, the chunked prefill, the
einsum and dense-loop paths; the kernels have their own parity tests under
``tests/``): the cell's last line, the control in lower precision, the two
broken-STATE programs (each must fail a limit of ``correct``), and the new
readers on hand-made inputs and on a GPT-2 run, where each is silent."""

import json
import os
import time

import pytest

from benchmark import control, run, state_control
from benchmark.harness import loader

CELL = "kimi-linear-ep16-serve-decode"
GPT2_CELL = "gpt2-medium-serve-steady"
NEW_READERS = ["kda_step_ms_per_tick", "kda_step_roofline_share",
               "hybrid_mla_decode_attn_roofline_share",
               "tick_state_bytes_share"]
# K K K M K K K M, the first layer's MLP dense: two whole periods
TINY_KIMI = {"hidden_size": 64, "num_hidden_layers": 8,
             "first_k_dense_replace": 1, "num_attention_heads": 4,
             "kv_lora_rank": 32, "qk_nope_head_dim": 16,
             "qk_rope_head_dim": 8, "v_head_dim": 16,
             "intermediate_size": 96, "moe_intermediate_size": 32,
             "num_experts": 16, "num_experts_held": 4,
             "num_experts_per_token": 4, "vocab_size": 200,
             "kda_gate_rank": 8,
             "linear_attn_config": {
                 "full_attn_layers": [4, 8],
                 "kda_layers": [1, 2, 3, 5, 6, 7],
                 "head_dim": 16, "num_heads": 4,
                 "short_conv_kernel_size": 4}}
TINY_TRAFFIC = {"rate_per_s": 8.0,
                "prompt_len": {"median": 12, "sigma": 0.8, "min": 4,
                               "max": 40},
                "output_len": {"median": 6, "sigma": 0.6, "min": 2,
                               "max": 16},
                "max_total": 64,
                "engine": {"n_slots": 4, "max_total": 64,
                           "prefill_bucket": 16, "queue_capacity": 16},
                "warm_prompts": [10, 20, 40], "check_requests": 16,
                "trace_seconds": 0.5, "min_tail_samples": 0}
SIZES = {"config": TINY_KIMI, "traffic": TINY_TRAFFIC}
# a mean gap and the shares of flipped routes and tokens scale with the
# model: the tiny one (float32-accumulating bf16 on the CPU, 8 layers)
# reads 0.0066 .. 0.019, 0.047 .. 0.074 and 0.039 .. 0.105 for the program,
# 0.16 .. 0.21, 0.44 .. 0.47 and 0.43 .. 0.50 for the fp8 control (four
# seeds, 332 served tokens each), 0.35 .. 0.49 / 0.53 .. 0.61 / 0.48 .. 0.58
# for a tick without decay and 1.1 .. 1.2 / 0.76 .. 0.78 / 0.75 .. 0.78 for a
# prefill's state at the padded length (three seeds, 222 tokens)
TINY_LIMITS = {"served_logit_gap": 0.06, "route_disagreement": 0.2,
               "argmax_disagreement": 0.25}


@pytest.fixture
def tiny_limits(monkeypatch):
    """The tiny model's limits; yields the rows its comparison made."""
    real, rows = loader.module, []

    def module(kind, name):
        mod = real(kind, name)
        if (kind, name) == ("families", "kimi_linear"):
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


def _run(capsys, trace=0, seconds=2, seed=3_000_000_019, cell=CELL,
         sizes=SIZES):
    rc = run.main(["--workload", cell, "--seed", str(seed), "--seconds",
                   str(seconds), "--trace", str(trace)], _allow_cpu=True,
                  _sizes=sizes, _t0=time.perf_counter())
    lines = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    return json.loads(lines[-1]), lines


@pytest.mark.parametrize("trace", [0, 1])
def test_cell_runs_and_prints_the_contracts_last_line(capsys, tiny_limits,
                                                      trace):
    out, lines = _run(capsys, trace)
    # a traced run may lose the requests due while the profiler's stop
    # holds the loop (PERF.md, Findings PR 26: 1 of 180 on the chip too)
    assert out["correct"] is True and out["failed"] <= trace, lines
    assert out["attempted"] > 0 and out["device"]["platform"] == "cpu"
    man = loader.manifest()
    known = {m["name"]: m["unit"]
             for m in man["per_layer" if trace else "end_to_end"]}
    for name, m in out["metrics"].items():
        assert m["unit"] == known[name] and isinstance(m["value"], float)
    if trace:
        # the counters' readers find their counters (the device-trace ones
        # find no TPU kernel on the CPU and leave their metric out)
        assert 0.0 < out["metrics"]["tick_state_bytes_share"]["value"] < 100.0
        assert 0.0 < out["metrics"]["moe_held_share"]["value"] < 100.0
        assert "tick_cache_read_share" in out["metrics"]
        assert "mla_decode_attn_roofline_share" not in out["metrics"]
    else:
        assert {"setup_s", "serve_tokens_per_s", "gap_p95_ms"} <= set(
            out["metrics"])


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


@pytest.mark.parametrize("fault", ["tick_without_decay",
                                   "prefill_state_at_s_pad"])
def test_a_broken_state_is_not_correct(capsys, tiny_limits, fault):
    """A logit check can be blind to a state: each broken program — a tick
    that skips the decay, a prefill that hands over the state of the PADDED
    prompt — has to fail a limit, and the program is whole again after."""
    from chainermn_tpu.ops import kda_step as ops
    from chainermn_tpu.parallel import kda

    before = (ops.kda_step, ops.kda_step_xla, kda.kda_project)
    rc = state_control.main(
        ["--workload", CELL, "--seed", "11", "--seconds", "4", "--faults",
         fault], _allow_cpu=True, _sizes=SIZES)
    lines = capsys.readouterr().out.strip().splitlines()
    assert rc == 0, lines
    assert json.loads(lines[-1]) == {"faults": 1, "faults_that_passed": 0}
    assert any(not r["ok"] for r in tiny_limits)
    assert (ops.kda_step, ops.kda_step_xla, kda.kda_project) == before


def test_hybrid_kernel_costs_from_counters():
    """One tick: 25 busy slots over 20 state layers, 10,000 live rows."""
    from benchmark.harness import hybrid_kernel_costs as costs

    cfg = loader.cell(loader.manifest(), CELL)["config"]
    run_ = {"engine_metrics": {
        "serving/tick_calls": 10.0,
        "serving/tick_state_slots_live": 10 * 25 * 20.0,
        "serving/tick_cache_rows_live": 100000.0}}
    step = costs.kda_step(cfg, run_)
    # a (32, 128, 128) float32 state read and written once, and five
    # float32 vectors and one beta broadcast to one: 4.2 MB a pair
    assert step["bytes"] == 500 * 32 * (2 * 128 * 128 + 6 * 128) * 4
    assert step["flops"] == 500 * 32 * 7 * 128 * 128
    # far under the v5e's ridge (240 FLOP/B): bound by bytes
    assert step["flops"] / step["bytes"] < 1.0
    mla = costs.decode_attn_mla(cfg, run_)
    assert mla["bytes"] == 7 * 10000 * 1152           # the 7 latent layers
    assert mla["flops"] == 7 * 10000 * 2 * 32 * 1088
    assert costs.kda_step(cfg, {"engine_metrics": {}}) is None
    # a configuration without such layers (every accepted cell's)
    other = loader.cell(loader.manifest(), GPT2_CELL)["config"]
    assert costs.kda_step(other, run_) is None
    assert costs.decode_attn_mla(other, run_) is None


def test_each_new_reader_is_silent_on_a_gpt2_run(capsys):
    """What this configuration's readers read is absent from a GPT-2 run —
    no ``kda_step`` kernel, no state counters above zero, no
    ``linear_attn_config``: each returns ``None`` and does not raise."""
    from benchmark.tests.conftest import TINY_GPT2, TINY_SERVE

    man = loader.manifest()
    for m in man["per_layer"]:
        if m["name"] in NEW_READERS:     # (the Jamba cell joined one)
            assert CELL in m["workloads"] \
                and GPT2_CELL not in m["workloads"], m
    man["per_layer"] = [dict(m, workloads=m["workloads"] + [GPT2_CELL])
                        if m["name"] in NEW_READERS else m
                        for m in man["per_layer"]]
    real = loader.manifest
    loader.manifest = lambda: man
    try:
        out, lines = _run(capsys, trace=1, cell=GPT2_CELL,
                          sizes={"config": TINY_GPT2, "traffic": TINY_SERVE})
    finally:
        loader.manifest = real
    assert out["correct"] is True, lines
    assert not set(NEW_READERS) & set(out["metrics"])
    assert "tick_cache_read_share" in out["metrics"]     # the old ones read
    # and handed nothing at all, each still returns None
    for name in NEW_READERS:
        read = loader.module("layer_metrics", name).read
        assert read({"window_s": 1.0}, None, {"engine_metrics": {}}) is None


def test_the_two_copies_of_the_reference_are_one_text():
    a = os.path.join(loader.BENCH, "reference", "kimi_linear.py")
    b = os.path.join(loader.ROOT, "tests", "kimi_linear_reference.py")
    assert open(a).read() == open(b).read()
