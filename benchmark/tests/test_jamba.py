"""The Jamba family end to end through ``main(_allow_cpu=...)`` at a tiny
size (CPU: the kernels' plain twins, the einsum attention; the kernels have
their own parity tests under ``tests/``): the cell's files, the cell's last
line with the new metrics, the control in lower precision (it must fail a
limit of ``correct``), the cost functions on hand-computed numbers, the new
readers on a GPT-2 run, where each is silent, and the scope table."""

import json
import os
import time

import pytest

from benchmark import control, run, state_control
from benchmark.harness import loader

CELL = "jamba2-3b-serve-chat-short"
GPT2_CELL = "gpt2-medium-serve-steady"
NEW_READERS = ["ssm_step_ms_per_tick", "ssm_step_roofline_share",
               "selective_scan_ms_per_prefill",
               "selective_scan_roofline_share", "tick_ms.ssm_proj",
               "tick_ms.ssm_core", "prefill_scan_pad_share"]
# M A M M A M: two whole periods of a 3-layer pattern
TINY_JAMBA = {"hidden_size": 64, "num_hidden_layers": 6,
              "attn_layer_period": 3, "attn_layer_offset": 1,
              "num_attention_heads": 4, "num_key_value_heads": 1,
              "intermediate_size": 96, "mamba_d_state": 4,
              "mamba_dt_rank": 8, "vocab_size": 200}
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
SIZES = {"config": TINY_JAMBA, "traffic": TINY_TRAFFIC}
# a mean gap scales with the model: the tiny one (bf16 weights on the CPU, 6
# layers, logits of order one over 200 rows) reads under 0.02 for the
# program and over 0.1 for the control (fp8 operands, a bfloat16 state)
TINY_LIMITS = {"served_logit_gap": 0.05, "argmax_disagreement": 0.2}


@pytest.fixture
def tiny_limits(monkeypatch):
    """The tiny model's limits; yields the rows its comparison made."""
    real, rows = loader.module, []

    def module(kind, name):
        mod = real(kind, name)
        if (kind, name) == ("families", "jamba"):
            mod.ref.LIMITS.update(TINY_LIMITS)
            mod.PAST_BUCKETS = (32, 16)
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


def test_the_cells_files_resolve_and_say_what_the_issue_fixed():
    man = loader.manifest()
    cell = loader.cell(man, CELL)
    cfg, tr = cell["config"], cell["traffic"]
    assert cell["chips"] == 1 and cfg["family"] == "jamba"
    assert cfg["reduced"] == [] and tr["driver"] == "serve_open_loop"
    # the catalog row's config, every key unchanged
    assert (cfg["num_hidden_layers"], cfg["hidden_size"],
            cfg["attn_layer_period"], cfg["attn_layer_offset"]) == (
                28, 2560, 14, 7)
    assert (cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["mamba_expand"], cfg["mamba_d_state"], cfg["mamba_d_conv"],
            cfg["mamba_dt_rank"], cfg["intermediate_size"],
            cfg["vocab_size"]) == (20, 1, 2, 16, 4, 160, 8192, 65536)
    assert tr["engine"] == {"n_slots": 128, "max_total": 2048,
                            "prefill_bucket": 256,
                            "max_prefills_per_tick": 1,
                            "queue_capacity": 512, "spill_bytes": 0}
    assert tr["prompt_len"] == {"median": 160, "sigma": 0.8, "min": 32,
                                "max": 1024}
    assert tr["output_len"] == {"median": 160, "sigma": 0.6, "min": 16,
                                "max": 512}
    assert tr["schedule_seed"] == 0
    # four fifths of the found knee; the tail's samples: 5 % of the
    # window's requests as a TRACED run counts them, those due before its
    # slice (the laguna cell's rule: a traced run has to read its p95 too)
    own = json.load(open(os.path.join(loader.BENCH, "cells",
                                      CELL + ".json")))
    assert tr["rate_per_s"] == pytest.approx(0.8 * own["knee_rate_per_s"])
    before_slice = man["run_seconds"] - tr["trace_seconds"] - 0.5
    assert tr["min_tail_samples"] == int(
        0.05 * tr["rate_per_s"] * before_slice)
    assert os.path.isfile(os.path.join(loader.BENCH, "cells",
                                       CELL + ".sweep.json"))
    due = {m["name"] for m in loader.metrics_of(
        man, "per_layer", CELL, reported={"serve_tokens_per_s",
                                          "gap_p95_ms", "setup_s"})}
    assert set(NEW_READERS) <= due and "tick_ms.unscoped" not in due
    for m in man["per_layer"]:
        if m["name"] in NEW_READERS:
            assert m["workloads"] == [CELL] and m["moves"] == "gap_p95_ms"


def test_the_family_reads_the_published_layer_order():
    fam = loader.module("families", "jamba")
    cfg = loader.cell(loader.manifest(), CELL)["config"]
    arch = fam.arch_of(cfg)
    assert [i for i, k in enumerate(arch.attn_kinds) if k == "mha"] == [7, 21]
    assert arch.attn_kinds.count("mamba") == 26
    assert arch.mamba.state_shapes == ((16, 40, 128), (3, 5120))
    assert not arch.positions and arch.tied_head and not arch.embed_scale
    assert fam.ref.sizes(cfg)["head"] == 128


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
        assert 0.0 < out["metrics"]["prefill_scan_pad_share"]["value"] < 100.0
        assert 0.0 < out["metrics"]["tick_state_bytes_share"]["value"] < 100.0
        assert "tick_cache_read_share" in out["metrics"]
        assert "tick_ms.unscoped" not in out["metrics"]
        assert "ssm_step_roofline_share" not in out["metrics"]
    else:
        assert {"setup_s", "serve_tokens_per_s", "gap_p95_ms"} <= set(
            out["metrics"])
    names = {r["name"] for r in tiny_limits}
    assert names == {"served_logit_gap", "argmax_disagreement"}


def test_the_control_in_lower_precision_fails_a_limit(capsys, tiny_limits):
    sizes = loader.merge(SIZES, {"traffic": {"check_requests": 64}})
    rc = control.main(["--workload", CELL, "--seeds", "5,6", "--seconds",
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
    from chainermn_tpu.ops import ssm_step as ops
    from chainermn_tpu.parallel import mamba

    before = (ops.ssm_step, ops.ssm_step_xla, mamba.mamba_project)
    rc = state_control.main(
        ["--workload", CELL, "--seed", "11", "--seconds", "4", "--faults",
         fault], _allow_cpu=True, _sizes=SIZES)
    lines = capsys.readouterr().out.strip().splitlines()
    assert rc == 0, lines
    assert json.loads(lines[-1]) == {"faults": 1, "faults_that_passed": 0}
    assert any(not r["ok"] for r in tiny_limits)
    assert (ops.ssm_step, ops.ssm_step_xla, mamba.mamba_project) == before


def test_ssm_kernel_costs_from_counters():
    """One busy (slot, layer) pair a tick; one 256-token prefill of 200
    real tokens."""
    from benchmark.harness import ssm_kernel_costs as costs

    cfg = loader.cell(loader.manifest(), CELL)["config"]
    # a (16, 5120) float32 state read and written once, three vectors of
    # 5120 float32 (c, dt in; y out) and B, C of 16: 716,928 B a pair
    assert costs.pair_bytes(5120, 16) == 2 * 327_680 + 3 * 20_480 + 128
    run_ = {"engine_metrics": {"serving/tick_calls": 10.0,
                               "serving/tick_state_slots_live": 10.0}}
    step = costs.ssm_step(cfg, run_)
    assert step["bytes"] == 716_928
    assert step["flops"] == 9 * 5120 * 16
    # far under the v5e's ridge (240 FLOP/B): bound by bytes
    assert step["flops"] / step["bytes"] < 2.0
    # the prefill: 200 real tokens x 26 layers, each 3 x 5120 + 32 float32,
    # and the state read and written once a layer
    run_ = {"engine_metrics": {"serving/prefill_scan_tokens": 200 * 26.0,
                               "serving/prefill_tokens_real": 200.0,
                               "serving/prefill_tokens_padded": 256.0}}
    scan = costs.selective_scan(cfg, run_, [256])
    assert scan["bytes"] == 200 * 26 * (3 * 5120 + 32) * 4 \
        + 26 * 2 * 327_680
    assert scan["flops"] == 200 * 26 * 9 * 5120 * 16
    assert costs.ssm_step(cfg, {"engine_metrics": {}}) is None
    assert costs.selective_scan(cfg, {"engine_metrics": {}}, [256]) is None
    # a configuration without such layers (every accepted cell's)
    other = loader.cell(loader.manifest(), GPT2_CELL)["config"]
    assert costs.ssm_step(other, run_) is None
    assert costs.selective_scan(other, run_, [256]) is None


def test_the_longer_scope_table_books_the_mamba_scopes():
    from benchmark.harness import scope_trace, ssm_scope_trace

    tick = "jit(serving_tick)/tick/layer/block/mamba/"
    assert ssm_scope_trace.leaf_of(tick + "proj/dot_general") == (
        "ssm_proj", "block/mamba/proj")
    assert ssm_scope_trace.leaf_of(tick + "core/ssm_step") == (
        "ssm_core", "block/mamba/core")
    assert ssm_scope_trace.leaf_of(tick + "conv/mul") == (
        "ssm_core", "block/mamba/conv")
    # the accepted table is as it was, before and after: to it they are
    # unscoped, and its own rows read the same through either
    assert scope_trace.bucket_of(tick + "proj/dot_general") is None
    attn = "jit(serving_tick)/tick/layer/block/attn/core/decode_attn_gqa"
    assert ssm_scope_trace.bucket_of(attn) == scope_trace.bucket_of(attn) \
        == "attn_core"
    assert ssm_scope_trace.BUCKETS[len(ssm_scope_trace.MAMBA_ROWS):] \
        == scope_trace.BUCKETS


def test_each_new_reader_is_silent_on_a_gpt2_run(capsys):
    """What this configuration's readers read is absent from a GPT-2 run —
    no ``ssm_step`` / ``selective_scan`` kernel, no scan counters above
    zero, no ``block/mamba`` scope: each returns ``None`` and does not
    raise."""
    from benchmark.tests.conftest import TINY_GPT2, TINY_SERVE

    man = loader.manifest()
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
    a = os.path.join(loader.BENCH, "reference", "jamba.py")
    b = os.path.join(loader.ROOT, "tests", "jamba_reference.py")
    assert open(a).read() == open(b).read()
