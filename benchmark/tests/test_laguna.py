"""The Laguna family end to end through ``main(_allow_cpu=...)`` at a tiny
size (CPU: the interpreted banded flash forward in the prefills, the einsum
tick over rows and rings, the dense-loop experts; the kernels have their
own parity tests under ``tests/``): the cell's last line, the control in
lower precision, the two broken-WINDOW programs (each must fail a limit of
``correct``), and the new readers on hand-made inputs and on a GPT-2 run,
where each is silent."""

import json
import os
import time

import pytest

from benchmark import control, run, state_control
from benchmark.harness import loader

CELL = "laguna-xs2-ep16-serve-mixed"
GPT2_CELL = "gpt2-medium-serve-steady"
NEW_READERS = ["gqa_decode_attn_roofline_share", "tick_ring_bytes_share",
               "window_flash_fwd_ms_per_prefill",
               "window_flash_fwd_roofline_share"]
# F S S S F S S S, unequal head counts, the first layer's MLP dense: two
# whole periods; a window of 8
TINY_LAGUNA = {
    "hidden_size": 64, "num_hidden_layers": 8, "head_dim": 16,
    "num_key_value_heads": 2, "num_attention_heads": 4,
    "num_attention_heads_per_layer": [4, 6, 6, 6, 4, 6, 6, 6],
    "layer_types": (["full_attention"] + ["sliding_attention"] * 3) * 2,
    "mlp_layer_types": ["dense"] + ["sparse"] * 7,
    "sliding_window": 8, "max_position_embeddings": 64,
    "rope_parameters": {
        "full_attention": {"factor": 4,
                           "original_max_position_embeddings": 16,
                           "beta_fast": 4,
                           "attention_factor": 1.1386294361119891},
        "original_max_position_embeddings": 16},
    "intermediate_size": 96, "moe_intermediate_size": 32,
    "shared_expert_intermediate_size": 32, "num_experts": 16,
    "num_experts_held": 4, "num_experts_per_tok": 4, "vocab_size": 200,
    # the tiny model's own: 8 layers of 64 columns need peaked scores for a
    # window of 8 to show through 40-token prompts
    "assumed": {"init": {"query_gain": 4.0}}}
TINY_TRAFFIC = {"rate_per_s": 8.0,
                "prompt_len": {"median": 14, "sigma": 0.8, "min": 4,
                               "max": 40},
                "output_len": {"median": 10, "sigma": 0.6, "min": 4,
                               "max": 20},
                "max_total": 64,
                "engine": {"n_slots": 4, "max_total": 64,
                           "prefill_bucket": 16, "queue_capacity": 16},
                "warm_prompts": [10, 20, 40], "check_requests": 16,
                "trace_seconds": 0.5, "min_tail_samples": 0}
SIZES = {"config": TINY_LAGUNA, "traffic": TINY_TRAFFIC}
# a mean gap and the shares of flipped routes and tokens scale with the
# model: see the readings beside each test's call
TINY_LIMITS = {"served_logit_gap": 0.06, "route_disagreement": 0.2,
               "argmax_disagreement": 0.25}


@pytest.fixture
def tiny_limits(monkeypatch):
    """The tiny model's limits and context length; yields the rows its
    comparison made."""
    real, rows = loader.module, []

    def module(kind, name):
        mod = real(kind, name)
        if (kind, name) == ("families", "laguna"):
            mod.ref.LIMITS.update(TINY_LIMITS)
            mod.LONG_CONTEXT = 24          # three tiny windows
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
        assert 0.0 < out["metrics"]["tick_ring_bytes_share"]["value"] < 100.0
        assert 0.0 < out["metrics"]["moe_held_share"]["value"] < 100.0
        assert 0.0 < out["metrics"]["tick_cache_read_share"]["value"] <= 100.0
        assert "mla_decode_attn_roofline_share" not in out["metrics"]
        assert "tick_state_bytes_share" not in out["metrics"]
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


@pytest.mark.parametrize("fault", ["sliding_sees_whole_prefix",
                                   "ring_filled_from_s_pad"])
def test_a_broken_window_is_not_correct(capsys, tiny_limits, fault):
    """A logit check can be blind to a window: each broken program — a
    prefill whose sliding layers attend their whole prefix, a prefill that
    fills the ring from the PADDED length — has to fail a limit, and the
    program is whole again after."""
    import importlib

    from chainermn_tpu.parallel import blocks

    ops = importlib.import_module("chainermn_tpu.ops.flash_attention")

    before = (ops.flash_attention, blocks.ring_rows)
    rc = state_control.main(
        ["--workload", CELL, "--seed", "11", "--seconds", "4", "--faults",
         fault], _allow_cpu=True, _sizes=SIZES)
    lines = capsys.readouterr().out.strip().splitlines()
    assert rc == 0, lines
    assert json.loads(lines[-1]) == {"faults": 1, "faults_that_passed": 0}
    assert any(not r["ok"] for r in tiny_limits)
    assert (ops.flash_attention, blocks.ring_rows) == before


def test_the_checked_requests_are_long_where_the_window_has_them():
    """At least half of the checked requests were served past
    ``LONG_CONTEXT`` tokens of context (the longest among them), or the
    sliding-sees-everything control proves nothing."""
    import numpy as np

    fam = loader.module("families", "laguna")

    class Handle:
        status = "done"
        routes = np.zeros((1, 39, 8), np.int32)

        def __init__(self, n):
            self.tokens = [0] * n

    lengths = [100, 3000, 200, 1500, 300, 1100, 400, 1025, 500, 2000, 600,
               700]
    reqs = [{"prompt": np.zeros(n - 50, np.int32)} for n in lengths]
    recs = [{"handle": Handle(50)} for _ in lengths]
    ctx = type("Ctx", (), {"seed": 7})()
    sample = fam.served_sample(ctx, recs, reqs, 8)
    got = [len(seq) for seq, _, _ in sample]
    assert len(got) == 8 and got[0] == 3000
    assert sum(n > fam.LONG_CONTEXT for n in got) >= 4
    assert any(n <= fam.LONG_CONTEXT for n in got)      # and short ones
    # a window with one long request: it, and whatever else finished
    few = fam.served_sample(ctx, recs[:3], reqs[:3], 8)
    assert sorted(len(s) for s, _, _ in few) == [100, 200, 3000]


def test_window_kernel_costs_from_counters():
    """One tick: 12 busy slots of 1400 rows; the rings all full."""
    from benchmark.harness import window_kernel_costs as costs

    cfg = loader.cell(loader.manifest(), CELL)["config"]
    rows = 12 * 1400
    # the traced slice's own ticks are counted (its counters' growth); the
    # whole run's, here three times as busy, are not
    run_ = {"engine_metrics": {
        "serving/tick_calls": 1000.0,
        "serving/cache_bytes_per_token": 10 * 4096.0,
        "serving/tick_row_bytes": 3000 * rows * 10 * 4096.0,
        "serving/tick_ring_rows_live": 3000 * 12 * 512 * 30.0,
        "serving/tick_ring_bytes": 3000 * 12 * 512 * 30 * 4096.0},
        "slice_metrics": {
        "serving/tick_calls": 10.0,
        "serving/cache_bytes_per_token": 0.0,
        "serving/tick_row_bytes": 10 * rows * 10 * 4096.0,
        "serving/tick_ring_rows_live": 10 * 12 * 512 * 30.0,
        "serving/tick_ring_bytes": 10 * 12 * 512 * 30 * 4096.0}}
    need = costs.decode_attn_gqa(cfg, run_)
    # K and V rows of 8 x 128 bf16 columns: 4096 B a row a layer
    assert need["bytes"] == (rows * 10 + 12 * 512 * 30) * 4096
    # 48 heads in the 10 full layers, 64 in the 30 sliding ones
    assert need["flops"] == 4 * 128 * (rows * 480 + 12 * 512 * 30 * 64)
    # far under the v5e's ridge (240 FLOP/B): bound by bytes
    assert need["flops"] / need["bytes"] < 10.0
    assert costs.band_pairs(3072, 512) == 512 * 513 // 2 + 2560 * 512
    assert costs.band_pairs(100, 512) == 100 * 101 // 2
    assert costs.decode_attn_gqa(cfg, {"engine_metrics": {}}) is None
    # an untraced run has no slice: nothing to read, not the run's mean
    assert costs.decode_attn_gqa(
        cfg, dict(run_, slice_metrics=None)) is None
    # a configuration without such layers (every accepted cell's)
    other = loader.cell(loader.manifest(), GPT2_CELL)["config"]
    assert costs.decode_attn_gqa(other, run_) is None


def test_each_new_reader_is_silent_on_a_gpt2_run(capsys):
    """What this configuration's readers read is absent from a GPT-2 run —
    no ``window_flash_fwd`` kernel, no ring counters above zero, no
    ``layer_types``: each returns ``None`` and does not raise."""
    from benchmark.tests.conftest import TINY_GPT2, TINY_SERVE

    man = loader.manifest()
    for m in man["per_layer"]:
        if m["name"] in NEW_READERS:
            assert m["workloads"] == [CELL], m
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
    a = os.path.join(loader.BENCH, "reference", "laguna.py")
    b = os.path.join(loader.ROOT, "tests", "laguna_reference.py")
    assert open(a).read() == open(b).read()
