"""The Mellum 2 family end to end through ``main(_allow_cpu=...)`` at a tiny
size (CPU: the interpreted banded and causal flash kernels forward and
backward, the dense-loop experts, the fused loss's XLA emulation; the kernels
have their own parity tests under ``tests/``): the training cell's last line
with and without the trace, the control in lower precision, the planted
faults (``plant_fault.py``), every new reader on the rehearsed run, on
hand-made inputs and on a GPT-2 run, where each is silent."""

import json
import os
import time

import pytest

from benchmark import control, run
from benchmark.harness import loader, train_moe_window_costs as costs
from benchmark.tests import plant_fault

CELL = "mellum2-ep4-train-s8192"
GPT2_CELL = "gpt2-medium-train-s1024"
NEW_READERS = ["window_flash_ms_per_step", "window_flash_bwd_roofline_share",
               "train_moe_gmm_ms_per_step", "train_moe_gmm_roofline_share",
               "moe_gmm_dw_roofline_share", "train_moe_held_share",
               "train_moe_slice_held_share",
               "train_moe_expert_load_max_over_mean", "train_ms.attn_core",
               "train_ms.moe_route", "train_ms.moe_experts",
               "train_full_flash_ms_per_step",
               "train_fused_ce_roofline_share"]
# one whole period, GQA group 2, a window of 16, 4 of 8 experts held
TINY_MELLUM = {"hidden_size": 64, "num_attention_heads": 4,
               "num_key_value_heads": 2, "head_dim": 16,
               "sliding_window": 16, "num_experts": 8,
               "num_experts_held": 4, "num_experts_per_tok": 2,
               "moe_intermediate_size": 32, "vocab_size": 256}
TINY_TRAFFIC = {"seq_len": 64, "batch_per_chip": 2, "pool_batches": 4,
                "readback_every": 2, "trace_steps": 4}
SIZES = {"config": TINY_MELLUM, "traffic": TINY_TRAFFIC}
# a 64-column model's gradients move with bfloat16's rounding as the
# published widths' do not (readings beside the test's call)
TINY_LIMITS = {"grad_norm_gap": 5e-2, "update_norm_gap": 5e-2,
               "route_disagreement": 5e-2,
               "route_disagreement_updated": 5e-2}


@pytest.fixture
def tiny_limits(monkeypatch):
    """The tiny model's limits; yields the rows its comparison made."""
    real, rows = loader.module, []

    def module(kind, name):
        mod = real(kind, name)
        if (kind, name) == ("families", "mellum2"):
            mod.ref.LIMITS.update(TINY_LIMITS)
            compare = mod.train_compare

            def keeping(*args, **kw):
                out = compare(*args, **kw)
                rows.extend(out)
                return out

            mod.train_compare = keeping
        return mod

    monkeypatch.setattr(loader, "module", module)
    return rows


@pytest.fixture
def fresh_counters():
    """The process tracer and the harness's record of the traced slice as
    a fresh process has them (the family enables the tracer and books the
    check's routing counts there, the slice's with the harness)."""
    from chainermn_tpu.observability import trace

    tr = trace.get_tracer()
    was = tr.enabled
    tr.reset()
    costs.reset_slice()
    yield tr
    tr.reset()
    costs.reset_slice()
    tr.enabled = was


def _run(capsys, trace=0, seconds=2, seed=3_800_000_019, cell=CELL,
         sizes=SIZES):
    rc = run.main(["--workload", cell, "--seed", str(seed), "--seconds",
                   str(seconds), "--trace", str(trace)], _allow_cpu=True,
                  _sizes=sizes, _t0=time.perf_counter())
    lines = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    return json.loads(lines[-1]), lines


@pytest.mark.parametrize("trace", [0, 1])
def test_cell_runs_and_prints_the_contracts_last_line(capsys, tiny_limits,
                                                      fresh_counters, trace):
    # tiny readings (seed 3800000019): grad 1.1e-2, update 7.0e-3, routes
    # 1.4e-2
    out, lines = _run(capsys, trace)
    assert out["correct"] is True and out["failed"] == 0, lines
    assert out["attempted"] > 0 and out["device"]["platform"] == "cpu"
    man = loader.manifest()
    known = {m["name"]: m["unit"]
             for m in man["per_layer" if trace else "end_to_end"]}
    for name, m in out["metrics"].items():
        assert m["unit"] == known[name] and isinstance(m["value"], float)
    assert [r["name"] for r in tiny_limits] == [
        "grad_norm_gap", "update_norm_gap", "route_disagreement",
        "route_disagreement_updated"]
    assert all(r["ok"] and r["value"] > 0 for r in tiny_limits)
    if not trace:
        assert {"setup_s", "train_samples_per_s"} <= set(out["metrics"])
        return
    # the counters' readers find their counters (the device-trace ones
    # find no TPU kernel on the CPU and leave their metric out): 4 of 8
    # experts held, so about half of the assignments
    assert 35.0 < out["metrics"]["train_moe_held_share"]["value"] < 65.0
    assert 1.0 <= out["metrics"]["train_moe_expert_load_max_over_mean"][
        "value"] < 2.0
    # ... and the traced slice's own steps theirs: the router has learned
    # by then (only held experts answer it), so it sends them no less
    assert out["metrics"]["train_moe_slice_held_share"]["value"] >= \
        out["metrics"]["train_moe_held_share"]["value"] - 5.0
    c = fresh_counters.counters()
    # the check's three steps with the program's counters, the slice's (4
    # traced steps, in twos) with the harness: 2 x 64 tokens x top-2 x 4
    # layers a step
    assert c["train/moe_steps"] == 3
    assert c["train/moe_assignments_total"] == 3 * 2 * 64 * 2 * 4
    assert sum(v for k, v in c.items() if k.startswith(
        "train/moe_expert_tokens/")) == c["train/moe_assignments_held"]
    assert not [k for k in c if k.startswith("train_slice/")]
    assert costs._slice["steps"] == 4
    assert costs._slice["total"] == 4 * 2 * 64 * 2 * 4
    assert 0 < costs._slice["held"] <= costs._slice["total"]
    assert not {"flash_fwd_ms_per_step", "fused_ce_roofline_share",
                "serve_idle_share.admit"} & set(out["metrics"])


def test_the_mfu_counts_the_slices_own_routing(fresh_counters):
    """``train_mfu`` reads the trainer's ``flops_per_sample`` after the
    window: the held experts' part at the routing of the traced slice's
    steps where there was one, at even routing else."""
    fam = loader.module("families", "mellum2")
    cell = loader.cell(loader.manifest(), CELL)
    cfg, traffic = cell["config"], cell["traffic"]

    class Ctx:
        config, traffic = cell["config"], cell["traffic"]

    trainer = object.__new__(fam.Trainer)
    trainer.ctx, trainer.samples_per_step = Ctx, 2
    trainer._slice = ()
    even = fam.train_flops_per_sample(cfg, traffic)
    assert trainer.flops_per_sample == even
    # ten steps at even routing: a quarter of 2 x 8192 x 8 x 4 layers
    total = 10 * 2 * 8192 * 8 * 4
    costs.book_slice([total, total // 4], steps=10)
    assert trainer.flops_per_sample == pytest.approx(even)
    # ... and at 55 % held: 4.4 of 8 chosen experts a token are here
    costs.reset_slice()
    costs.book_slice([total, total * 0.55], steps=10)
    more = trainer.flops_per_sample
    experts = 3 * 3 * 2 * 2304 * 896 * 4 * 8192      # fwd + bwd, 4 layers
    assert more - even == pytest.approx((4.4 - 2.0) * experts)
    assert 1.15 < more / even < 1.35


@pytest.mark.parametrize("fault, caught_by", [
    ("frozen", {"update_norm_gap"}),
    ("half_batch", {"grad_norm_gap", "route_disagreement"})])
def test_a_planted_fault_is_not_correct(capsys, tiny_limits, fresh_counters,
                                        fault, caught_by):
    """``plant_fault.py`` through ``run.py``: a step that returns its state
    unchanged, and a step that trains on half its batch — ``correct`` is
    false, by the limits named.  Tiny readings, seed 3800000019: frozen
    gradient (AdamW's first moment stays 0) and update 1.0, routes 0.014
    and 0.016 — a 64-column router's logits move too little in two steps to
    tell, the published widths' do (PERF.md, section 2); half batch
    gradient 0.51, update 0.20, routes 0.48 and 0.49."""
    rc = plant_fault.main(
        ["--fault", fault, "--workload", CELL, "--seed", "3800000019",
         "--seconds", "1", "--trace", "0"], _allow_cpu=True, _sizes=SIZES,
        _t0=time.perf_counter())
    lines = capsys.readouterr().out.strip().splitlines()
    assert rc == 0 and json.loads(lines[-1])["correct"] is False, lines
    failed = {r["name"] for r in tiny_limits if not r["ok"]}
    assert caught_by <= failed, tiny_limits


def test_the_control_in_lower_precision_fails_a_limit(capsys, tiny_limits):
    rc = control.main(["--workload", CELL, "--seeds", "5,6"],
                      _allow_cpu=True, _sizes=SIZES)
    lines = capsys.readouterr().out.strip().splitlines()
    assert rc == 0, lines[-1]          # no control passed
    assert json.loads(lines[-1])["controls_that_passed"] == 0


def test_needed_work_from_shapes_and_counters(fresh_counters, monkeypatch):
    """The cell's own sizes: 2 x 8192 tokens, a band of 1024, 3 sliding
    layers of 32 query heads of 128; 16 held experts of 2304 x 896."""
    cell = loader.cell(loader.manifest(), CELL)
    cfg, traffic = cell["config"], cell["traffic"]
    pairs = 1024 * 1025 // 2 + (8192 - 1024) * 1024
    assert costs.band_pairs(8192, 1024) == pairs
    fwd = costs.window_flash_fwd(cfg, traffic)
    assert fwd["flops"] == 3 * 4 * 128 * 32 * 2 * pairs
    bwd = costs.window_flash_bwd(cfg, traffic)
    assert bwd["flops"] == 2 * fwd["flops"]
    # far over the v5e's ridge (240 FLOP/B): bound by operations
    assert bwd["flops"] / bwd["bytes"] > 500
    # at even routing a quarter of 2 x 8192 x 8 assignments a layer are held
    held = 4 * 2 * 8192 * 8 / 4
    dw = costs.moe_gmm_dw(cfg, traffic, held)
    assert dw["flops"] == 3 * 2 * 2304 * 896 * held
    allp = costs.moe_gmm_train(cfg, traffic, held)
    assert allp["flops"] == 3 * dw["flops"]
    assert costs.moe_gmm_dw(cfg, traffic, 0) is None
    # a configuration without such layers (every accepted cell's)
    other = loader.cell(loader.manifest(), GPT2_CELL)
    assert costs.window_flash_bwd(other["config"], other["traffic"]) is None
    assert costs.moe_gmm_train(other["config"], other["traffic"], 1e5) is None
    assert costs.held_assignments_per_step() is None     # no slice yet
    assert costs.slice_held_share() is None
    costs.book_slice([300, 75, 16, 5, 70])
    costs.book_slice([100, 25, 16, 20, 5])
    assert costs.held_assignments_per_step() == 50.0
    assert costs.slice_held_share() == 25.0
    costs.reset_slice()
    # the fused loss at the sliced vocabulary: 6 T V D
    ce = costs.fused_ce(cfg, traffic)
    assert ce["flops"] == 6 * 2 * 8192 * 24576 * 2304
    assert costs.fused_ce(other["config"], other["traffic"]) is None
    # the full layers' flash kernels by the START of their names
    trace = {"op_seconds": {"flash_fwd.3 [tpu_custom_call]": 0.02,
                            "flash_bwd.1 [tpu_custom_call]": 0.03,
                            "window_flash_fwd.2 [tpu_custom_call]": 0.5,
                            "flash_fwd_like_fusion.1": 1.0}}
    monkeypatch.setattr(costs.program_trace, "cell_of", lambda t: cell)
    assert costs.full_flash_ms_per_step(
        trace, {"steps_in_slice": 10}) == pytest.approx(5.0)
    monkeypatch.setattr(costs.program_trace, "cell_of", lambda t: other)
    assert costs.full_flash_ms_per_step(trace, {"steps_in_slice": 10}) is None
    # the family's FLOPs a sample: about 1.5 GFLOP a token
    fam = loader.module("families", "mellum2")
    per_token = fam.train_flops_per_sample(cfg, traffic) / 8192
    assert 1.4e9 < per_token < 1.6e9


def test_train_scope_buckets():
    """Under ``loss_grad`` ``scope_trace`` books the phase; this reader the
    same table's served rows, the phase's scope taken off the path."""
    from benchmark.harness import scope_trace
    from benchmark.harness.train_scope_trace import bucket_of

    for op_name, want, phase in [
        ("jit(train_step)/loss_grad/jvp(checkpoint)/block/attn/core/"
         "block/attn/window/window_flash_fwd", "attn_core", "fwd"),
        ("jit(train_step)/loss_grad/transpose(jvp(loss_grad))/jvp()/"
         "rematted_computation/block/attn/core/flash_fwd", "attn_core",
         "bwd"),
        ("jit(train_step)/loss_grad/transpose(jvp(block/attn))/core/"
         "flash_bwd", "attn_core", "bwd"),
        ("jit(train_step)/loss_grad/jvp(block/mlp)/block/moe/route/top_k",
         "moe_route", "fwd"),
        ("jit(train_step)/loss_grad/jvp(block/mlp)/block/moe/dispatch/"
         "cumsum", "moe_route", "fwd"),
        ("jit(train_step)/loss_grad/transpose(jvp(block/mlp))/block/moe/"
         "gmm/moe_gmm_dw", "moe_experts", "bwd"),
        ("jit(train_step)/loss_grad/jvp(block/attn)/proj/dot_general",
         "attn_proj", "fwd"),
        ("jit(train_step)/loss_grad/jvp(block/mlp)/mul", "ffn_dense", "fwd"),
        ("jit(train_step)/loss_grad/jvp(head_ce)/fused_ce_stats", None,
         "fwd"),
        ("jit(train_step)/optimizer/mul", "optimizer", "optimizer"),
    ]:
        assert bucket_of(op_name) == want, op_name
        assert scope_trace.bucket_of(op_name) == phase, op_name


def test_the_train_split_on_recorded_traces():
    """On the chip's recorded traces (``benchmark/tests``' fixtures): a dense
    GPT-2 train step holds none of the scopes the ``train_ms.*`` metrics
    read and a program without scopes none at all — ``None`` both times, no
    raise; with the phase taken off, the served programs' own split of the
    same file is what ``scope_trace`` gives (one table, one split)."""
    from benchmark.harness import scope_trace, train_scope_trace

    here = os.path.dirname(os.path.abspath(__file__))
    scopes = os.path.join(here, "recorded_v5e_scopes.xplane.pb")
    assert train_scope_trace.split(scopes) is None
    assert train_scope_trace.split(
        os.path.join(here, "recorded_v5e_tiny.xplane.pb")) is None
    served = scope_trace.split(scopes, "serving_tick")
    assert served["buckets"]["moe_experts"] > 0
    assert train_scope_trace.inside_phase(
        "jit(train_step)/loss_grad/transpose(jvp(loss_grad))/jvp()/"
        "block/mlp/block/moe/gmm/moe_gmm_dw") == (
        "jit(train_step)//transpose(jvp())/jvp()/block/mlp/block/moe/gmm/"
        "moe_gmm_dw")


def test_each_new_reader_is_silent_on_a_gpt2_run(capsys, fresh_counters):
    """What this configuration's readers read is absent from a GPT-2 train
    run — no ``window_flash`` or ``moe_gmm`` kernel, no ``train/moe_*``
    counter, no such scope: each returns ``None`` and does not raise."""
    from benchmark.tests.conftest import TINY_GPT2, TINY_TRAIN

    man = loader.manifest()
    for m in man["per_layer"]:
        if m["name"] in NEW_READERS:
            assert m["workloads"] == [CELL], m
    assert {m["name"] for m in man["per_layer"]} >= set(NEW_READERS)
    man["per_layer"] = [dict(m, workloads=m["workloads"] + [GPT2_CELL])
                        if m["name"] in NEW_READERS else m
                        for m in man["per_layer"]]
    real = loader.manifest
    loader.manifest = lambda: man
    try:
        out, lines = _run(capsys, trace=1, cell=GPT2_CELL,
                          sizes={"config": TINY_GPT2, "traffic": TINY_TRAIN})
    finally:
        loader.manifest = real
    assert not set(NEW_READERS) & set(out["metrics"]), lines
    # and handed nothing at all, each still returns None
    for name in NEW_READERS:
        read = loader.module("layer_metrics", name).read
        assert read({"window_s": 1.0, "op_seconds": {}}, None,
                    {"steps_in_slice": 4}) is None


def test_a_program_before_this_configuration_fails_at_once(monkeypatch):
    """On the parent commit (no softmax router) the family's import raises
    before the reference's minutes: the cell fails cleanly there."""
    from chainermn_tpu.parallel import blocks

    class Old:
        __dataclass_fields__ = {"n_experts": None}

    monkeypatch.setattr(blocks, "MoEConfig", Old)
    with pytest.raises(RuntimeError, match="softmax router"):
        loader.module("families", "mellum2")


def test_the_two_copies_of_the_reference_are_one_text():
    a = os.path.join(loader.BENCH, "reference", "mellum2.py")
    b = os.path.join(loader.ROOT, "tests", "mellum2_reference.py")
    assert open(a).read() == open(b).read()
