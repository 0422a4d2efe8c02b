"""The readers of what the PROGRAM writes into a trace (its spans, its
named programs and kernels): on synthetic events, and on the recorded v5e
trace of a program that has none of them, where each returns ``None``."""

import os
import shutil

import pytest

from benchmark.harness import (device, kernel_costs, loader,
                               program_trace as pt, trace_reduce as tr)

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "recorded_v5e_tiny.xplane.pb")
TRAIN, SERVE = "gpt2-medium-train-s1024", "gpt2-medium-serve-steady"
#: the per-layer metrics that read what the program writes (ISSUE 25)
NEW = [m["name"] for m in loader.manifest()["per_layer"]
       if m["name"].endswith(("_ms_per_step", "_roofline_share"))
       or m["name"].startswith(("serve_idle_share.", "tick_device_ms",
                                "prefill_device_ms", "prefill_pad_share"))]

# one engine iteration on the host (ns), the device busy 100..400 (a
# prefill) and 520..900 (the tick); the slice is 0..1000
HOST = [
    ("traced_slice", 0, 1000),
    ("engine_step", 20, 980),              # the benchmark's own span
    ("serving/step", 30, 970),
    ("serving/expire", 30, 40),
    ("serving/admit", 40, 60),
    ("serving/prefill", 70, 430),
    ("serving/prefill/stage", 70, 90),
    ("serving/prefill/dispatch", 90, 110),
    ("serving/prefill/readback", 110, 430),
    ("serving/emit", 430, 450),
    ("serving/tick", 470, 930),
    ("serving/tick/stage", 470, 500),
    ("serving/tick/dispatch", 500, 530),
    ("serving/tick/readback", 530, 930),
    ("serving/emit", 930, 950),
    ("serving/bookkeeping", 950, 970),
]
EVENTS = {"devices": {0: [("fusion.1", 100, 400), ("fusion.2", 520, 900)]},
          "async": {}, "host": HOST}
MODULES = {0: [("serving_prefill_256", 100, 400), ("serving_tick", 520, 900),
               ("serving_tick", 1200, 1500)]}          # the last: outside


def test_idle_split_is_exhaustive_and_follows_the_spans():
    v = pt.view(EVENTS, MODULES)
    assert v["gaps"] == [(0, 100), (400, 520), (900, 1000)]
    split = {k: round(t * 1e9) for k, t in pt.idle_split(v).items()}
    assert split == {
        "outside_step": 30 + 30,        # 0..30 and 970..1000
        "admit": 10 + 20,               # expire 30..40, admit 40..60
        "prefill_host": 30 + 30,        # 70..100 before, 400..430 after
        "emit": 20 + 20,                # 430..450 and 930..950
        "tick_host": 50 + 30,           # 470..520 before, 900..930 after
        "bookkeeping": 20 + 10 + 20,    # its span, 60..70 and 450..470
    }
    reduced = tr.reduce(EVENTS, ("engine_step",))
    idle = 100.0 * (1.0 - reduced["busy_s"] / reduced["window_s"])
    assert sum(split.values()) / 1000 * 100 == pytest.approx(idle)
    # and what the accepted breakdown reads is untouched by the new spans
    assert [g[0] for g in reduced["idle_gaps"]] == ["engine_step"]


def test_idle_children_split_a_bucket_by_stage_dispatch_readback():
    v = pt.view(EVENTS, MODULES)     # the slice is 1000 ns: 10 ns = 1 %
    assert pt.idle_children(v, "serving/tick") == {
        "serving/tick/dispatch": pytest.approx(2.0),
        "serving/tick/readback": pytest.approx(3.0),
        "serving/tick/stage": pytest.approx(3.0)}


def test_a_program_without_spans_gives_no_split():
    bare = dict(EVENTS, host=[h for h in HOST
                              if not h[0].startswith("serving/")])
    assert pt.idle_split(pt.view(bare, MODULES)) is None
    assert pt.view(dict(EVENTS, host=[]), MODULES) is None     # no slice


def test_programs_are_read_by_name_inside_the_slice():
    v = pt.view(EVENTS, MODULES)
    assert [m[0] for m in v["modules"]] == ["serving_prefill_256",
                                            "serving_tick"]
    assert pt.MODULE_NAME.match(
        "jit_serving_prefill_512(6074760096634504725)").group(1) \
        == "serving_prefill_512"


@pytest.mark.parametrize("suffix", [".7", ".26", ""])
def test_kernel_grouping_survives_a_changed_numeric_suffix(suffix):
    tag = tr.KERNEL_TAG
    trace = {"op_seconds": {
        f"flash_fwd{suffix}{tag}": 0.010, f"flash_fwd.99{tag}": 0.006,
        f"flash_bwd{suffix}{tag}": 0.024,
        f"fused_ce_stats{suffix}{tag}": 0.008,
        f"fused_ce_dh{suffix}{tag}": 0.009,
        f"fused_ce_dtable{suffix}{tag}": 0.010,
        "fusion.7": 0.5, "flash_fwd_like_fusion.1": 0.3}}
    run = {"steps_in_slice": 2}
    assert kernel_costs.ms_per_step(trace, run, "flash_fwd") \
        == pytest.approx(8.0)
    assert kernel_costs.ms_per_step(trace, run, "flash_bwd") \
        == pytest.approx(12.0)
    assert kernel_costs.ms_per_step(trace, run, "fused_ce") \
        == pytest.approx(13.5)
    # a program before the names: one lump, no kernel metric
    old = {"op_seconds": {f"transpose_jvp___.27{tag}": 0.2}}
    assert kernel_costs.ms_per_step(old, run, "fused_ce") is None


@pytest.mark.parametrize("kernel,ms", [("flash_fwd", 16.5),
                                       ("flash_bwd", 23.9),
                                       ("fused_ce", 27.1)])
def test_roofline_share_at_the_cells_shapes_and_recorded_times(
        kernel, ms, monkeypatch):
    """The times PERF.md records for the train cell (my chip runs, PR 24)
    against what the kernels need: no share over 100 %."""
    cell = loader.cell(loader.manifest(), TRAIN)
    monkeypatch.setattr(pt, "cell_of", lambda trace: cell)
    trace = {"op_seconds": {f"{kernel}.1{tr.KERNEL_TAG}": ms / 1e3}}
    run = {"steps_in_slice": 1, "peaks": device.PEAKS["TPU v5 lite"]}
    share = kernel_costs.roofline_share(trace, run, kernel)
    want = {"flash_fwd": 12.7, "flash_bwd": 17.5, "fused_ce": 47.4}[kernel]
    assert share == pytest.approx(want, abs=0.1) and share <= 100.0
    cost = getattr(kernel_costs, kernel)(cell["config"], cell["traffic"])
    assert cost["flops"] / 197e12 >= cost["bytes"] / 819e9   # FLOP-bound


@pytest.fixture
def recorded(tmp_path, monkeypatch):
    """The recorded v5e trace laid out as a traced run of the serving cell
    leaves it, and its reduction."""
    d = tmp_path / SERVE / "plugins" / "profile" / "2026_09_27"
    d.mkdir(parents=True)
    shutil.copy(RECORDED, d / "vm.xplane.pb")
    monkeypatch.setattr(pt, "TRACE_ROOT", str(tmp_path))
    pt._CACHE.clear()
    return tr.reduce_file(str(d / "vm.xplane.pb"),
                          ("input", "dispatch", "readback"))


@pytest.mark.parametrize("name", NEW)
def test_every_new_reader_is_silent_on_a_program_without_names(
        recorded, name):
    """tanh(x @ x) on a v5e: no program span, no named program, no kernel,
    no counter — each reader returns ``None`` and raises nothing."""
    run = {"steps_in_slice": 4, "values": {}, "engine_metrics": {},
           "peaks": device.PEAKS["TPU v5 lite"]}
    assert len(NEW) >= 15        # ISSUE 25's fifteen and every kernel reader since
    assert pt.load(recorded) is not None          # the trace itself is read
    assert loader.module("layer_metrics", name).read(
        recorded, [], run) is None


def test_another_runs_trace_is_not_this_runs(recorded, tmp_path):
    assert pt.load(dict(recorded, window_s=recorded["window_s"] + 1e-6)) \
        is None                                    # not the same slice
    assert pt.cell_of(recorded)["name"] == SERVE
    path, cell = pt.newest_xplane()
    assert cell == SERVE
    os.utime(path, (1.0, 1.0))                     # written long ago
    assert pt.newest_xplane() is None and pt.load(recorded) is None


def test_prefill_pad_share_from_the_engines_counters():
    read = loader.module("layer_metrics", "prefill_pad_share").read
    run = {"engine_metrics": {"serving/prefill_tokens_real": 192.0,
                              "serving/prefill_tokens_padded": 256.0}}
    assert read({}, [], run) == pytest.approx(25.0)
    assert read({}, [], {"engine_metrics": {}}) is None
