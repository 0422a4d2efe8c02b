"""``benchmark/harness/scope_trace.py`` on a trace recorded on the chip
(``recorded_v5e_scopes.xplane.pb``: a tiny two-layer latent-attention /
routed-expert LM's ``serving_tick`` and ``serving_prefill_128`` and a tiny
``train_step``, a few executions each; ``scripts/record_scope_fixture.py``
records it and drops the stats and HLO protos no reader reads), on synthetic
events, and on the older recorded trace of a program without any scope, where
every reader returns ``None``."""

import os
import shutil
import subprocess
import sys

import pytest

from benchmark.harness import (loader, program_trace as pt,
                               scope_trace as st, trace_reduce as tr)

HERE = os.path.dirname(os.path.abspath(__file__))
SCOPES = os.path.join(HERE, "recorded_v5e_scopes.xplane.pb")
BARE = os.path.join(HERE, "recorded_v5e_tiny.xplane.pb")
SERVE = "deepseek-v3-ep16-serve-steady"
TRAIN = "gpt2-medium-train-s1024"
#: the split's readers that the two recorded programs' cells report (a
#: bucket of another family's longer table, ``tick_ms.ssm_*``, is silent here)
NEW = [m for m in loader.manifest()["per_layer"]
       if m["name"].startswith(("tick_ms.", "step_ms."))
       and {SERVE, TRAIN} & set(m["workloads"])]
SERVED = {"embed_head", "attn_proj", "attn_core", "cache_write", "ffn_dense",
          "moe_route", "moe_experts"}


def test_the_fixture_is_small():
    assert os.path.getsize(SCOPES) < 1_000_000


def test_the_metadata_walk_finds_the_programs_and_their_scopes():
    meta = st.read_metadata(SCOPES)
    names = set(meta["programs"].values())
    assert {"serving_tick", "serving_prefill_128", "train_step"} <= names
    chip0 = min(meta["ops"])
    paths, ambiguous = st.scope_paths(meta, chip0, "serving_tick")
    assert not ambiguous
    assert all(p.startswith("jit(serving_tick)/") or "/" not in p
               for p in paths.values())
    found = {st.leaf_of(p)[1] for p in paths.values()}
    assert {"tick/embed", "tick/head", "block/mla/proj", "block/mla/core",
            "tick/work_list", "cache_write", "block/mlp", "block/moe/route",
            "block/moe/dispatch", "block/moe/gmm",
            "block/moe/shared"} <= found
    # the kernels keep their names, and are booked where they were written
    kernels = {n: p for n, p in paths.items() if n.endswith(tr.KERNEL_TAG)}
    assert {st.bucket_of(p) for n, p in kernels.items()
            if "decode_attn_mla" in n} == {"attn_core"}
    assert {st.bucket_of(p) for n, p in kernels.items()
            if "moe_gmm" in n} == {"moe_experts"}
    # another program's operations are not this one's
    train, _ = st.scope_paths(meta, chip0, "train_step")
    assert {st.bucket_of(p) for p in train.values()} >= {"fwd", "bwd",
                                                          "optimizer"}
    assert not any("serving_tick" in p for p in train.values())


@pytest.mark.parametrize("prefix,want", [
    ("serving_tick", SERVED), ("serving_prefill", SERVED),
    ("train_step", {"fwd", "bwd", "optimizer"})])
def test_a_programs_buckets_sum_to_its_mean_execution_time(prefix, want):
    out = st.split(SCOPES, prefix)
    modules = pt.read_modules(SCOPES)
    runs = [(s, e) for n, s, e in modules[min(modules)]
            if n.startswith(prefix)]
    assert out["executions"] == len(runs) >= 2
    mean = sum(e - s for s, e in runs) / 1e6 / len(runs)
    assert out["mean_ms"] == pytest.approx(mean)
    assert set(out["buckets"]) == want
    assert all(ms > 0 for ms in out["buckets"].values())
    total = sum(out["buckets"].values()) + sum(out["unscoped"].values())
    assert total == pytest.approx(out["mean_ms"], rel=1e-9)
    assert out["unscoped"]["ambiguous"] == 0.0
    assert out["unscoped"]["bubbles"] >= 0.0
    # the rows of the table are the buckets, split by leaf scope
    by_bucket = {}
    for bucket, leaf, ms, calls in out["leaves"]:
        by_bucket[bucket] = by_bucket.get(bucket, 0.0) + ms
        assert calls > 0
    for bucket, ms in out["buckets"].items():
        assert by_bucket[bucket] == pytest.approx(ms)
    if prefix == "train_step":
        leaves = {(b, leaf) for b, leaf, _, _ in out["leaves"]}
        assert {("fwd", "block/attn"), ("bwd", "block/attn"),
                ("fwd", "block/mlp"), ("bwd", "block/mlp"),
                ("fwd", "head_ce"), ("bwd", "head_ce")} <= leaves


def test_self_time_is_trace_reduces_rule_on_the_recorded_operations():
    """``self_ns`` walks only the operations that hold others; the rule is
    ``trace_reduce.self_times``', which walks every one: the same seconds a
    name on the chip's own events (``while`` loops with their bodies)."""
    import numpy as np

    ops = sorted(tr.read_events(SCOPES)["devices"][0],
                 key=lambda ev: (ev[1], -ev[2]))
    names, start, end = zip(*ops)
    start, end = np.asarray(start, float), np.asarray(end, float)
    assert (end[:-1] > start[1:]).sum() >= 10          # it holds such loops
    mine = {}
    for name, ns in zip(names, st.self_ns(start, end)):
        mine[name] = mine.get(name, 0.0) + ns / 1e9
    want = tr.self_times(ops)
    assert mine.keys() == want.keys()
    for name, seconds in want.items():      # to the nanosecond a name
        assert mine[name] == pytest.approx(seconds, abs=2e-9), name


def test_self_time_and_an_ambiguous_name_on_synthetic_events():
    """Two executions of 100 ns; a ``while`` of 60 ns holds a 40 ns child;
    ``fusion.3`` is written under two scopes and is nobody's."""
    runs = [(0, 100), (200, 300)]
    ops = [("while.1", 10, 70), ("fusion.2", 20, 60), ("fusion.3", 70, 90),
           ("while.1", 210, 270), ("fusion.2", 220, 260),
           ("fusion.3", 270, 290), ("copy.9", 292, 296),
           ("fusion.2", 400, 440)]                     # outside: not booked
    paths = {"while.1": "jit(serving_tick)/tick/layer/block/mlp/block/moe/"
                        "dispatch/while",
             "fusion.2": "jit(serving_tick)/tick/layer/block/mlp/block/moe/"
                         "dispatch/while/body/add",
             "fusion.3": "jit(serving_tick)/tick/head/dot_general"}
    out = st.split_events(ops, runs, paths, {"fusion.3"})
    assert out["executions"] == 2 and out["mean_ms"] == pytest.approx(1e-4)
    assert out["buckets"] == {"moe_route": pytest.approx(60e-6)}
    assert out["unscoped"] == {
        "ambiguous": pytest.approx(20e-6),          # fusion.3, both runs
        "no_scope": pytest.approx(2e-6),            # copy.9 has no metadata
        "bubbles": pytest.approx(18e-6)}
    assert [(b, leaf) for b, leaf, _, _ in out["leaves"]] == [
        ("moe_route", "block/moe/dispatch"), ("unscoped", "ambiguous"),
        ("unscoped", "no_scope")]
    assert "block/moe/dispatch" in st.table(out)


def test_names_shared_by_two_scopes_are_found_in_the_metadata():
    meta = {"programs": {7: "serving_prefill_1024", 8: "serving_prefill_2048",
                         9: "serving_tick"},
            "ops": {0: [
                ("%fusion.1 = f32[] fusion()", 7, "jit(a)/prefill/head/add"),
                ("%fusion.1 = f32[8] fusion()", 8, "jit(a)/block/mlp/add"),
                ("%fusion.2 = f32[] fusion()", 7, "jit(a)/block/mlp/mul"),
                ("%fusion.2 = f32[8] fusion()", 8, "jit(a)/block/mlp/mul"),
                ("%fusion.1 = f32[] fusion()", 9, "jit(b)/tick/embed/add"),
                ("%copy.4 = f32[] copy()", None, "jit(a)/cache_write/copy"),
                ("%copy.5 = f32[] copy()", 7, None)]}}
    paths, ambiguous = st.scope_paths(meta, 0, "serving_prefill")
    assert ambiguous == {"fusion.1"}            # two prefills, two scopes
    assert paths["fusion.2"] == "jit(a)/block/mlp/mul"
    assert "copy.4" in paths and "copy.5" not in paths
    one, none = st.scope_paths(meta, 0, "serving_prefill_1024")
    assert not none and one["fusion.1"] == "jit(a)/prefill/head/add"
    tick, _ = st.scope_paths(meta, 0, "serving_tick")
    assert tick["fusion.1"] == "jit(b)/tick/embed/add"


def _as_a_traced_run(tmp_path, monkeypatch, recorded, cell):
    d = tmp_path / cell / "plugins" / "profile" / "2026_09_29"
    d.mkdir(parents=True)
    shutil.copy(recorded, d / "vm.xplane.pb")
    monkeypatch.setattr(pt, "TRACE_ROOT", str(tmp_path))
    pt._CACHE.clear()
    st._SPLITS.clear()
    return tr.reduce_file(str(d / "vm.xplane.pb"), ())


@pytest.mark.parametrize("metric", NEW, ids=[m["name"] for m in NEW])
def test_every_reader_reads_the_recorded_trace(tmp_path, monkeypatch, metric):
    """Laid out as a ``--trace 1`` run leaves it: every reader gives its
    bucket of the split, the named ones above 0, and a cell's readers sum
    to the program's mean execution time."""
    name = metric["name"]
    cell, prefix = ((SERVE, "serving_tick") if name.startswith("tick_ms.")
                    else (TRAIN, "train_step"))
    reduced = _as_a_traced_run(tmp_path, monkeypatch, SCOPES, cell)
    run, lines = {"chips": 1}, []
    # (``device.say`` bound its stream when it was imported: no capture
    # fixture sees it)
    monkeypatch.setattr(st.device, "say", lambda _, text: lines.append(text))
    value = loader.module("layer_metrics", name).read(reduced, [], run)
    out = st.split(SCOPES, prefix)
    bucket = name.split(".", 1)[1]
    said = "\n".join(lines)
    if bucket == st.UNSCOPED:
        assert value == pytest.approx(sum(out["unscoped"].values()))
        assert value >= 0.0 and "bubbles" in said
    else:
        assert value == pytest.approx(out["buckets"][bucket]) and value > 0
    assert ("executions, mean" in said) == (bucket in ("embed_head", "fwd"))
    family = [m["name"] for m in NEW
              if m["name"].startswith(name.split(".")[0] + ".")]
    total = sum(loader.module("layer_metrics", n).read(reduced, [], run)
                for n in family)
    assert total == pytest.approx(out["mean_ms"])
    assert metric["source"] == "device_trace" and metric["unit"] == "ms"


@pytest.mark.parametrize("metric", NEW, ids=[m["name"] for m in NEW])
def test_every_reader_is_silent_on_a_trace_without_scopes(
        tmp_path, monkeypatch, metric):
    """tanh(x @ x) on a v5e: operations with a ``tf_op`` and no scope in
    it, no named program — ``None``, and nothing raised; and without any
    trace of this run at all."""
    reduced = _as_a_traced_run(tmp_path, monkeypatch, BARE, SERVE)
    read = loader.module("layer_metrics", metric["name"]).read
    assert pt.load(reduced) is not None
    assert read(reduced, [], {"chips": 1}) is None
    assert st.split(BARE, "_lambda") is None     # the program ran; no scope
    monkeypatch.setattr(pt, "TRACE_ROOT", str(tmp_path / "nothing"))
    assert read(reduced, [], {"chips": 1}) is None


def test_the_command_line_prints_the_table():
    script = os.path.join(os.path.dirname(HERE), "harness", "scope_trace.py")
    got = subprocess.run(
        [sys.executable, script, SCOPES, "--program", "serving_tick"],
        capture_output=True, text=True, env=dict(os.environ,
                                                 JAX_PLATFORMS="cpu"))
    assert got.returncode == 0, got.stderr
    assert "executions, mean" in got.stdout
    for leaf in ("block/moe/dispatch", "block/mla/proj", "tick/work_list",
                 "bubbles"):
        assert leaf in got.stdout
    none = subprocess.run(
        [sys.executable, script, BARE, "--program", "serving_tick"],
        capture_output=True, text=True, env=dict(os.environ,
                                                 JAX_PLATFORMS="cpu"))
    assert none.returncode == 1 and "no execution" in none.stdout
