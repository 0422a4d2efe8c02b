"""The fixed-rate serving cells' own data: each runs at four fifths of a
knee that its sweep file shows, and judges its tails on 5 % of the requests
a traced run counts.  And the sweep's test of "sustained" on made-up
backlogs."""

import json
import os

import pytest

from benchmark import sweep_rate
from benchmark.harness import device, loader

FIXED_RATE = ["gpt2-medium-serve-steady", "deepseek-v3-ep16-serve-steady",
              "kimi-linear-ep16-serve-decode", "laguna-xs2-ep16-serve-mixed",
              "jamba2-3b-serve-chat-short"]


def test_every_open_loop_cell_is_listed():
    man = loader.manifest()
    served = [w["name"] for w in man["workloads"]
              if loader.cell(man, w["name"])["traffic"]["driver"]
              == "serve_open_loop"]
    assert served == FIXED_RATE


@pytest.mark.parametrize("name", FIXED_RATE)
def test_a_fixed_rate_cell_runs_at_four_fifths_of_a_knee_its_sweep_shows(name):
    man = loader.manifest()
    tr = loader.cell(man, name)["traffic"]
    cells = os.path.join(loader.BENCH, "cells")
    own = json.load(open(os.path.join(cells, name + ".json")))
    assert tr["rate_per_s"] == pytest.approx(0.8 * own["knee_rate_per_s"])
    sweep = json.load(open(os.path.join(cells, name + ".sweep.json")))
    assert sweep["workload"] == name
    at_knee = [r for r in sweep["rows"]
               if r["rate_per_s"] == own["knee_rate_per_s"]]
    assert len(at_knee) == 1 and at_knee[0]["failed"] == 0
    # the tail's samples: 5 % of the window's requests as a TRACED run
    # counts them, those due before its slice
    before_slice = man["run_seconds"] - tr["trace_seconds"] - 0.5
    assert tr["min_tail_samples"] == int(
        0.05 * tr["rate_per_s"] * before_slice)


def test_the_steady_chat_mix_holds_no_stale_rate():
    """Only ``gpt2-medium-serve-steady`` uses the mix: its own numbers and
    the cell's agree, so no reader meets the rate of an older tick."""
    man = loader.manifest()
    users = [w["name"] for w in man["workloads"]
             if w["traffic"] == "chat-steady"]
    assert users == ["gpt2-medium-serve-steady"]
    mix = json.load(open(os.path.join(loader.BENCH, "traffic",
                                      "chat-steady.json")))
    tr = loader.cell(man, users[0])["traffic"]
    assert (mix["rate_per_s"], mix["min_tail_samples"]) == (
        tr["rate_per_s"], tr["min_tail_samples"])


@pytest.mark.parametrize("failed, mid, end, verdict", [
    (0, 28, 37, True),       # the Jamba sweep's 12/s: drained in 3.7 s
    (0, 50, 61, True),       # its 16/s
    (0, 127, 100, True),     # its knee
    (0, 251, 357, False),    # 28/s: the queue grew all through the window
    (0, 0, 2, True),         # an all but empty engine
    (0, 0, 3, False),
    (1, 28, 20, False),      # a failed request is never sustained
    (0, None, 5, False),     # a window too short to have a middle
])
def test_sustained_allows_the_schedules_own_fluctuation(failed, mid, end,
                                                        verdict):
    assert sweep_rate.sustained(failed, mid, end) is verdict


def test_deepest_queue_counts_the_requests_waiting_at_once():
    class Handle:
        def __init__(self, submitted, admitted=None):
            self.timestamps = {"submitted": submitted}
            if admitted is not None:
                self.timestamps["prefill_start"] = admitted

    recs = [{"handle": Handle(0.0, 1.0)}, {"handle": Handle(0.5, 2.0)},
            {"handle": Handle(0.7)}, {"handle": Handle(2.5, 2.6)},
            {"handle": None}]
    # at 0.7 three wait; the third is never admitted and waits to the end
    assert sweep_rate.deepest_queue(recs) == 3
    assert sweep_rate.deepest_queue([]) == 0


@pytest.mark.parametrize("warm_hit", [True, False])
def test_seed_0_compiles_nothing_in_the_window_once_a_hit_is_warmed(
        capsys, monkeypatch, warm_hit):
    """``--seed 0`` draws its first window prompt from the warm-up's own
    random stream, so it hits the prefix cache: without a hit in the warm-up
    the engine builds ``serving_prefix_copy`` inside the window and the run
    prints ``correct: false`` with every comparison passing."""
    import time

    from benchmark import run
    from conftest import TINY_GPT2, TINY_SERVE

    real = loader.module

    def module(kind, name):
        mod = real(kind, name)
        if (kind, name) == ("families", "gpt2") and not warm_hit:
            init = mod.Server.__init__

            def cold(self, ctx):
                init(self, ctx)
                self.warm_prefix_hit = False
            mod.Server.__init__ = cold
        return mod

    said = []
    monkeypatch.setattr(loader, "module", module)
    monkeypatch.setattr(device, "say",
                        lambda devices, text, file=None: said.append(text))
    rc = run.main(["--workload", "gpt2-medium-serve-steady", "--seed", "0",
                   "--seconds", "2", "--trace", "0"], _allow_cpu=True,
                  _sizes={"config": TINY_GPT2, "traffic": TINY_SERVE},
                  _t0=time.perf_counter())
    assert rc == 0
    checks = [ln.split("check ")[1] for ln in said if "check " in ln]
    assert len(checks) == 3
    assert [c.split(":")[0] for c in checks if "NOT OK" in c] == (
        [] if warm_hit else ["compiles_in_window"])
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last)["correct"] is warm_hit
