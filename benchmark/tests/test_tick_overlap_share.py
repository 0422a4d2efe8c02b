"""The reader of the engine's overlap counter (ISSUE 32): on synthetic
input, and silent on a program that has no such counter (the parent)."""

import pytest

from benchmark.harness import loader

READ = loader.module("layer_metrics", "tick_overlap_share").read


def test_tick_overlap_share_from_the_engines_counters():
    run = {"engine_metrics": {"serving/tick_calls": 400.0,
                              "serving/tick_launches_overlapped": 380.0}}
    assert READ({}, [], run) == pytest.approx(95.0)
    # a program that reads every tick before it launches the next: 0
    run["engine_metrics"]["serving/tick_launches_overlapped"] = 0.0
    assert READ({}, [], run) == 0.0


@pytest.mark.parametrize("run", [
    {"engine_metrics": {"serving/tick_calls": 9.0}},     # the parent
    {},
    {"engine_metrics": {"serving/tick_calls": 0.0,       # no tick at all
                        "serving/tick_launches_overlapped": 0.0}},
])
def test_tick_overlap_share_reads_nothing_where_there_is_nothing(run):
    assert READ({}, [], run) is None
