"""The reader of the pool's donation counters (ISSUE 28): on synthetic
input, and silent on a program that has neither counter (the parent)."""

import pytest

from benchmark.harness import loader

READ = loader.module("layer_metrics", "pool_donated_share").read


def test_pool_donated_share_from_the_engines_counters():
    run = {"engine_metrics": {"serving/pool_calls": 200.0,
                              "serving/pool_calls_donated": 200.0}}
    assert READ({}, [], run) == pytest.approx(100.0)
    # a backend that declines the donation copies: 0, not nothing
    run["engine_metrics"]["serving/pool_calls_donated"] = 0.0
    assert READ({}, [], run) == 0.0
    # the parent has no such counters; an engine that made no call has 0 of 0
    assert READ({}, [], {"engine_metrics": {
        "serving/tick_calls": 9.0}}) is None
    assert READ({}, [], {}) is None
    assert READ({}, [], {"engine_metrics": {
        "serving/pool_calls": 0.0, "serving/pool_calls_donated": 0.0}}) is None
