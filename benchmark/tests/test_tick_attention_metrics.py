"""The two readers of the flash-decode tick (ISSUE 26): the kernel's device
time a tick, and the share of the pool's cache blocks the ticks read — on
synthetic input, and silent on a program that has neither."""

import pytest

from benchmark.harness import loader, program_trace as pt, trace_reduce as tr

MS_PER_TICK = loader.module("layer_metrics", "decode_attn_ms_per_tick").read
READ_SHARE = loader.module("layer_metrics", "tick_cache_read_share").read


def test_decode_attn_ms_per_tick_divides_by_the_slices_ticks(monkeypatch):
    tag = tr.KERNEL_TAG
    trace = {"op_seconds": {
        f"decode_attn_mha.3{tag}": 0.004, f"decode_attn_mha.27{tag}": 0.006,
        f"kv_cache_write.1{tag}": 0.5, "decode_attn_like_fusion.2": 0.3}}
    view = {"modules": [("serving_prefill_256", 0, 1), ("serving_tick", 2, 3),
                        ("serving_tick", 4, 5)]}
    monkeypatch.setattr(pt, "load", lambda t: view)
    assert MS_PER_TICK(trace, [], {}) == pytest.approx(5.0)
    # no tick in the slice, or another run's trace: nothing to divide by
    monkeypatch.setattr(pt, "load", lambda t: {"modules": []})
    assert MS_PER_TICK(trace, [], {}) is None
    monkeypatch.setattr(pt, "load", lambda t: None)
    assert MS_PER_TICK(trace, [], {}) is None


def test_a_tick_on_the_einsum_path_has_no_kernel_time(monkeypatch):
    """The parent of ISSUE 26: ticks in the slice, no ``decode_attn``
    kernel among the operations — the metric is left out, nothing raised."""
    monkeypatch.setattr(pt, "load",
                        lambda t: {"modules": [("serving_tick", 2, 3)]})
    trace = {"op_seconds": {"convert.7": 0.01, "fusion.2": 0.02}}
    assert MS_PER_TICK(trace, [], {}) is None
    assert MS_PER_TICK({}, [], {}) is None


def test_tick_cache_read_share_from_the_engines_counters():
    run = {"engine_metrics": {"serving/tick_cache_blocks_read": 48.0,
                              "serving/tick_cache_blocks_total": 64.0}}
    assert READ_SHARE({}, [], run) == pytest.approx(75.0)
    assert READ_SHARE({}, [], {"engine_metrics": {}}) is None
    assert READ_SHARE({}, [], {}) is None
    # an engine that has not ticked yet holds 0 of 0
    assert READ_SHARE({}, [], {"engine_metrics": {
        "serving/tick_cache_blocks_read": 0.0,
        "serving/tick_cache_blocks_total": 0.0}}) is None
