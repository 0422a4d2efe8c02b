"""Heterogeneous fleet + rolling weight upgrade tests (ISSUE 18).

Cheapest first:

* **Registry units** (jax-free): variant/generation bookkeeping,
  immutable published generations, machine-readable refusals.
* **Model-keyed index units** (jax-free): claims carry ``model_id``;
  a pinned match never crosses variants, and the near-miss (the only
  claims belong to another variant) is a counted ``model_mismatch``
  stale fallback.
* **Two-variant local fleet** (devices): one ``FleetRouter`` fronting
  workers with DIFFERENT weights; ``model_id`` pins routing and each
  pinned request decodes token-exactly against its own variant's
  ``lm_generate`` oracle; an unknown model is a machine-readable
  rejection.
* **Rolling weight upgrade** (devices): a checkpoint-v2 generation
  (saved SHARDED, installed via ``reshard_host``) rolls across a live
  2-worker fleet — zero fleet restart, ``drain_shed == 0``,
  token-exact pre/post parity on a pinned greedy request, and every
  worker left serving generation 2.
"""

import time

import numpy as np
import pytest

from chainermn_tpu.serving.fleet_cache import FleetCacheIndex
from chainermn_tpu.serving.models import ModelRegistry, ModelVariant

VOCAB, D, HEADS, LAYERS = 32, 16, 4, 2
HEAD_DIM = D // HEADS


# ---------------------------------------------------------------------------
# registry units (no jax)
# ---------------------------------------------------------------------------

def test_registry_register_get_latest():
    reg = ModelRegistry()
    reg.register(ModelVariant("small", {"w": 1}, head_dim=4))
    reg.register(ModelVariant("small", {"w": 2}, head_dim=4,
                              generation=2))
    reg.register(ModelVariant("big", {"w": 3}, head_dim=8,
                              worker_kwargs={"n_slots": 2}))
    assert reg.ids() == ["big", "small"]
    assert "small" in reg and "nope" not in reg
    assert reg.get("small").params == {"w": 2}          # latest wins
    assert reg.get("small", generation=1).params == {"w": 1}
    assert reg.latest_generation("small") == 2
    assert reg.get("big").worker_kwargs == {"n_slots": 2}


def test_registry_refusals():
    reg = ModelRegistry()
    reg.register(ModelVariant("m", {}, head_dim=4))
    with pytest.raises(ValueError, match="already registered"):
        reg.register(ModelVariant("m", {}, head_dim=4))
    with pytest.raises(KeyError, match="unknown model_id"):
        reg.get("ghost")
    with pytest.raises(KeyError, match="no generation 9"):
        reg.get("m", generation=9)
    with pytest.raises(ValueError, match="generation"):
        ModelVariant("m", {}, head_dim=4, generation=0)
    with pytest.raises(ValueError, match="model_id"):
        ModelVariant("", {}, head_dim=4)


# ---------------------------------------------------------------------------
# model-keyed index units (no jax)
# ---------------------------------------------------------------------------

def _geom(mid, n_layers=2, kv_dim=16):
    return {"n_layers": n_layers, "kv_dim": kv_dim,
            "dtype": "float32", "model_id": mid}


def test_index_claims_are_model_keyed():
    idx = FleetCacheIndex()
    idx.insert("wa", 1, [1, 2, 3, 4], 4, geom=_geom("a"))
    idx.insert("wb", 1, [1, 2, 3, 4], 4, geom=_geom("b"))
    rec, mlen = idx.match([1, 2, 3, 4, 5], model_id="a")
    assert rec.worker == "wa" and rec.model_id == "a" and mlen == 4
    rec, _ = idx.match([1, 2, 3, 4, 5], model_id="b")
    assert rec.worker == "wb"
    # unpinned match still works (single-model fleets unchanged)
    rec, mlen = idx.match([1, 2, 3, 4, 5])
    assert rec is not None and mlen == 4
    assert idx.stale_fallbacks == {}
    idx.check_invariants()


def test_index_cross_model_near_miss_counted():
    idx = FleetCacheIndex()
    idx.insert("wa", 1, [7, 8, 9, 10], 4, geom=_geom("a"))
    rec, mlen = idx.match([7, 8, 9, 10, 11], model_id="b")
    assert rec is None and mlen == 0
    assert idx.stale_fallbacks == {"model_mismatch": 1}
    assert idx.misses == 1
    # a pinned query against an UNLABELED legacy claim is refused too
    idx.insert("w0", 1, [5, 6, 7, 8], 4, geom=None)
    rec, _ = idx.match([5, 6, 7, 8, 9], model_id="a")
    assert rec is None
    assert idx.stale_fallbacks["model_mismatch"] == 2
    # peek face distorts nothing
    before = dict(idx.stale_fallbacks)
    idx.match([7, 8, 9, 10, 11], model_id="b", count=False)
    assert idx.stale_fallbacks == before


# ---------------------------------------------------------------------------
# two-variant fleet + rolling upgrade (devices)
# ---------------------------------------------------------------------------

def _params(seed=0):
    import jax
    from chainermn_tpu.parallel import init_tp_transformer_lm

    return init_tp_transformer_lm(
        jax.random.PRNGKey(seed), VOCAB, D, HEADS, LAYERS, max_len=64,
        pos_impl="rope")


def _mesh(devices):
    import chainermn_tpu as mn

    return mn.make_nd_mesh(("model",), (1,), devices[:1])


def _oracle(params, mesh, prompt, max_new):
    from chainermn_tpu.parallel import make_lm_generator

    gen = make_lm_generator(mesh, "model", head_dim=HEAD_DIM,
                            max_new_tokens=max_new)
    return np.asarray(gen(params, np.asarray(prompt)[None]))[0].tolist()


def _drive_until_terminal(router, runtimes, handles, timeout=90):
    t0 = time.time()
    while any(h.status not in ("done", "evicted") for h in handles):
        assert time.time() - t0 < timeout, (
            "fleet hung: " + str([(h.status, h.finish_reason)
                                  for h in handles]))
        time.sleep(0.005)


def test_heterogeneous_fleet_routes_by_model(devices, tmp_path):
    from chainermn_tpu.serving.fleet import build_local_fleet
    from chainermn_tpu.serving.scheduler import AdmissionError

    mesh = _mesh(devices)
    p_small, p_big = _params(0), _params(1)
    reg = ModelRegistry()
    reg.register(ModelVariant("small", p_small, head_dim=HEAD_DIM))
    reg.register(ModelVariant("big", p_big, head_dim=HEAD_DIM))
    wk = dict(n_slots=2, max_total=24, mesh=mesh)
    router, runtimes = build_local_fleet(
        None, {"engine": ["small", "big"]}, registry=reg,
        # wide lease window: first-prefill compiles stall the GIL for
        # seconds and this test is about routing, not detection (0.34 s
        # still misread one as death under the tier-1 run's six workers,
        # 1 loaded run in 24: about a second now)
        beat_interval_s=0.02, miss_beats=48, worker_kwargs=wk,
        bundle_dir=str(tmp_path / "bundles"))
    try:
        import threading
        threads = [threading.Thread(target=rt.run, daemon=True)
                   for rt in runtimes]
        for t in threads:
            t.start()
        router.start()
        prompt = [3, 1, 4, 1, 5]
        hs = router.submit(prompt, 6, model_id="small")
        hb = router.submit(prompt, 6, model_id="big")
        _drive_until_terminal(router, runtimes, [hs, hb])
        # each pinned request decoded on ITS variant, token-exactly
        assert hs.tokens == _oracle(p_small, mesh, prompt, 6)
        assert hb.tokens == _oracle(p_big, mesh, prompt, 6)
        assert hs.tokens != hb.tokens, "variants decode identically"
        # workers adopted their identity onto the wire
        by_model = {w.model_id: w for w in router.workers.values()}
        assert set(by_model) == {"small", "big"}
        assert all(w.weights_generation == 1
                   for w in router.workers.values())
        with pytest.raises(AdmissionError) as ei:
            router.submit(prompt, 4, model_id="ghost")
        assert ei.value.reason == "no_model_worker"
        m = router.metrics()
        assert m["fleet/rejected/no_model_worker"] == 1
    finally:
        for rt in runtimes:
            rt.finished = True
        router.close()


def test_rolling_upgrade_zero_shed_token_exact(devices, tmp_path):
    import jax
    import threading

    from chainermn_tpu.serving.fleet import (build_local_fleet,
                                             rolling_upgrade)

    mesh = _mesh(devices)
    params = _params(0)
    wk = dict(n_slots=2, max_total=24, mesh=mesh)
    router, runtimes = build_local_fleet(
        params, {"engine": 2}, head_dim=HEAD_DIM,
        beat_interval_s=0.02, miss_beats=16, worker_kwargs=wk,
        bundle_dir=str(tmp_path / "bundles"))
    threads = [threading.Thread(target=rt.run, daemon=True)
               for rt in runtimes]
    for t in threads:
        t.start()
    router.start()
    try:
        pinned = [2, 7, 1, 8, 2]
        before = router.submit(pinned, 6)
        _drive_until_terminal(router, runtimes, [before])
        want = _oracle(params, mesh, pinned, 6)
        assert before.tokens == want

        # checkpoint v2: the same values RE-SAVED by a 2-process world
        # with the embedding row-sharded — reshard_host must
        # reassemble it bit-for-bit (that is what makes pre/post
        # token parity a test of the INSTALL path, not of luck)
        params_np = jax.tree_util.tree_map(np.asarray, params)
        layout = jax.tree_util.tree_map(lambda x: None, params_np)
        layout["embed"] = 0
        shards = []
        for i in range(2):
            s = jax.tree_util.tree_map(lambda x: x, params_np)
            s["embed"] = np.split(params_np["embed"], 2, axis=0)[i]
            shards.append(s)

        old_names = set(router.workers)
        report = rolling_upgrade(router, runtimes, shards, layout,
                                 generation=2, head_dim=HEAD_DIM,
                                 worker_kwargs=wk, timeout_s=60.0)
        assert report["generation"] == 2
        assert report["drain_shed"] == 0          # the acceptance bar
        assert len(report["upgraded"]) == 2
        # zero fleet restart: the old incarnations DRAINED (nothing
        # died) and both replacements are live under generation 2
        for name in old_names:
            assert router.workers[name].state == "drained"
        live = [w for w in router.workers.values()
                if w.state in ("starting", "live")]
        assert len(live) == 2
        for w in live:
            assert w.name not in old_names

        after = router.submit(pinned, 6)
        _drive_until_terminal(router, runtimes, [after])
        assert after.tokens == want               # token-exact parity
        for w in live:
            assert w.weights_generation == 2      # adopted off the wire

        # a second call refuses: nothing is below generation 2
        with pytest.raises(ValueError, match="no live engine worker"):
            rolling_upgrade(router, runtimes, shards, layout,
                            generation=2, head_dim=HEAD_DIM,
                            worker_kwargs=wk)
    finally:
        for rt in runtimes:
            rt.finished = True
        router.close()
