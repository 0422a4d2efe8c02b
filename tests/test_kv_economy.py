"""Fleet-global KV economy tests (ISSUE 12), fast tier.

Five layers, cheapest first:

* **Spill-store units** (jax-free): bounded LRU byte budget, longest-
  prefix match semantics, oversize refusal, eviction hook.
* **Fleet-index fuzz** (jax-free): the router's global radix trie vs
  per-worker ground truth under randomized interleavings of announce /
  evict / spill-demote / death-fence / snapshot re-admission — with
  every announce delivered, the index claims EXACTLY what live workers
  hold; a stale claim (announce still in flight) resolves to the
  counted fallback, never a wedge.
* **CRC integrity** (devices): every ``kv_transfer.v1`` payload is
  CRC32-stamped at pack; an injected bit-flip is REFUSED at
  ``unpack_into`` — at the transfer plane, at the engine's spill
  restore (counted, degrades to re-prefill, still token-exact), and at
  a fleet pull landing (reservation cancelled, counted, re-prefill).
* **Engine spill→restore** (devices): a scavenged hot prefix spills to
  host RAM byte-exactly and a later matching prompt restores through
  the compiled inject path — token-exact vs ``lm_generate``.
* **Fleet economy + chaos** (devices): 4-worker shared-prefix workload
  with fleet-wide ``prefill_calls == 1`` per unique prefix (remote
  hits served by PULL); the slab owner killed mid-pull → the request
  completes token-exact via local re-prefill, a ``remote_pull_fault``
  bundle names worker+lane, and nothing hangs or leaks a reservation.

The real-process SIGKILL-mid-pull acceptance lives in
tests/test_chaos_serving.py (slow tier).
"""

import json
import os
import pickle
import random
import subprocess
import sys
import time

import numpy as np
import pytest

from chainermn_tpu.serving.fleet_cache import FleetCacheIndex
from chainermn_tpu.serving.spill import HostSpillStore

ROOT = os.path.join(os.path.dirname(__file__), "..")

VOCAB, D, HEADS, LAYERS = 32, 16, 4, 2
HEAD_DIM = D // HEADS


# ---------------------------------------------------------------------------
# spill-store units (no jax)
# ---------------------------------------------------------------------------

def test_spill_store_lru_budget_and_match():
    evicted = []
    store = HostSpillStore(capacity_bytes=100,
                           on_evict=lambda seq, ln: evicted.append(seq))
    assert store.put((1, 2, 3), 3, b"x" * 40)
    assert store.put((1, 2, 4, 5), 4, b"y" * 40)
    assert store.n_entries == 2 and store.bytes_held == 80
    # longest spilled prefix, capped at len(prompt)-1 and entry length
    seq, mlen = store.match([1, 2, 4, 5, 9])
    assert seq == (1, 2, 4, 5) and mlen == 4
    seq, mlen = store.match([1, 2, 3, 7])
    assert seq == (1, 2, 3) and mlen == 3
    # cap at len(prompt)-1: the last prompt token must run live
    seq, mlen = store.match([1, 2, 3])
    assert mlen == 2
    assert store.match([9, 9, 9]) is None
    # a third entry busts the budget and evicts the least recently
    # USED: the cap-2 match of [1,2,3] was a TIE served by (1,2,4,5)
    # (first iterated), so (1,2,3) is the LRU victim
    assert store.put((7, 8, 9), 3, b"z" * 40)
    assert store.n_entries == 2 and evicted == [(1, 2, 3)]
    # oversize payload refused, counted, store untouched
    assert not store.put((5, 5, 5), 3, b"w" * 101)
    assert store.rejected_oversize == 1 and store.n_entries == 2
    # exact get + covering + drop
    assert store.get((7, 8, 9)) == b"z" * 40
    assert store.covering((1, 2, 4)) == b"y" * 40
    store.drop((7, 8, 9))
    assert store.get((7, 8, 9)) is None
    assert (7, 8, 9) in evicted


def test_spill_store_rejects_zero_capacity():
    with pytest.raises(ValueError, match="capacity_bytes"):
        HostSpillStore(capacity_bytes=0)


# ---------------------------------------------------------------------------
# fleet-index fuzz vs per-worker ground truth (no jax)
# ---------------------------------------------------------------------------

def _random_seq(rng, shared_roots):
    """Token sequences with heavy prefix sharing (the workload shape
    the trie exists for)."""
    root = rng.choice(shared_roots)
    tail = tuple(rng.randrange(16) for _ in range(rng.randrange(0, 5)))
    return root + tail


def test_fleet_index_fuzz_vs_ground_truth():
    """Randomized announce / evict / spill-demote / death-fence /
    snapshot-readmission interleavings with DELAYED delivery: whenever
    the announce queue drains, the index holds exactly the live
    workers' ground truth; while announces are in flight, any stale
    claim a match returns resolves to the counted fallback."""
    rng = random.Random(0xEC0)
    shared_roots = [tuple(rng.randrange(16) for _ in range(4))
                    for _ in range(6)]
    workers = [f"w{i}" for i in range(4)]
    idx = FleetCacheIndex(min_prefix_len=2)
    epoch = {w: 1 for w in workers}
    alive = {w: True for w in workers}
    truth = {w: {} for w in workers}     # seq -> (length, tier)
    pending = []                         # delayed announce deliveries

    def deliver(n=None):
        k = len(pending) if n is None else min(n, len(pending))
        for _ in range(k):
            fn = pending.pop(0)
            fn()

    def check_matches_truth():
        for w in workers:
            got = idx.entries_for(w)
            want = truth[w] if alive[w] else {}
            assert got == want, (w, got, want)
        idx.check_invariants()

    stale_seen = 0
    for step in range(3000):
        op = rng.random()
        w = rng.choice(workers)
        if op < 0.35:                    # insert (donation announce)
            if not alive[w]:
                continue
            seq = _random_seq(rng, shared_roots)
            truth[w][seq] = (len(seq), "hot")
            e = epoch[w]
            pending.append(lambda w=w, s=seq, e=e: idx.insert(
                w, e, s, len(s)))
        elif op < 0.55:                  # evict / spill-demote
            if not alive[w] or not truth[w]:
                continue
            seq = rng.choice(sorted(truth[w]))
            if rng.random() < 0.5 and truth[w][seq][1] == "hot":
                truth[w][seq] = (truth[w][seq][0], "spill")
                pending.append(lambda w=w, s=seq: idx.demote(w, s))
            else:
                del truth[w][seq]
                pending.append(lambda w=w, s=seq: idx.evict(w, s))
        elif op < 0.62:                  # death: fence drops everything
            if not alive[w]:
                continue
            alive[w] = False
            deliver()                    # the fence path runs in-order
            idx.drop_worker(w)
            # announces the corpse queued die with the fence upstream
            truth[w] = {}
        elif op < 0.70:                  # re-admission: snapshot rebuild
            if alive[w]:
                continue
            alive[w] = True
            epoch[w] += 1
            n = rng.randrange(0, 4)
            truth[w] = {}
            entries = []
            for _ in range(n):
                seq = _random_seq(rng, shared_roots)
                truth[w][seq] = (len(seq), "hot")
                entries.append({"seq": list(seq), "length": len(seq)})
            e = epoch[w]
            pending.append(lambda w=w, es=entries, e=e: idx.snapshot(
                w, e, es))
        elif op < 0.90:                  # match + stale resolution
            prompt = _random_seq(rng, shared_roots) + (99,)
            rec, mlen = idx.match(
                prompt, workers={x for x in workers if alive[x]})
            if rec is not None:
                assert alive[rec.worker]
                assert mlen <= len(prompt) - 1
                covered = any(
                    len(s) >= mlen and s[:mlen] == tuple(prompt[:mlen])
                    for s in truth[rec.worker])
                if not covered:
                    # a stale claim (its evict is still in `pending`):
                    # the pull-time resolution — counted, claim dropped
                    stale_seen += 1
                    idx.count_stale("stale")
                    idx.evict(rec.worker, rec.seq)
        else:                            # drain a few deliveries
            deliver(rng.randrange(1, 6))
        if step % 250 == 249:
            deliver()
            check_matches_truth()
    deliver()
    check_matches_truth()
    # the fuzz exercised the interesting paths
    assert idx.inserts > 200 and idx.evicts > 50
    assert idx.snapshots > 10 and idx.dropped_workers > 10
    assert idx.stale_fallbacks.get("stale", 0) == stale_seen


def test_fleet_index_tier_preference_and_match_for():
    idx = FleetCacheIndex()
    idx.insert("w0", 1, (1, 2, 3, 4), 4, tier="hot")
    idx.insert("w1", 1, (1, 2, 3, 4), 4, tier="spill")
    rec, mlen = idx.match([1, 2, 3, 4, 5])
    assert rec.worker == "w0" and mlen == 4     # hot beats spill
    assert idx.match_for("w1", [1, 2, 3, 4, 5]) == 4
    assert idx.match_for("w2", [1, 2, 3, 4, 5]) == 0
    # peek semantics: match_for never touched the counters
    assert idx.hits == 1 and idx.misses == 0


# ---------------------------------------------------------------------------
# CRC integrity at the transfer plane (devices)
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


def _corrupt(payload: bytes) -> bytes:
    """Flip one K/V element inside the payload, leaving the CRC stamp
    as packed — the receiver must notice."""
    data = pickle.loads(payload)
    k, v = data["rows"][0]
    k = np.array(k, copy=True)
    k.flat[0] += 1.0
    data["rows"][0] = (k, v)
    return pickle.dumps(data, protocol=pickle.HIGHEST_PROTOCOL)


def test_pack_stamps_crc_and_unpack_refuses_bitflip(devices):
    from chainermn_tpu.serving.cache_pool import CachePool
    from chainermn_tpu.serving.transfer import KvTransferPlane

    mesh = _mesh(devices)
    pool = CachePool(2, 8, LAYERS, HEADS * HEAD_DIM, np.float32, mesh,
                     "model")
    plane = KvTransferPlane()
    payload = plane.pack(pool, 0, 4, meta={"seq": [1, 2, 3, 4]})
    assert pickle.loads(payload)["crc32"] is not None
    # clean payload lands
    stats = plane.unpack_into(payload, pool, 1)
    assert stats["length"] == 4
    # bit-flipped payload REFUSED before anything touches the pool
    with pytest.raises(ValueError, match="CRC mismatch"):
        plane.unpack_into(_corrupt(payload), pool, 1)


# ---------------------------------------------------------------------------
# engine spill -> restore (devices)
# ---------------------------------------------------------------------------

def _engine(params, mesh, n_slots=2, max_total=48, **kw):
    from chainermn_tpu.serving import ServingEngine

    return ServingEngine(params, head_dim=HEAD_DIM, n_slots=n_slots,
                         max_total=max_total, mesh=mesh, **kw)


def _run_one(eng, prompt, new):
    h = eng.submit(prompt, new)
    eng.run()
    assert h.status == "done", (h.status, h.finish_reason)
    return h


def test_spill_restore_byte_exact_and_token_exact(devices):
    """Scavenging a hot rc==0 prefix slot spills its slab to host RAM;
    a later matching prompt restores it through the compiled inject
    path.  The spilled payload is byte-exact vs the slot's K/V, and
    the restored request's tokens match ``lm_generate`` exactly."""
    import jax

    params, mesh = _params(), _mesh(devices)
    eng = _engine(params, mesh)
    try:
        rng = np.random.RandomState(7)
        hot = rng.randint(0, VOCAB, 10).astype(np.int32)
        new = 6
        want = _oracle(params, mesh, hot, new)
        h = _run_one(eng, hot, new)
        assert h.tokens == want
        # the donation is in the device cache; capture its slab rows
        entry = eng.prefix_cache.entries()[0]
        rows0 = [
            (np.asarray(jax.device_get(kc[entry.slot, :entry.length])),
             np.asarray(jax.device_get(vc[entry.slot, :entry.length])))
            for kc, vc in eng.pool.caches]
        # churn: distinct prompts scavenge (and spill) the hot entry
        for i in range(3):
            _run_one(eng, rng.randint(0, VOCAB, 10).astype(np.int32),
                     2)
        assert eng.spill.spills >= 1
        payload = eng.spill.covering(tuple(int(t) for t in entry.seq))
        assert payload is not None
        packed = pickle.loads(payload)
        for (k0, v0), (kp, vp) in zip(rows0, packed["rows"]):
            np.testing.assert_array_equal(k0, kp)   # byte-exact spill
            np.testing.assert_array_equal(v0, vp)
        # the hot prompt again: device-trie miss, SPILL hit -> restore
        hits_before = eng.prefix_cache.hits
        h2 = _run_one(eng, hot, new)
        assert h2.tokens == want                    # token-exact restore
        assert eng.spill.restores == 1
        assert eng.engine.prefill_calls == 4        # hot once + 3 churn
        assert eng.prefix_cache.hits == hits_before  # not a trie hit
        # refcounts drained, pool consistent
        eng.pool.allocator.check_invariants()
        assert eng.prefix_cache.total_refcount() == 0
    finally:
        eng.close()


def test_spill_crc_refusal_degrades_to_prefill(devices):
    """An injected bit-flip in the spilled payload is refused at
    restore, counted, dropped from the store — and the request still
    completes token-exact via a normal prefill (wrong KV is never
    served)."""
    params, mesh = _params(), _mesh(devices)
    eng = _engine(params, mesh)
    try:
        rng = np.random.RandomState(8)
        hot = rng.randint(0, VOCAB, 10).astype(np.int32)
        new = 6
        want = _oracle(params, mesh, hot, new)
        _run_one(eng, hot, new)
        for _ in range(3):
            _run_one(eng, rng.randint(0, VOCAB, 10).astype(np.int32),
                     2)
        assert eng.spill.spills >= 1
        seq = next(s for s, _ in eng.spill.entries()
                   if s[:10] == tuple(int(t) for t in hot))
        eng.spill.put(seq, len(seq), _corrupt(eng.spill.get(seq)))
        prefills_before = eng.engine.prefill_calls
        h = _run_one(eng, hot, new)
        assert h.tokens == want                 # degraded, still exact
        assert eng.spill.crc_refusals == 1
        assert eng.spill.restores == 0
        assert eng.spill.get(seq) is None       # corrupt bytes dropped
        assert eng.engine.prefill_calls == prefills_before + 1
    finally:
        eng.close()


def test_spill_disabled_engine_unchanged(devices):
    params, mesh = _params(), _mesh(devices)
    eng = _engine(params, mesh, spill_bytes=0)
    try:
        assert eng.spill is None
        h = _run_one(eng, np.arange(6, dtype=np.int32), 4)
        assert h.tokens == _oracle(params, mesh,
                                   np.arange(6, dtype=np.int32), 4)
        assert "serving/spill/spills" not in eng.metrics()
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# fleet economy: global index + remote pulls (devices)
# ---------------------------------------------------------------------------

def _drive(router, runtimes, n=1, live=None):
    for _ in range(n):
        for rt in (live if live is not None else runtimes):
            rt.step()
        router.step()


def _drive_until(router, runtimes, pred, live=None, timeout=90,
                 what="condition"):
    t0 = time.time()
    while not pred():
        assert time.time() - t0 < timeout, f"fleet hung waiting: {what}"
        _drive(router, runtimes, live=live)
        time.sleep(0.001)


def _drive_until_terminal(router, runtimes, handles, live=None,
                          timeout=90):
    _drive_until(
        router, runtimes,
        lambda: all(h.status in ("done", "evicted") for h in handles),
        live=live, timeout=timeout,
        what=str([(h.status, h.finish_reason) for h in handles]))


@pytest.fixture
def economy_fleet(devices, tmp_path):
    from chainermn_tpu.serving.fleet import build_local_fleet

    params = _params()
    mesh = _mesh(devices)
    router, runtimes = build_local_fleet(
        params, {"engine": 4}, head_dim=HEAD_DIM,
        bundle_dir=str(tmp_path / "bundles"),
        beat_interval_s=0.01, miss_beats=3,
        worker_kwargs=dict(n_slots=3, max_total=24, mesh=mesh,
                           queue_capacity=8))
    yield params, mesh, router, runtimes, str(tmp_path / "bundles")
    for rt in runtimes:
        rt.finished = True
    router.close()


def test_shared_prefix_fleet_prefills_once(economy_fleet):
    """THE economy acceptance: 4 requests sharing one prompt across a
    4-worker fleet cost ONE fleet-wide prefill — the leader prefills
    and announces, every follower's local miss resolves by pulling the
    slab over the transfer plane, token-exact throughout."""
    params, mesh, router, runtimes, _ = economy_fleet
    _drive(router, runtimes, n=3)
    prompt = (np.arange(10) % VOCAB).astype(np.int32)
    new = 6
    want = _oracle(params, mesh, prompt, new)

    leader = router.submit(prompt, new)
    _drive_until_terminal(router, runtimes, [leader])
    assert leader.tokens == want
    # the donation announce lands in the global index
    _drive_until(router, runtimes,
                 lambda: router.cache_index.n_entries >= 1,
                 what="cache announce")
    owner = router.cache_index.workers()[0]

    followers = [router.submit(prompt, new) for _ in range(3)]
    _drive_until_terminal(router, runtimes, followers)
    for h in followers:
        assert h.status == "done" and h.tokens == want

    # fleet-wide prefill_calls == 1 per unique prefix (here: 1)
    prefills = sum(rt.engine.engine.prefill_calls for rt in runtimes)
    assert prefills == 1, (
        f"fleet paid {prefills} prefills for 4 requests of ONE prefix")
    m = router.metrics()
    assert m["fleet/cache/remote_pulls"] >= 1
    assert m["fleet/cache/stale_fallbacks"] == 0
    assert m["fleet/cache/crc_refusals"] == 0
    # the pulled copies were announced: the index now names multiple
    # holders of the prefix
    assert len(router.cache_index.workers()) >= 2
    # every pool clean: refcounts drained, no reservation leaked
    for rt in runtimes:
        rt.pool.allocator.check_invariants()
        assert rt.pool.reserved_count == 0
        assert rt.engine.prefix_cache.total_refcount() == 0
    # provider block renders
    state = router.introspect_state()
    assert state["cache_index"]["remote_pulls"] == \
        m["fleet/cache/remote_pulls"]
    assert owner in state["cache_index"]["per_worker"]


def test_owner_killed_mid_pull_falls_back_token_exact(economy_fleet):
    """The ISSUE 12 chaos acceptance, in-process (kill() is a SIGKILL
    to the supervisor): the slab owner dies after the pull is planned
    and before it completes — the puller's request completes
    token-exact via local re-prefill, a ``remote_pull_fault`` bundle
    names worker+lane, the fallback is counted, and no process hangs
    or leaks a reservation."""
    from chainermn_tpu.observability.flight import (find_bundles,
                                                    read_bundle)

    params, mesh, router, runtimes, bundles = economy_fleet
    _drive(router, runtimes, n=3)
    prompt = (np.arange(11) % VOCAB).astype(np.int32)
    new = 6
    want = _oracle(params, mesh, prompt, new)

    leader = router.submit(prompt, new)
    _drive_until_terminal(router, runtimes, [leader])
    _drive_until(router, runtimes,
                 lambda: router.cache_index.n_entries >= 1,
                 what="cache announce")
    owner = router.cache_index.workers()[0]
    rt_owner = next(rt for rt in runtimes if rt.name == owner)
    survivors = [rt for rt in runtimes if rt.name != owner]

    # the owner dies the instant the pull is planned — it never packs
    rt_owner.kill()
    h = router.submit(prompt, new)
    with router._lock:
        entry = router._inflight[h.trace_id]
        assert entry.get("pull"), "no pull planned — test premise broke"
        assert entry["pull"]["owner"] == owner
    _drive_until_terminal(router, runtimes, [h], live=survivors)
    assert h.status == "done" and h.tokens == want
    m = router.metrics()
    assert m["fleet/cache/stale_fallbacks/owner_lost"] == 1
    assert router.workers[owner].state == "dead"
    # the fault bundle names the worker and its lane
    paths = [p for p in find_bundles(bundles)
             if "remote_pull_fault" in os.path.basename(p)]
    assert paths, "no remote_pull_fault bundle dumped"
    rpf = (read_bundle(paths[-1])["manifest"]["extra"]
           or {})["remote_pull_fault"]
    assert rpf["owner"] == owner and owner in rpf["lane"]
    assert rpf["reason"] == "owner_lost"
    assert rpf["trace_id"] == h.trace_id
    # explain_bundle renders it (the satellite)
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "scripts",
                                      "explain_bundle.py"),
         paths[-1], "--json"],
        capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    rep = json.loads(out.stdout)
    assert rep["remote_pull_fault"]["owner"] == owner
    assert rep["remote_pull_fault"]["reason"] == "owner_lost"
    # no leaked reservation anywhere
    for rt in survivors:
        rt.pool.allocator.check_invariants()
        assert rt.pool.reserved_count == 0


def test_pull_lane_fault_cancels_reservation_and_degrades(devices):
    """The ONE caught DcnLaneError on the landing side: the
    destination's lane_get faults permanently — its reservation is
    cancelled, the nack names the lane, the fallback is counted, and
    the request completes token-exact via local re-prefill."""
    from chainermn_tpu.communicators.base import set_lane_fault_injector
    from chainermn_tpu.serving.fleet import build_local_fleet

    params = _params()
    mesh = _mesh(devices)
    router, runtimes = build_local_fleet(
        params, {"engine": 2}, head_dim=HEAD_DIM,
        beat_interval_s=0.01, miss_beats=3,
        worker_kwargs=dict(n_slots=3, max_total=24, mesh=mesh,
                           lane_timeout_s=2.0))
    try:
        _drive(router, runtimes, n=3)
        prompt = (np.arange(9) % VOCAB).astype(np.int32)
        want = _oracle(params, mesh, prompt, 5)
        leader = router.submit(prompt, 5)
        _drive_until_terminal(router, runtimes, [leader])
        _drive_until(router, runtimes,
                     lambda: router.cache_index.n_entries >= 1,
                     what="cache announce")

        def injector(lane, attempt):
            if lane.startswith("kv_transfer/get/pfx/"):
                raise RuntimeError(
                    "assertion failed: injected lane fault")

        set_lane_fault_injector(injector)
        try:
            h = router.submit(prompt, 5)
            _drive_until_terminal(router, runtimes, [h])
        finally:
            set_lane_fault_injector(None)
        assert h.status == "done" and h.tokens == want
        m = router.metrics()
        assert m["fleet/cache/stale_fallbacks/lane_fault"] == 1
        assert m["fleet/cache/remote_pulls"] == 0
        for rt in runtimes:
            rt.pool.allocator.check_invariants()
            assert rt.pool.reserved_count == 0
    finally:
        for rt in runtimes:
            rt.finished = True
        router.close()


def test_pull_crc_corruption_counted_and_degrades(devices):
    """A slab corrupted on the lane between publish and landing is
    REFUSED at the destination (CRC), counted on both sides, and the
    request re-prefills — corrupt KV is never installed."""
    from chainermn_tpu.serving.fleet import build_local_fleet

    params = _params()
    mesh = _mesh(devices)
    router, runtimes = build_local_fleet(
        params, {"engine": 2}, head_dim=HEAD_DIM,
        beat_interval_s=0.01, miss_beats=3,
        worker_kwargs=dict(n_slots=3, max_total=24, mesh=mesh))
    try:
        _drive(router, runtimes, n=3)
        prompt = (np.arange(12) % VOCAB).astype(np.int32)
        want = _oracle(params, mesh, prompt, 5)
        leader = router.submit(prompt, 5)
        _drive_until_terminal(router, runtimes, [leader])
        _drive_until(router, runtimes,
                     lambda: router.cache_index.n_entries >= 1,
                     what="cache announce")
        owner = router.cache_index.workers()[0]
        rt_owner = next(rt for rt in runtimes if rt.name == owner)
        dst = [rt for rt in runtimes if rt.name != owner]

        h = router.submit(prompt, 5)
        tag = f"pfx/{h.trace_id}"
        # drive ONLY the owner (not the router — its pump would
        # forward the install) until the slab is published, then
        # corrupt it in the store before the destination lands it
        t0 = time.time()
        while tag not in router.store.tags():
            assert time.time() - t0 < 30, "slab never published"
            rt_owner.step()
            time.sleep(0.001)
        router.store.put(tag, _corrupt(
            router.store.get(tag, timeout_s=0.0)))
        _drive_until_terminal(router, runtimes, [h])
        assert h.status == "done" and h.tokens == want
        m = router.metrics()
        assert m["fleet/cache/stale_fallbacks/crc"] == 1
        assert m["fleet/cache/crc_refusals"] == 1   # worker-side count
        for rt in dst:
            rt.pool.allocator.check_invariants()
            assert rt.pool.reserved_count == 0
    finally:
        for rt in runtimes:
            rt.finished = True
        router.close()


def test_stale_claim_degrades_to_reprefill(devices):
    """An index claim whose prefix was evicted AND whose spill copy is
    gone nacks ``stale`` at pull time: counted, the claim dropped, the
    request re-prefills token-exact — the index is a hint, never
    truth."""
    from chainermn_tpu.serving.fleet import build_local_fleet

    params = _params()
    mesh = _mesh(devices)
    router, runtimes = build_local_fleet(
        params, {"engine": 2}, head_dim=HEAD_DIM,
        beat_interval_s=0.01, miss_beats=3,
        worker_kwargs=dict(n_slots=3, max_total=24, mesh=mesh))
    try:
        _drive(router, runtimes, n=3)
        prompt = (np.arange(8) % VOCAB).astype(np.int32)
        want = _oracle(params, mesh, prompt, 5)
        leader = router.submit(prompt, 5)
        _drive_until_terminal(router, runtimes, [leader])
        _drive_until(router, runtimes,
                     lambda: router.cache_index.n_entries >= 1,
                     what="cache announce")
        owner = router.cache_index.workers()[0]
        rt_owner = next(rt for rt in runtimes if rt.name == owner)
        # silently lose the owner's copies WITHOUT announces (the
        # worst case: a buggy/om-killed cache, announce lost) — the
        # index still advertises the prefix
        pc = rt_owner.engine.prefix_cache
        pc.on_evict = None               # suppress the spill + announce
        while pc.entries():
            pc.evict_entry(pc.entries()[0])
        assert rt_owner.engine.spill.n_entries == 0
        assert router.cache_index.n_entries >= 1   # stale claim live

        h = router.submit(prompt, 5)
        _drive_until_terminal(router, runtimes, [h])
        assert h.status == "done" and h.tokens == want
        m = router.metrics()
        assert m["fleet/cache/stale_fallbacks/stale"] == 1
        # the stale claim was dropped at resolution
        assert router.cache_index.entries_for(owner) == {}
    finally:
        for rt in runtimes:
            rt.finished = True
        router.close()


def test_snapshot_rebuild_rides_readmission(economy_fleet):
    """Death fences drop a worker's index entries; the breaker-governed
    hello re-admission rebuilds them via the snapshot announce."""
    params, mesh, router, runtimes, _ = economy_fleet
    _drive(router, runtimes, n=3)
    prompt = (np.arange(10) % VOCAB).astype(np.int32)
    leader = router.submit(prompt, 5)
    _drive_until_terminal(router, runtimes, [leader])
    _drive_until(router, runtimes,
                 lambda: router.cache_index.n_entries >= 1,
                 what="cache announce")
    owner = router.cache_index.workers()[0]
    rt_owner = next(rt for rt in runtimes if rt.name == owner)
    survivors = [rt for rt in runtimes if rt.name != owner]
    rt_owner.kill()
    _drive_until(router, runtimes,
                 lambda: router.workers[owner].state == "dead",
                 live=survivors, what="death detection")
    assert router.cache_index.entries_for(owner) == {}   # fence dropped
    time.sleep(0.6)                      # past the breaker hold-off
    rt_owner.killed = False              # the worker comes back
    _drive_until(router, runtimes,
                 lambda: router.workers[owner].state == "live"
                 and router.cache_index.entries_for(owner) != {},
                 what="readmission snapshot")
    # the rebuilt view matches what the worker actually holds
    held = {tuple(e.seq) for e in rt_owner.engine.prefix_cache.entries()}
    held |= {tuple(s) for s, _ in rt_owner.engine.spill.entries()}
    assert set(router.cache_index.entries_for(owner)) <= held


def test_orphan_tag_sweep(devices):
    """The satellite: slab/pfx tags owned by no in-flight request are
    GC'd after the grace window; owned tags survive."""
    from chainermn_tpu.serving.fleet import build_local_fleet

    params = _params()
    mesh = _mesh(devices)
    router, runtimes = build_local_fleet(
        params, {"engine": 2}, head_dim=HEAD_DIM,
        beat_interval_s=0.01, miss_beats=3,
        worker_kwargs=dict(n_slots=2, max_total=24, mesh=mesh),
        orphan_sweep_interval_s=0.0, orphan_grace_s=0.05)
    try:
        _drive(router, runtimes, n=3)
        # an orphan: its worker died between pack-publish and
        # install-ack, nothing in _inflight references it
        router.store.put("slab/req-dead-00000001", b"corpse")
        router.store.put("pfx/req-dead-00000002", b"corpse")
        router.store.put("other/unrelated", b"keep")
        # an OWNED tag: a live in-flight request's slab must survive
        h = router.submit((np.arange(6) % VOCAB).astype(np.int32), 4)
        owned = f"slab/{h.trace_id}"
        router.store.put(owned, b"live")
        router._last_supervise = 0.0         # defeat the throttle
        router.supervisor_tick()             # first sighting
        assert router._orphan_seen           # orphans on the clock
        time.sleep(0.1)                      # grace elapses
        router._last_supervise = 0.0
        router.supervisor_tick()             # second sighting: GC
        tags = set(router.store.tags())
        assert "slab/req-dead-00000001" not in tags
        assert "pfx/req-dead-00000002" not in tags
        assert "other/unrelated" in tags     # non-slab tags untouched
        assert owned in tags                 # owned tag survives
        assert router._orphans_swept == 2
        _drive_until_terminal(router, runtimes, [h])
    finally:
        for rt in runtimes:
            rt.finished = True
        router.close()


def test_index_spill_evict_spares_rehydrated_hot_claim():
    """A spill-store eviction announce is tier-scoped: after the
    worker re-donated the same sequence to its device trie (the record
    is hot again), the late spill eviction must NOT delete the hot
    claim — the prefix is still pullable."""
    idx = FleetCacheIndex()
    idx.insert("w0", 1, (1, 2, 3, 4), 4)                 # hot
    assert idx.demote("w0", (1, 2, 3, 4))                # spilled
    idx.insert("w0", 1, (1, 2, 3, 4), 4, tier="hot")     # re-donated
    # the spill store LRU-evicts its (now stale) copy
    assert not idx.evict("w0", (1, 2, 3, 4), tier="spill")
    rec, mlen = idx.match([1, 2, 3, 4, 9])
    assert rec is not None and rec.tier == "hot" and mlen == 4
    # an UNSCOPED evict (device slab gone, not spilled) still removes
    assert idx.evict("w0", (1, 2, 3, 4))
    assert idx.match([1, 2, 3, 4, 9]) == (None, 0)


def test_pull_send_loses_race_to_supervisor_resolution(economy_fleet):
    """The submit/_cancel_pulls_on interleave: the supervisor resolves
    the pull (owner died) and dispatches the request while the submit
    thread is still inside its cache_pull send — when that send fails,
    the submit thread must NOT dispatch again (the same trace would
    run twice on the worker)."""
    params, mesh, router, runtimes, _ = economy_fleet
    _drive(router, runtimes, n=3)
    prompt = (np.arange(10) % VOCAB).astype(np.int32)
    want = _oracle(params, mesh, prompt, 5)
    leader = router.submit(prompt, 5)
    _drive_until_terminal(router, runtimes, [leader])
    _drive_until(router, runtimes,
                 lambda: router.cache_index.n_entries >= 1,
                 what="cache announce")

    submits_seen = {}
    for rt in runtimes:
        orig = rt._handle_submit

        def counted(wire, rt=rt, orig=orig):
            submits_seen[wire["trace_id"]] = \
                submits_seen.get(wire["trace_id"], 0) + 1
            return orig(wire)
        rt._handle_submit = counted

    orig_send = router._send_cache_pull

    def racing_send(owner_wc, req, pull):
        # the supervisor wins the race mid-send: it resolves the pull
        # (fallback submit to the destination) before our send fails
        with router._lock:
            entry = router._inflight[req.trace_id]
        router._pull_fallback(entry, "owner_lost",
                              "test: supervisor resolved first")
        raise RuntimeError("owner lane broke mid-send")

    router._send_cache_pull = racing_send
    try:
        h = router.submit(prompt, 5)
    finally:
        router._send_cache_pull = orig_send
    _drive_until_terminal(router, runtimes, [h])
    assert h.status == "done" and h.tokens == want
    # exactly ONE dispatch reached a worker for this trace
    assert submits_seen.get(h.trace_id) == 1, submits_seen


def test_reset_stats_resets_cache_rate_counters(economy_fleet):
    params, mesh, router, runtimes, _ = economy_fleet
    _drive(router, runtimes, n=3)
    prompt = (np.arange(10) % VOCAB).astype(np.int32)
    h = router.submit(prompt, 5)
    _drive_until_terminal(router, runtimes, [h])
    router.cache_index.count_stale("stale")
    assert router.cache_index.misses >= 1
    router.reset_stats()
    m = router.metrics()
    assert m["fleet/cache/hits"] == 0 and m["fleet/cache/misses"] == 0
    assert m["fleet/cache/stale_fallbacks"] == 0
    assert m["fleet/cache/remote_pulls"] == 0
    # structure survives the counter reset
    assert m["fleet/cache/index_entries"] >= 0


def test_file_lane_store_tags_roundtrip(tmp_path):
    from chainermn_tpu.serving.lanes import FileLaneStore, _unsafe_tag

    store = FileLaneStore(str(tmp_path))
    tags = ["slab/req-1a2b", "pfx/req-3c_4d", "lease/w☺0",
            "mbx/ctl.w0/12"]
    for t in tags:
        store.put(t, b"x")
    assert sorted(store.tags()) == sorted(tags)
    # tmp debris and undecodable names are skipped, not crashed on
    (tmp_path / ".tmp-zzz").write_bytes(b"torn")
    (tmp_path / "bad_escape_").write_bytes(b"junk")
    assert sorted(store.tags()) == sorted(tags)
    with pytest.raises(ValueError):
        _unsafe_tag("trailing_")
