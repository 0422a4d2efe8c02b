"""Serving fleet tests: router, radix-trie prefix cache, SLO admission.

Same three-layer shape as tests/test_serving.py, cheapest first:

* **Policy invariants** (jax-free): the three-state slot allocator
  (free/busy/cached+refcount), the radix trie (match/insert/dedup/
  subsume/LRU-evict), and a standalone cache+allocator fuzz — hundreds
  of random donate/match/retain/evict sequences with invariants checked
  every step, no devices anywhere.
* **Engine + fleet integration**: the ISSUE 7 acceptance gates —
  (a) a shared system prompt provably SKIPS re-prefill (engine
  ``prefill_calls``/``prefill_compiles`` asserted) and one merged
  Chrome trace shows a single trace id crossing router → replica →
  decode ticks; (b) the prefix-cache fuzz on the REAL engine: random
  overlapping-prefix workloads stay token-exact vs ``lm_generate`` on
  hits AND misses, no slot leaks, refcounts drain to zero; (c) the
  overload test at 2 replicas: offered load beyond capacity sheds
  (machine-readably) while admitted TTFT p99 stays bounded — degrade
  by rejection, not queue collapse, cross-checked against the goodput
  ledger's queue-wait split.
* **CLI smoke** (slow tier): ``python -m chainermn_tpu.serve
  --replicas 2`` in a fresh interpreter with schema-checked router
  metrics output (the PR 5 flight-recorder subprocess style).
"""

import json
import os
import random
import subprocess
import sys

import numpy as np
import pytest

from chainermn_tpu.serving import AdmissionError
from chainermn_tpu.serving.cache_pool import SlotAllocator
from chainermn_tpu.serving.prefix_cache import PrefixCache

ROOT = os.path.join(os.path.dirname(__file__), "..")

VOCAB, D, HEADS, LAYERS = 32, 16, 4, 2
HEAD_DIM = D // HEADS


# ---------------------------------------------------------------------------
# policy invariants (no jax)
# ---------------------------------------------------------------------------

def test_slot_allocator_cached_state_and_refcounts():
    alloc = SlotAllocator(3)
    a, b = alloc.acquire(), alloc.acquire()
    alloc.cache(a)                        # busy -> cached, rc=0
    assert alloc.cached_count == 1 and alloc.busy_count == 1
    assert alloc.refcount(a) == 0
    assert alloc.retain(a) == 1
    with pytest.raises(ValueError, match="reader"):
        alloc.uncache(a)                  # pinned: refuse eviction
    assert alloc.unretain(a) == 0
    with pytest.raises(ValueError, match="underflow"):
        alloc.unretain(a)
    alloc.uncache(a)                      # rc==0: back to free
    assert alloc.free_count == 2
    with pytest.raises(ValueError, match="not busy"):
        alloc.cache(a)                    # only busy slots donate
    with pytest.raises(ValueError, match="not cached"):
        alloc.retain(b)
    alloc.check_invariants()


def test_prefix_trie_match_insert_dedup_subsume():
    evicted = []
    pc = PrefixCache(evict_slot=evicted.append, min_prefix_len=2)
    assert pc.match([1, 2, 3]) == (None, 0)
    e1 = pc.insert([1, 2, 3, 4, 5], slot=0, length=5)
    assert e1 is not None
    # longest-prefix match, capped at len(prompt)-1 and entry length
    ent, n = pc.match([1, 2, 3, 4, 5, 9, 9])
    assert ent is e1 and n == 5
    ent, n = pc.match([1, 2, 3, 4, 5])      # cap: last token live
    assert ent is e1 and n == 4
    ent, n = pc.match([1, 2, 7, 7])          # mid-edge partial match
    assert ent is e1 and n == 2
    assert pc.match([9, 1, 2, 3])[0] is None  # no shared first token
    # dedup: a covered donation is rejected (caller keeps the slot)
    assert pc.insert([1, 2, 3], slot=1, length=3) is None
    assert pc.rejected_insertions == 1
    # a LONGER donation subsumes and evicts the shorter unpinned entry
    e2 = pc.insert([1, 2, 3, 4, 5, 6, 7], slot=2, length=7)
    assert e2 is not None and evicted == [0]
    assert pc.n_entries == 1
    # branch: shares [1,2] then diverges -> edge split, both live
    e3 = pc.insert([1, 2, 9, 9], slot=3, length=4)
    assert e3 is not None and pc.n_entries == 2
    ent, n = pc.match([1, 2, 9, 9, 0])
    assert ent is e3 and n == 4
    pc.check_invariants()


def test_prefix_cache_refcounts_and_lru_eviction():
    evicted = []
    pc = PrefixCache(evict_slot=evicted.append, min_prefix_len=2)
    e1 = pc.insert([1, 1, 1, 1], slot=0, length=4)
    e2 = pc.insert([2, 2, 2, 2], slot=1, length=4)
    pc.retain(e1)
    with pytest.raises(ValueError, match="pinned"):
        pc.evict_entry(e1)
    # LRU among rc==0 only: e2 is the only candidate
    assert pc.evict_lru() == 1 and evicted == [1]
    assert pc.evict_lru() is None          # e1 pinned, nothing left
    pc.release(e1)
    with pytest.raises(ValueError, match="underflow"):
        pc.release(e1)
    assert pc.evict_lru() == 0
    assert pc.n_entries == 0 and pc.total_refcount() == 0
    # peek never mutates counters or LRU order
    e3 = pc.insert([3, 3, 3, 3], slot=2, length=4)
    hits, clock = pc.hits, e3.last_used
    assert pc.peek_len([3, 3, 3, 9]) == 3
    assert pc.hits == hits and e3.last_used == clock
    pc.check_invariants()


def test_admission_error_machine_readable_payload():
    e = AdmissionError("shed_slo", "burning", retry_after_ms=12.5,
                       queue_depth=7)
    d = json.loads(json.dumps(e.to_dict()))   # wire-shape round-trip
    assert d == {"reason": "shed_slo", "detail": "burning",
                 "retry_after_ms": 12.5, "queue_depth": 7}
    # PR 3 call sites carry no payload: fields default to None and
    # to_dict stays minimal
    bare = AdmissionError("queue_full", "at capacity")
    assert bare.retry_after_ms is None and bare.queue_depth is None
    assert set(bare.to_dict()) == {"reason", "detail"}


def test_fuzz_trie_allocator_no_leak_refcounts_drain():
    """Standalone cache+allocator fuzz: random donate/match/retain/
    release/evict against a reference model; slot partition and
    refcount invariants checked EVERY step, full drain at the end."""
    rng = random.Random(0)
    for trial in range(30):
        n_slots = rng.choice([3, 4, 6])
        alloc = SlotAllocator(n_slots)
        pc = PrefixCache(retain_slot=alloc.retain,
                         release_slot=alloc.unretain,
                         evict_slot=alloc.uncache, min_prefix_len=2)
        bases = [[rng.randrange(8) for _ in range(rng.randint(2, 6))]
                 for _ in range(3)]
        pinned = []                      # (entry, slot_of_reader)
        for step in range(200):
            op = rng.random()
            seq = rng.choice(bases) + [rng.randrange(8) for _ in
                                       range(rng.randint(0, 4))]
            if op < 0.45:                # a request: acquire + match
                slot = alloc.acquire()
                if slot is None and pc.evictable_count():
                    pc.evict_lru()
                    slot = alloc.acquire()
                if slot is None:
                    continue
                ent, n = pc.match(seq)
                if ent is not None:
                    assert list(ent.seq[:n]) == list(seq[:n])
                    assert n <= len(seq) - 1
                    pc.retain(ent)
                    pinned.append((ent, slot))
                else:
                    pinned.append((None, slot))
            elif op < 0.85 and pinned:   # finish: release pin, donate
                ent, slot = pinned.pop(rng.randrange(len(pinned)))
                if ent is not None:
                    pc.release(ent)
                if pc.insert(seq, slot, len(seq)) is not None:
                    alloc.cache(slot)
                else:
                    alloc.release(slot)
            elif pc.evictable_count():   # pressure: evict LRU
                pc.evict_lru()
            alloc.check_invariants()
            pc.check_invariants()
            assert pc.total_refcount() == sum(
                1 for e, _ in pinned if e is not None)
        # drain: every reader finishes; all refcounts return to zero
        for ent, slot in pinned:
            if ent is not None:
                pc.release(ent)
            alloc.release(slot)
        assert pc.total_refcount() == 0
        while pc.evict_lru() is not None:
            pass
        alloc.check_invariants()
        assert alloc.free_count == n_slots  # no slot leaked anywhere


# ---------------------------------------------------------------------------
# engine + fleet integration (devices)
# ---------------------------------------------------------------------------

def _params(seed=0):
    import jax
    from chainermn_tpu.parallel import init_tp_transformer_lm

    return init_tp_transformer_lm(
        jax.random.PRNGKey(seed), VOCAB, D, HEADS, LAYERS, max_len=64)


def _mesh(devices, tp=1):
    import chainermn_tpu as mn

    return mn.make_nd_mesh(("model",), (tp,), devices[:tp])


def _oracle_fn(params, mesh, max_new):
    from chainermn_tpu.parallel import make_lm_generator

    gen = make_lm_generator(mesh, "model", head_dim=HEAD_DIM,
                            max_new_tokens=max_new)

    def oracle(prompt, n):
        return np.asarray(
            gen(params, np.asarray(prompt)[None]))[0][:n].tolist()

    return oracle


def test_prefix_cache_fuzz_token_exact_no_leak(devices):
    """Satellite (ISSUE 7): randomized submit/complete/evict workloads
    with OVERLAPPING prefixes on the real engine — outputs token-exact
    vs ``lm_generate`` on both cache hits and misses, no slot leak,
    all refcounts zero at drain."""
    from chainermn_tpu.serving import ServingEngine

    params = _params(seed=2)
    mesh = _mesh(devices)
    oracle = _oracle_fn(params, mesh, 8)
    rng = np.random.RandomState(4)
    eng = ServingEngine(params, head_dim=HEAD_DIM, n_slots=3,
                        max_total=28, mesh=mesh, queue_capacity=32,
                        max_prefills_per_tick=2)
    bases = [rng.randint(0, VOCAB, n).tolist() for n in (6, 9)]
    handles = []
    for trial in range(3):
        for i in range(8):
            if rng.rand() < 0.7:   # overlapping-prefix family
                prompt = bases[rng.randint(len(bases))] \
                    + rng.randint(0, VOCAB, rng.randint(1, 4)).tolist()
            else:                  # fresh prompt (miss path)
                prompt = rng.randint(0, VOCAB, rng.randint(4, 8)).tolist()
            max_new = int(rng.randint(2, 7))
            handles.append((prompt, max_new,
                            eng.submit(prompt, max_new)))
            if rng.rand() < 0.5:
                eng.step()
            eng.pool.allocator.check_invariants()
        eng.run(steps_budget=400)
    for prompt, max_new, h in handles:
        assert h.status == "done", (h.status, h.finish_reason)
        assert h.tokens == oracle(prompt, max_new), (prompt, h.tokens)
    # both paths actually exercised
    assert eng.prefix_cache.hits > 0 and eng.prefix_cache.misses > 0
    # drain invariants: no busy slots, no pins, partition intact
    assert eng.pool.busy_count == 0
    assert eng.prefix_cache.total_refcount() == 0
    eng.pool.allocator.check_invariants()
    eng.prefix_cache.check_invariants()
    assert eng.pool.free_count + eng.pool.cached_count == 3
    eng.close()


def test_admission_batch_requeued_when_slots_pinned(devices):
    """Regression: when an admission batch dies mid-way (every
    scavengeable slot pinned by EARLIER admissions in the same batch),
    the not-yet-admitted remainder of the batch must go back to the
    queue head — dropping it stranded handles 'queued' forever while
    run() drained believing the engine idle."""
    from chainermn_tpu.serving import ServingEngine

    params = _params(seed=14)
    mesh = _mesh(devices)
    eng = ServingEngine(params, head_dim=HEAD_DIM, n_slots=4,
                        max_total=24, mesh=mesh, queue_capacity=8,
                        max_prefills_per_tick=4)
    # three cached entries with distinct prefixes + one free slot
    mk = lambda t: np.array([t] * 6 + [t, t + 9], dtype=np.int32) % VOCAB
    for t in (1, 2, 3):
        h = eng.submit(mk(t), 3)
        eng.run(steps_budget=60)
        assert h.status == "done"
    assert eng.pool.cached_count == 3 and eng.pool.free_count == 1
    # one batch of four: two prefix hits pin their entries, the third
    # hit finds its source evicted by the second's acquire and misses
    # with nothing scavengeable left — it AND the fourth must requeue
    handles = [eng.submit(np.array([t] * 6 + [5, 5], np.int32) % VOCAB,
                          3) for t in (1, 2, 3, 4)]
    eng.run(steps_budget=200)
    for i, h in enumerate(handles):
        assert h.status == "done", (i, h.status, h.finish_reason)
    assert eng.pool.busy_count == 0
    assert eng.prefix_cache.total_refcount() == 0
    eng.pool.allocator.check_invariants()
    eng.close()


def test_acceptance_shared_prefix_skips_prefill_one_trace_id(
        devices, tmp_path):
    """ISSUE 7 acceptance (prefix half): a shared system prompt across
    requests PROVABLY skips re-prefill — engine prefill_calls/
    prefill_compiles asserted — and the merged Chrome trace shows ONE
    trace id crossing router/dispatch → replica queue-wait/prefix-copy
    → decode ticks."""
    from chainermn_tpu import observability as obs
    from chainermn_tpu.serving import Replica, ServingRouter

    params = _params(seed=3)
    mesh = _mesh(devices)
    oracle = _oracle_fn(params, mesh, 6)
    reps = [Replica.build(params, f"replica{i}", head_dim=HEAD_DIM,
                          n_slots=2, max_total=32, mesh=mesh,
                          queue_capacity=8) for i in range(2)]
    router = ServingRouter(reps)
    obs.reset()
    obs.enable()
    try:
        rng = np.random.RandomState(5)
        system = rng.randint(0, VOCAB, 12).tolist()
        prompts = [system + rng.randint(0, VOCAB, 3).tolist()
                   for _ in range(4)]
        handles = []
        for p in prompts:   # sequential: drain between submits so the
            h = router.submit(p, 6)   # affinity score sees no backlog
            router.run(steps_budget=200)
            handles.append((p, h))
    finally:
        obs.disable()
    for p, h in handles:
        assert h.status == "done"
        assert h.tokens == oracle(p, 6), (p, h.tokens)
    e0, e1 = reps[0].engine, reps[1].engine
    # request 0 prefilled once; 1..3 hit the radix trie and COPIED the
    # shared prefix instead of re-prefilling it — on one replica, by
    # prefix affinity, with zero compiles or prefills on the other
    assert e0.engine.prefill_calls == 1, e0.engine.prefill_calls
    assert e0.engine.prefill_compiles == 1
    assert e0.engine.prefix_copies == 3
    assert e0.prefix_cache.hits == 3
    assert e1.engine.prefill_calls == 0
    assert e1.engine.tick_calls == 0
    m = router.metrics()
    assert m["router/affinity_dispatches_total"] == 3.0
    # merged Perfetto doc: ONE trace id crosses every hop
    trace_path = tmp_path / "router_trace.json"
    obs.export_chrome_trace(str(trace_path))
    doc = json.loads(trace_path.read_text())
    events = doc["traceEvents"] if isinstance(doc, dict) else doc
    tid = handles[1][1].trace_id          # a prefix-hit request
    assert tid.startswith("req-") and "rt" in tid   # router-minted
    spans = {ev["name"] for ev in events
             if (ev.get("args") or {}).get("trace_id") == tid}
    for expected in ("router/dispatch", "request/queue_wait",
                     "serving/prefix_copy", "request/decode_tick"):
        assert expected in spans, (expected, sorted(spans))
    # and the request's async flow (b/e pair) carries the same id
    flow_phases = {ev["ph"] for ev in events if ev.get("id") == tid}
    assert {"b", "e"} <= flow_phases, flow_phases
    router.close()


def test_acceptance_overload_sheds_machine_readably(devices):
    """ISSUE 7 acceptance (overload half): at 2 replicas under offered
    load beyond fleet capacity, the router SHEDS (shed rate > 0, every
    rejection machine-readable with retry_after_ms + queue_depth) while
    admitted requests' TTFT p99 stays bounded by the refused-to-
    overfill queues — degradation by shedding, not queue collapse —
    cross-checked against the GoodputLedger queue-wait split."""
    from chainermn_tpu.serving import Replica, ServingRouter
    from chainermn_tpu.serving.router import REJECT_REASONS

    params = _params(seed=6)
    mesh = _mesh(devices)
    n_slots, queue_cap, s_p, new = 2, 2, 6, 6
    reps = [Replica.build(params, f"replica{i}", head_dim=HEAD_DIM,
                          n_slots=n_slots, max_total=s_p + new,
                          mesh=mesh, queue_capacity=queue_cap)
            for i in range(2)]
    router = ServingRouter(reps)
    rng = np.random.RandomState(7)
    prompts = [rng.randint(0, VOCAB, s_p).astype(np.int32)
               for _ in range(30)]
    # warm the compiles, then reset so steady-state numbers are clean
    h = router.submit(prompts[0], 2)
    router.run(steps_budget=50)
    assert h.status == "done"
    router.reset_stats()

    admitted, rejections = [], []
    for p in prompts:   # submit EVERY round: far beyond capacity
        try:
            admitted.append(router.submit(p, new))
        except AdmissionError as e:
            rejections.append(e)
        router.step()
    router.run(steps_budget=2000)

    m = router.metrics()
    assert m["router/shed_rate"] > 0, m
    assert len(rejections) == m["router/rejected_total"]
    for e in rejections:           # every rejection machine-readable
        assert e.reason in REJECT_REASONS
        d = e.to_dict()
        assert d["retry_after_ms"] >= 1.0 and d["queue_depth"] >= 0
        assert m[f"router/rejected/{e.reason}"] > 0   # counted per-reason
    for h in admitted:
        assert h.status == "done", (h.status, h.finish_reason)
    # bounded TTFT: an admitted request waits behind AT MOST the
    # bounded queue + the running slots — price that worst-case backlog
    # at the fleet's own measured p99 token latency; queue collapse
    # (unbounded buffering of all 30 requests) would blow well past it
    tok_p99 = max(m[f"router/{r.name}/token_latency_p99_ms"]
                  for r in reps)
    prefill_ms = max(m[f"router/{r.name}/ttft_p50_ms"] for r in reps)
    backlog_tokens = queue_cap * (s_p + new) + n_slots * new
    bound = 2.0 * (backlog_tokens * tok_p99 + prefill_ms)
    assert m["router/fleet_ttft_p99_ms"] < bound, (
        m["router/fleet_ttft_p99_ms"], bound)
    # the queue-wait SPLIT of TTFT (the PR 5 goodput plumbing's phase
    # stamps): time in the bounded queue — submit → prefill_start —
    # obeys the same backlog bound for EVERY admitted request; a
    # collapsed queue shows up exactly here first
    waits_ms = sorted(
        (h.timestamps["prefill_start"] - h.timestamps["submitted"]) * 1e3
        for h in admitted)
    assert waits_ms[-1] <= bound, (waits_ms[-1], bound)
    # and each replica's wall-clock ledger still reconciles (partition
    # held within 10% through the router hop)
    for rep in reps:
        g = rep.engine.goodput.report()
        assert g["coverage_frac"] >= 0.9, g
    router.close()


def test_more_replicas_shed_no_more_at_the_same_offered_load(devices):
    """One submit every fleet round, beyond one replica's capacity,
    through 1 and then 4 replicas: one replica drowns (it sheds), four
    shed no more than one did, and every admitted request finishes.
    Step-driven: these are counts, not speeds."""
    from chainermn_tpu.serving import build_fleet

    params = _params(seed=6)
    mesh = _mesh(devices)
    rng = np.random.RandomState(7)
    prompts = [rng.randint(0, VOCAB, 6).astype(np.int32)
               for _ in range(16)]

    def shed_at(n_replicas):
        router = build_fleet(params, n_replicas, head_dim=HEAD_DIM,
                             n_slots=2, max_total=12, mesh=mesh,
                             queue_capacity=2)
        try:
            admitted, shed = [], 0
            for p in prompts:
                try:
                    admitted.append(router.submit(p, 6))
                except AdmissionError:
                    shed += 1
                router.step()
            router.run(steps_budget=2000)
            assert all(h.status == "done" for h in admitted)
            assert shed == router.metrics()["router/rejected_total"]
            return shed
        finally:
            router.close()

    one, four = shed_at(1), shed_at(4)
    assert one > 0, "one replica must drown at this load"
    assert four <= one, (four, one)


def test_router_deadline_infeasible_sheds(devices):
    """Deadline-aware dispatch: a request whose deadline no replica can
    meet is shed at SUBMIT (reason shed_slo) instead of being queued to
    certain death; a feasible deadline dispatches normally."""
    from chainermn_tpu.serving import Replica, ServingRouter

    params = _params(seed=8)
    mesh = _mesh(devices)
    reps = [Replica.build(params, "replica0", head_dim=HEAD_DIM,
                          n_slots=1, max_total=16, mesh=mesh,
                          queue_capacity=4)]
    router = ServingRouter(reps)
    # build real backlog: a running request + queued work
    rng = np.random.RandomState(9)
    p = rng.randint(0, VOCAB, 4).astype(np.int32)
    router.submit(p, 8)
    router.step()                        # running
    router.submit(p, 8)                  # queued: backlog_tokens > 0
    with pytest.raises(AdmissionError) as exc:
        router.submit(p, 4, deadline_s=1e-9)
    assert exc.value.reason == "shed_slo"
    assert exc.value.retry_after_ms is not None
    assert "deadline" in str(exc.value)
    # generous deadline: dispatches fine
    h = router.submit(p, 4, deadline_s=3600)
    router.run(steps_budget=400)
    assert h.status == "done"
    router.close()


def test_router_slo_burn_sheds_before_page(devices):
    """SLO-aware admission: with the fleet tracker burning past the
    shed threshold (but configured BELOW the paging threshold) and
    backlog present, new work is refused with reason shed_slo."""
    from chainermn_tpu.observability.slo import SLOTracker
    from chainermn_tpu.serving import Replica, ServingRouter

    params = _params(seed=10)
    mesh = _mesh(devices)
    slo = SLOTracker(ttft_target_ms=1e-6,   # everything violates
                     windows_s=(30.0, 300.0), min_observations=2,
                     burn_threshold=1e9)    # the PAGER never fires
    reps = [Replica.build(params, "replica0", head_dim=HEAD_DIM,
                          n_slots=1, max_total=16, mesh=mesh,
                          queue_capacity=8, slo=slo)]
    router = ServingRouter(reps, slo=slo, shed_burn_threshold=1.0)
    rng = np.random.RandomState(11)
    p = rng.randint(0, VOCAB, 4).astype(np.int32)
    for _ in range(3):                   # feed TTFT observations
        h = router.submit(p, 2)
        router.run(steps_budget=60)
        assert h.status == "done"
    assert slo.burn_rate("ttft", 30.0) > 1.0
    assert not slo.findings              # shed fires BEFORE any page
    router.submit(p, 6)                  # backlog (queued, no step yet)
    with pytest.raises(AdmissionError) as exc:
        router.submit(p, 6)              # burning + backlog => shed
    assert exc.value.reason == "shed_slo"
    assert exc.value.queue_depth >= 1
    assert exc.value.retry_after_ms >= 1.0
    assert not slo.findings              # still no page fired
    router.run(steps_budget=400)
    router.close()


def test_router_rejections_reach_metricsz_and_jsonl(devices, tmp_path):
    """Satellite (ISSUE 7): per-reason rejection counters reach the
    Prometheus /metricsz payload and the serving JSONL stream
    (router_rejection records + the router_summary roll-up),
    schema-checked."""
    from chainermn_tpu.observability.export import (MetricsWriter,
                                                    read_metrics_jsonl)
    from chainermn_tpu.serving import Replica, ServingRouter

    params = _params(seed=12)
    mesh = _mesh(devices)
    stream = tmp_path / "router.jsonl"
    writer = MetricsWriter(str(stream))
    reps = [Replica.build(params, "replica0", head_dim=HEAD_DIM,
                          n_slots=1, max_total=12, mesh=mesh,
                          queue_capacity=1)]
    router = ServingRouter(reps, metrics_writer=writer)
    rng = np.random.RandomState(13)
    p = rng.randint(0, VOCAB, 4).astype(np.int32)
    # too_long first (queue still empty — a full fleet queue would
    # shadow it with queue_full, which is the rejection precedence)
    with pytest.raises(AdmissionError) as e2:
        router.submit(rng.randint(0, VOCAB, 10).astype(np.int32), 10)
    assert e2.value.reason == "too_long"
    router.submit(p, 4)
    with pytest.raises(AdmissionError) as e1:
        router.submit(p, 4)              # queue (capacity 1) is full
    assert e1.value.reason == "queue_full"
    router.run(steps_budget=200)
    router.finalize_metrics()
    writer.close()
    # /metricsz: the statusz server's extra_gauges path, per reason
    from chainermn_tpu.observability.introspect import StatusServer
    srv = StatusServer(extra_gauges=router.metrics)
    prom = srv.metricsz()
    assert "chainermn_tpu_router_rejected_queue_full 1.0" in prom
    assert "chainermn_tpu_router_rejected_too_long 1.0" in prom
    assert "chainermn_tpu_router_rejected_shed_slo 0.0" in prom
    # JSONL stream: schema-valid, per-rejection records + the summary
    records = read_metrics_jsonl(str(stream), strict=True)
    kinds = [r["kind"] for r in records]
    assert kinds.count("router_rejection") == 2
    assert kinds[-1] == "router_summary"
    rej = [r for r in records if r["kind"] == "router_rejection"]
    assert {r["reason"] for r in rej} == {"queue_full", "too_long"}
    for r in rej:
        assert r["router/retry_after_ms"] >= 1.0
        assert "router/queue_depth" in r and "trace_id" in r
    assert records[-1]["router/rejected_total"] == 2.0
    # fleet statusz provider: per-replica introspection aggregated
    state = router.introspect_state()
    assert state["rejected"]["queue_full"] == 1
    assert "replica0" in state["replica_state"]
    assert "prefix_cache" in state["replica_state"]["replica0"]
    router.close()


@pytest.mark.slow
def test_serve_cli_replicas_subprocess(tmp_path):
    """``python -m chainermn_tpu.serve --replicas 2`` in a fresh
    interpreter (PR 5 flight-recorder subprocess style): exit 0, every
    request served, schema-checked router metrics in the summary AND
    in the JSONL stream."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"
    metrics = tmp_path / "m.jsonl"
    prom = tmp_path / "m.prom"
    out = subprocess.run(
        [sys.executable, "-m", "chainermn_tpu.serve", "--devices", "8",
         "--tp", "1", "--train-steps", "5", "--requests", "6",
         "--replicas", "2", "--n-slots", "2", "--max-new-tokens", "4",
         "--steps-budget", "120",
         "--metrics-out", str(metrics), "--prom-out", str(prom)],
        capture_output=True, text=True, timeout=600, env=env, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-2000:]
    summary = json.loads(out.stdout.strip().splitlines()[-1])
    assert summary["schema"] == "chainermn_tpu.serve.v1"
    assert summary["replicas"] == 2
    for row in summary["requests"]:
        assert row["status"] == "done", row
    m = summary["metrics"]
    assert m["router/replicas"] == 2.0
    assert m["router/dispatched_total"] == 6.0
    for reason in ("queue_full", "too_long", "shed_slo"):
        assert f"router/rejected/{reason}" in m
    assert "router/fleet_tokens_per_sec" in m
    # per-replica goodput ledgers each reconcile (PR 5 contract held
    # through the router hop)
    for name, g in summary["goodput"].items():
        assert g["coverage_frac"] >= 0.9, (name, g)
    from chainermn_tpu.observability.export import read_metrics_jsonl
    records = read_metrics_jsonl(str(metrics), strict=True)
    assert records and records[-1]["kind"] == "router_summary"
    assert prom.read_text().count("chainermn_tpu_router_") >= 8
