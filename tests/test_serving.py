"""Continuous-batching serving engine tests.

Three layers, cheapest first:

* **Policy invariants** (jax-free): the slot allocator and scheduler are
  pure host Python, so their invariants — no slot leak, FIFO admission,
  reject-with-reason backpressure, deadline expiry — are fuzzed directly
  with a simulated engine loop: hundreds of random arrival/eviction
  sequences per test, no compile anywhere.
* **Engine integration** (the acceptance gate): a 4-slot pool serving 8
  staggered requests must (a) start decoding a late-arriving request
  BEFORE the first batch drains — iteration-level batching, asserted on
  the per-request span timestamps — and (b) emit TOKEN-EXACT output vs
  running each request alone through ``lm_generate`` (which doubles as
  the no-cross-talk oracle: slots share every tick's batch and are
  recycled between requests, so any leakage between sequences breaks
  exactness).  The serving gauges must reach the Prometheus textfile
  and ``metrics()`` must be a JSON-clean record of finite numbers.
* **CLI smoke**: ``chainermn_tpu.serve`` in-process with a tiny config —
  summary JSON on stdout, schema-valid metrics JSONL, exit 0.
"""

import json
import os
import random
import subprocess
import sys
import time

import numpy as np
import pytest

from chainermn_tpu.serving import AdmissionError, Request, Scheduler
from chainermn_tpu.serving.cache_pool import SlotAllocator

ROOT = os.path.join(os.path.dirname(__file__), "..")

VOCAB, D, HEADS, LAYERS = 32, 16, 4, 2
HEAD_DIM = D // HEADS


# ---------------------------------------------------------------------------
# policy invariants (no jax)
# ---------------------------------------------------------------------------

def test_slot_allocator_invariants():
    alloc = SlotAllocator(3)
    a, b = alloc.acquire(), alloc.acquire()
    assert (a, b) == (0, 1)
    alloc.release(a)
    assert alloc.acquire() == 0          # recycled, lowest-first
    assert alloc.acquire() == 2
    assert alloc.acquire() is None       # saturated
    with pytest.raises(ValueError, match="not busy"):
        alloc.release(1)                 # double release
        alloc.release(1)
    alloc.check_invariants()


def test_scheduler_backpressure_and_reasons():
    sched = Scheduler(queue_capacity=2, slot_capacity=16)
    now = 0.0
    sched.submit(Request([1, 2], 4), now)
    sched.submit(Request([1, 2], 4), now)
    with pytest.raises(AdmissionError) as e:
        sched.submit(Request([1, 2], 4), now)
    assert e.value.reason == "queue_full"
    with pytest.raises(AdmissionError) as e:
        sched.submit(Request(list(range(10)), 10), now)  # 20 > 16
    assert e.value.reason == "too_long"
    # the learned-pos table bound tightens slot capacity
    tight = Scheduler(queue_capacity=2, slot_capacity=64, max_positions=8)
    with pytest.raises(AdmissionError) as e:
        tight.submit(Request([1, 2, 3, 4], 6), now)      # 10 > 8
    assert e.value.reason == "too_long"


def test_scheduler_fifo_admission_and_interleave_bound():
    sched = Scheduler(queue_capacity=8, slot_capacity=64,
                      max_prefills_per_tick=2)
    reqs = [Request([1], 2) for _ in range(5)]
    for r in reqs:
        sched.submit(r, 0.0)
    # bounded by max_prefills_per_tick even with more slots free
    first = sched.admissions(free_slots=4, now=0.0)
    assert [r.id for r in first] == [reqs[0].id, reqs[1].id]
    # bounded by free slots even with prefill budget left
    second = sched.admissions(free_slots=1, now=0.0)
    assert [r.id for r in second] == [reqs[2].id]


def test_scheduler_deadline_expiry_and_eviction_reasons():
    sched = Scheduler(queue_capacity=4, slot_capacity=64)
    late = Request([1], 4, deadline_t=1.0)
    ok = Request([1], 4)
    sched.submit(late, 0.0)
    sched.submit(ok, 0.0)
    expired = sched.expire_queued(now=2.0)
    assert expired == [late] and late.status == "evicted" \
        and late.finish_reason == "deadline"
    assert [r.id for r in sched.admissions(4, 2.0)] == [ok.id]
    # eviction precedence: eos > max_tokens > deadline
    r = Request([1], 2, eos_id=9, deadline_t=10.0)
    r.tokens = [5]
    assert sched.eviction_reason(r, 0.0) is None
    r.tokens = [5, 9]
    assert sched.eviction_reason(r, 99.0) == "eos"
    r2 = Request([1], 2)
    r2.tokens = [5, 6]
    assert sched.eviction_reason(r2, 0.0) == "max_tokens"
    r3 = Request([1], 8, deadline_t=1.0)
    r3.tokens = [5]
    assert sched.eviction_reason(r3, 2.0) == "deadline"


def test_fuzzed_arrival_eviction_no_leak_fifo_under_backpressure():
    """Simulated engine loop, no devices: random arrivals, lengths and
    deadlines against a 4-slot pool.  Invariants checked EVERY step:
    free+busy partitions the slots, admission is FIFO among accepted
    requests, the queue never exceeds capacity, rejections happen only
    at capacity, and every accepted request terminates with a legal
    reason."""
    rng = random.Random(0)
    for trial in range(20):
        n_slots, cap = 4, 3
        sched = Scheduler(queue_capacity=cap, slot_capacity=32,
                          max_prefills_per_tick=rng.choice([1, 2]))
        alloc = SlotAllocator(n_slots)
        running = {}          # slot -> (req, remaining_ticks)
        accepted, admitted, finished = [], [], []
        now = 0.0
        for step in range(120):
            now += 1.0
            # random arrivals
            for _ in range(rng.randrange(3)):
                req = Request([1] * rng.randint(1, 8),
                              rng.randint(1, 6),
                              eos_id=7 if rng.random() < 0.3 else None,
                              deadline_t=(now + rng.randint(1, 30)
                                          if rng.random() < 0.3 else None))
                try:
                    sched.submit(req, now)
                except AdmissionError as e:
                    assert e.reason == "queue_full"
                    assert sched.queue_depth == cap  # only reject at cap
                else:
                    accepted.append(req)
            for req in sched.expire_queued(now):
                finished.append(req)
                assert req.finish_reason == "deadline"
            for req in sched.admissions(alloc.free_count, now):
                slot = alloc.acquire()
                assert slot is not None
                admitted.append(req)
                running[slot] = (req, rng.randint(1, req.max_new_tokens))
            # decode tick: emit one token per active slot (the last
            # simulated token is 7, tripping eos for requests that set it)
            for slot in list(running):
                req, rem = running[slot]
                req.tokens.append(0 if rem > 1 else 7)
                running[slot] = (req, rem - 1)
                reason = sched.eviction_reason(req, now)
                if reason:
                    req.finish(reason, now)
                    finished.append(req)
                    del running[slot]
                    alloc.release(slot)
            alloc.check_invariants()
            assert alloc.busy_count == len(running)
            assert sched.queue_depth <= cap
        # FIFO: admission order is a subsequence-respecting prefix order
        order = {r.id: i for i, r in enumerate(accepted)}
        assert [order[r.id] for r in admitted] == sorted(
            order[r.id] for r in admitted)
        for req in finished:
            assert req.finish_reason in ("eos", "max_tokens", "deadline")
            assert req.done_event.is_set()


# ---------------------------------------------------------------------------
# engine integration (devices)
# ---------------------------------------------------------------------------

def _params(pos_impl="learned", n_kv_heads=None, seed=0):
    import jax
    from chainermn_tpu.parallel import init_tp_transformer_lm

    return init_tp_transformer_lm(
        jax.random.PRNGKey(seed), VOCAB, D, HEADS, LAYERS, max_len=64,
        pos_impl=pos_impl, n_kv_heads=n_kv_heads)


def _mesh(devices, tp):
    import chainermn_tpu as mn

    return mn.make_nd_mesh(("model",), (tp,), devices[:tp])


def _oracle(params, mesh, prompt, max_new):
    """Each request ALONE through the closed-batch generator (greedy
    tokens are max_new-invariant prefixes, so one program serves every
    request length)."""
    from chainermn_tpu.parallel import make_lm_generator

    gen = make_lm_generator(mesh, "model", head_dim=HEAD_DIM,
                            max_new_tokens=max_new)
    return np.asarray(gen(params, np.asarray(prompt)[None]))[0]


def test_iteration_level_batching_end_to_end(devices, tmp_path):
    """THE acceptance test: 4-slot pool, 8 staggered requests; a late
    arrival starts decoding before the first batch drains; outputs are
    token-exact vs lm_generate alone (= no cross-talk through the shared
    pool / recycled slots); gauges reach Prometheus and ``metrics()``
    is a JSON-clean record."""
    from chainermn_tpu import observability as obs
    from chainermn_tpu.serving import ServingEngine

    params = _params()
    mesh = _mesh(devices, 2)
    eng = ServingEngine(params, head_dim=HEAD_DIM, n_slots=4, max_total=32,
                        mesh=mesh, queue_capacity=8,
                        max_prefills_per_tick=2)
    obs.reset()
    obs.enable()
    try:
        rng = np.random.RandomState(1)
        prompts = [rng.randint(0, VOCAB, 6).astype(np.int32)
                   for _ in range(8)]
        # request 0 runs LONG; its wave-mates finish early, freeing slots
        # for the late wave while 0 is still decoding
        max_new = [12, 4, 4, 4, 6, 6, 6, 6]
        streamed = {}
        handles = [eng.submit(prompts[i], max_new[i],
                              on_token=lambda t, rid: streamed.setdefault(
                                  rid, []).append(t))
                   for i in range(4)]
        for _ in range(2):
            eng.step()
        handles += [eng.submit(prompts[i], max_new[i]) for i in range(4, 8)]
        eng.run(steps_budget=200)
    finally:
        obs.disable()

    # every request completed by length
    for h in handles:
        assert h.status == "done", (h.id, h.status, h.finish_reason)
        assert h.finish_reason == "max_tokens"

    # iteration-level batching: request 4 decoded its first token BEFORE
    # the longest first-wave request finished (span timestamps)
    t_first_late = handles[4].timestamps["first_token"]
    t_drain = handles[0].timestamps["finished"]
    assert t_first_late < t_drain, (t_first_late, t_drain)
    for h in handles:
        ts = h.timestamps
        assert ts["submitted"] <= ts["prefill_start"] \
            <= ts["first_token"] <= ts["finished"]

    # token-exact vs each request alone through lm_generate
    oracle12 = {i: _oracle(params, mesh, prompts[i], 12) for i in range(8)}
    for i, h in enumerate(handles):
        want = oracle12[i][: max_new[i]].tolist()
        assert h.tokens == want, (i, h.tokens, want)
    # streaming callbacks saw exactly the same tokens, in order
    for i in range(4):
        assert streamed[handles[i].id] == handles[i].tokens

    # tracer carries the per-request serving instants + tick spans
    names = {ev["name"] for ev in obs.get_tracer().events()}
    for expected in ("serving/request/queued", "serving/request/prefill",
                     "serving/request/first_token",
                     "serving/request/complete", "serving/tick",
                     "serving/prefill"):
        assert expected in names, (expected, sorted(names)[:30])

    # Prometheus textfile carries the serving gauges
    prom = eng.write_prometheus(str(tmp_path / "serving.prom"))
    assert "chainermn_tpu_serving_tokens_per_sec" in prom
    assert "chainermn_tpu_serving_ttft_p50_ms" in prom
    assert "chainermn_tpu_serving_slot_occupancy_pct" in prom

    # metrics() round-trips JSON and its headline keys are finite numbers
    m = json.loads(json.dumps(eng.metrics()))
    for key in ("serving/tokens_per_sec", "serving/ttft_p50_ms",
                "serving/ttft_p99_ms", "serving/slot_occupancy_pct"):
        assert np.isfinite(m[key]), (key, m[key])
    assert m["serving/tokens_per_sec"] > 0


@pytest.mark.parametrize("pos_impl,n_kv_heads", [("rope", 2)])
def test_rope_gqa_exactness_with_recycled_slots(devices, pos_impl,
                                                n_kv_heads):
    """Per-row RoPE + GQA through the pool, with slot RECYCLING: more
    requests than slots at mixed prompt lengths, so late requests decode
    in slots still holding an earlier sequence's stale K/V — exactness
    proves the per-slot masks keep it unreachable."""
    from chainermn_tpu.serving import ServingEngine

    params = _params(pos_impl=pos_impl, n_kv_heads=n_kv_heads, seed=3)
    mesh = _mesh(devices, 2)
    eng = ServingEngine(params, head_dim=HEAD_DIM, n_slots=2, max_total=32,
                        mesh=mesh, queue_capacity=8)
    rng = np.random.RandomState(7)
    prompts = [rng.randint(0, VOCAB, rng.choice([4, 6])).astype(np.int32)
               for _ in range(5)]
    handles = [eng.submit(p, 5) for p in prompts]
    eng.run(steps_budget=200)
    for p, h in zip(prompts, handles):
        assert h.status == "done"
        assert h.tokens == _oracle(params, mesh, p, 5).tolist(), h.id


def test_eos_and_deadline_eviction_live(devices):
    """EOS eviction against the real engine (eos learned from the oracle
    so it is guaranteed to be emitted), and deadline eviction of a
    RUNNING request (deadline forced into the past between ticks)."""
    from chainermn_tpu.serving import ServingEngine

    # seed 57: a random init whose greedy continuation is not constant
    # under the installed jax's PRNG ([8, 8, 0, ...]), so the eos lands
    # on the THIRD token; the expectation below holds for any sequence
    params = _params(seed=57)
    mesh = _mesh(devices, 1)
    eng = ServingEngine(params, head_dim=HEAD_DIM, n_slots=2, max_total=32,
                        mesh=mesh)
    prompt = np.arange(5, dtype=np.int32) % VOCAB
    want = _oracle(params, mesh, prompt, 6).tolist()
    eos = want[2]
    h = eng.submit(prompt, 6, eos_id=eos)
    eng.run(steps_budget=50)
    assert h.status == "done" and h.finish_reason == "eos"
    # stops at the FIRST eos, eos token included
    assert h.tokens == want[:want.index(eos) + 1]
    assert eng.pool.busy_count == 0      # slot released

    h2 = eng.submit(prompt, 27, deadline_s=3600)    # 5 + 27 = max_total
    eng.step()                           # admitted + first token
    assert h2.status == "running"
    h2._req.deadline_t = time.monotonic() - 1.0
    eng.step()
    assert h2.status == "evicted" and h2.finish_reason == "deadline"
    assert eng.pool.busy_count == 0


def test_live_backpressure_and_too_long(devices):
    from chainermn_tpu.serving import ServingEngine

    params = _params(seed=6)
    eng = ServingEngine(params, head_dim=HEAD_DIM, n_slots=1, max_total=16,
                        mesh=_mesh(devices, 1), queue_capacity=1)
    with pytest.raises(AdmissionError) as e:
        eng.submit(np.zeros(10, np.int32), 10)       # 20 > 16
    assert e.value.reason == "too_long"
    eng.submit(np.zeros(4, np.int32), 2)
    with pytest.raises(AdmissionError) as e:
        eng.submit(np.zeros(4, np.int32), 2)         # queue at capacity
    assert e.value.reason == "queue_full"
    assert eng.metrics()["serving/rejected_total"] == 2.0
    eng.run(steps_budget=20)                         # drains cleanly

    # deadline_s=0.0 means ALREADY expired, not "no deadline"
    h = eng.submit(np.zeros(4, np.int32), 4, deadline_s=0.0)
    eng.step()
    assert h.status == "evicted" and h.finish_reason == "deadline"


def test_prefill_bucket_padding_counts_against_capacity(devices):
    """Admission must reject on the PADDED prompt length: a 13-token
    prompt under prefill_bucket=8 pads to 16, which cannot fit a
    max_total=14 slot even though 13 + 1 would."""
    from chainermn_tpu.serving import ServingEngine

    params = _params(seed=6)
    eng = ServingEngine(params, head_dim=HEAD_DIM, n_slots=1, max_total=14,
                        mesh=_mesh(devices, 1), prefill_bucket=8)
    with pytest.raises(AdmissionError) as e:
        eng.submit(np.zeros(13, np.int32), 1)
    assert e.value.reason == "too_long" and "pads to 16" in str(e.value)
    # a 5-token prompt pads to 8 and fits; exactness holds through the
    # padded prefill (causal attention never reads a pad)
    prompt = (np.arange(5) % VOCAB).astype(np.int32)
    h = eng.submit(prompt, 4)
    eng.run(steps_budget=20)
    assert h.status == "done"
    assert h.tokens == _oracle(params, _mesh(devices, 1), prompt, 4).tolist()


def test_tick_cache_block_counters(devices):
    """How much of the pool the tick's attention reads (the flash-decode
    kernels' work list): blocks at or below each BUSY slot's position over
    the blocks the pool holds, host arithmetic on ``pool.pos`` — summed
    over ticks, in ``metrics()``, zeroed by ``reset_stats()``."""
    from chainermn_tpu.ops.decode_attention import DEFAULT_BLOCK_S
    from chainermn_tpu.serving import ServingEngine

    total = 2 * DEFAULT_BLOCK_S              # two blocks a slot
    eng = ServingEngine(_params(pos_impl="rope"), head_dim=HEAD_DIM,
                        n_slots=3, max_total=total, mesh=_mesh(devices, 1),
                        max_prefills_per_tick=2)
    m = eng.metrics()
    assert m["serving/tick_cache_blocks_read"] == 0.0
    assert m["serving/tick_cache_blocks_total"] == 0.0
    rng = np.random.RandomState(5)
    handles = [eng.submit(rng.randint(0, VOCAB, n).astype(np.int32), 4)
               for n in (4, 9)]
    # the third slot stays free, holding a position in the second block
    # (a cached prefix would): it serves nobody, so none of it is read
    eng.pool.pos[2] = total - 2
    eng.run(steps_budget=20)
    assert [h.status for h in handles] == ["done", "done"]
    ticks = eng.engine.tick_calls
    assert ticks >= 3
    m = eng.metrics()
    # slots 0 and 1 live in their first block: one block a busy slot a
    # tick (both are busy in every tick: 4 tokens each, admitted together)
    assert m["serving/tick_cache_blocks_read"] == 2.0 * ticks
    assert m["serving/tick_cache_blocks_total"] == 6.0 * ticks
    # and the rows a tick reads: ``pos + 1`` of each (the token it writes)
    assert m["serving/tick_cache_rows_live"] == sum(
        (4 + k + 1) + (9 + k + 1) for k in range(ticks))
    eng.reset_stats()
    m = eng.metrics()
    assert m["serving/tick_cache_blocks_read"] == 0.0
    assert m["serving/tick_cache_blocks_total"] == 0.0
    eng.close()


def _leaves(caches):
    import jax

    return jax.tree_util.tree_leaves(caches)


@pytest.mark.parametrize("program", ["prefill", "tick", "prefix_copy"])
def test_pool_is_donated_to_every_program_that_returns_it(devices, program):
    """The pool's buffers have one owner: the prefill, the tick and the
    prefix copy take them DONATED, so the arrays bound before the call
    are deleted by it (a stale reader fails, it does not get a copy) and
    ``pool.caches`` holds live ones.  The prefix copy reads its source
    slot out of the very buffer it writes: the source rows stay."""
    from chainermn_tpu.serving import ServingEngine

    eng = ServingEngine(_params(pos_impl="rope"), head_dim=HEAD_DIM,
                        n_slots=3, max_total=16, mesh=_mesh(devices, 2))
    dec, pool = eng.engine, eng.pool
    prompt = np.arange(1, 7, dtype=np.int32)
    slot = pool.acquire()
    dec.prefill_into_slot(prompt, slot)
    dec.tick(np.zeros(pool.n_slots, np.int32))
    src_rows = [np.asarray(buf[slot]) for buf in _leaves(pool.caches)]
    assert any(r.any() for r in src_rows)       # the slot holds real K/V

    old = _leaves(pool.caches)
    if program == "prefill":
        dec.prefill_into_slot(prompt[:4], pool.acquire())
    elif program == "tick":
        dec.tick(np.zeros(pool.n_slots, np.int32))
    else:
        dst = pool.acquire()
        dec.copy_prefix(slot, dst, 6)
        for buf, want in zip(_leaves(pool.caches), src_rows):
            np.testing.assert_array_equal(np.asarray(buf[slot]), want)
            np.testing.assert_array_equal(np.asarray(buf[dst]), want)
    new = _leaves(pool.caches)
    assert all(x.is_deleted() for x in old)
    assert not any(x.is_deleted() for x in new)
    with pytest.raises(RuntimeError, match="deleted"):
        np.asarray(old[0])
    eng.close()


def test_tick_operands_that_stand_are_handed_in_again(devices):
    """A tick's per-slot operands other than the positions change at
    admissions and ends only, and a transfer is the dearest thing the
    host does a tick (v5e: 0.25 ms each): while an operand's values stand
    the program is handed the device array made for them, a changed value
    gets a new one — also where the caller writes its own buffer in place
    — and the tokens are the closed-batch generator's."""
    from chainermn_tpu.serving import ServingEngine

    params, mesh = _params(pos_impl="rope"), _mesh(devices, 1)
    eng = ServingEngine(params, head_dim=HEAD_DIM, n_slots=3, max_total=16,
                        mesh=mesh)
    dec, pool = eng.engine, eng.pool
    prompt = np.arange(1, 7, dtype=np.int32)
    slot = pool.acquire()
    tokens = [dec.prefill_into_slot(prompt, slot)]
    override = np.zeros(pool.n_slots, np.int32)
    temps = np.zeros(pool.n_slots, np.float32)
    live = np.zeros(pool.n_slots, bool)
    live[slot] = True
    handed = []
    for step in range(4):
        # the first token is the host's, the later ones stay on the device
        override[slot] = tokens[0] if step == 0 else -1
        if step == 3:
            temps[1] = 0.5          # a free slot's: in place, as the engine
        out = dec.collect_tick(dec.launch_tick(override, None, temps, live))
        tokens.append(int(out[slot]))
        handed.append({k: v[1] for k, v in dec._operands.items()})
    assert set(handed[0]) == {"override", "keys", "temps", "live"}
    assert handed[1]["override"] is not handed[0]["override"]
    assert handed[2]["override"] is handed[1]["override"]
    assert all(handed[i]["live"] is handed[0]["live"]
               and handed[i]["keys"] is handed[0]["keys"] for i in (1, 2, 3))
    assert handed[2]["temps"] is handed[0]["temps"]
    assert handed[3]["temps"] is not handed[0]["temps"]
    np.testing.assert_array_equal(np.asarray(handed[3]["temps"]), temps)
    np.testing.assert_array_equal(np.asarray(handed[0]["temps"]), 0.0)
    np.testing.assert_array_equal(
        tokens, _oracle(params, mesh, prompt, 5))
    eng.close()


def test_pool_update_is_safe_under_concurrent_callers(devices):
    """``CachePool.update`` / ``read`` do "read the buffers → launch → bind
    the result" under the pool's lock: more threads than cores hammering
    one pool with a donating program (and readers slicing it) lose no
    update and never hand a program buffers another call has given away."""
    import threading

    import jax

    from chainermn_tpu.serving.cache_pool import CachePool

    pool = CachePool(2, 4, 1, 8, np.float32, _mesh(devices, 1))
    bump = jax.jit(lambda caches: jax.tree_util.tree_map(
        lambda c: c + 1, caches), donate_argnums=(0,))
    n_threads, per_thread = 16, 40
    errors = []

    def writer():
        try:
            for _ in range(per_thread):
                pool.update(lambda caches: (None, bump(caches)))
                pool.read(
                    lambda caches: caches[0][0][0, 0]).block_until_ready()
        except Exception as e:  # noqa: BLE001 - reported below
            errors.append(repr(e))

    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=writer) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(was)
    assert not any(t.is_alive() for t in threads)
    assert errors == []
    assert pool.calls == pool.calls_donated == n_threads * per_thread
    for buf in _leaves(pool.caches):
        assert float(np.asarray(buf).min()) == n_threads * per_thread


def test_pool_call_counters(devices):
    """``serving/pool_calls`` counts the program calls that returned the
    pool's buffers, ``serving/pool_calls_donated`` those that deleted the
    ones they were given: equal when every call was donated (a backend
    that declines would read 0, not copy silently).  Tokens stay those of
    ``lm_generate``; ``reset_stats()`` zeroes both."""
    from chainermn_tpu.serving import ServingEngine

    params, mesh = _params(), _mesh(devices, 2)
    eng = ServingEngine(params, head_dim=HEAD_DIM, n_slots=2, max_total=32,
                        mesh=mesh, queue_capacity=8)
    m = eng.metrics()
    assert m["serving/pool_calls"] == m["serving/pool_calls_donated"] == 0.0
    rng = np.random.RandomState(11)
    prompts = [rng.randint(0, VOCAB, n).astype(np.int32) for n in (5, 6, 4)]
    handles = [eng.submit(p, 5) for p in prompts]
    eng.run(steps_budget=100)
    for p, h in zip(prompts, handles):
        assert h.tokens == _oracle(params, mesh, p, 5).tolist()
    dec = eng.engine
    m = eng.metrics()
    assert m["serving/pool_calls"] == float(
        dec.tick_calls + dec.prefill_calls + dec.prefix_copies) > 0.0
    assert m["serving/pool_calls_donated"] == m["serving/pool_calls"]
    eng.reset_stats()
    m = eng.metrics()
    assert m["serving/pool_calls"] == m["serving/pool_calls_donated"] == 0.0
    eng.close()


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def test_serve_cli_inprocess(tmp_path, capsys):
    """``python -m chainermn_tpu.serve`` smoke, in-process (the 8-device
    CPU env is already up): exits 0, prints ONE summary JSON line on
    stdout, and writes a schema-valid metrics JSONL stream."""
    from chainermn_tpu import serve
    from chainermn_tpu.observability.export import read_metrics_jsonl

    metrics = tmp_path / "serve_metrics.jsonl"
    rc = serve.main([
        "--tp", "1", "--vocab", "32", "--d-model", "16", "--n-heads", "2",
        "--n-layers", "1", "--seq-len", "12", "--train-steps", "2",
        "--requests", "3", "--prompt-len", "4", "--max-new-tokens", "3",
        "--n-slots", "2", "--steps-budget", "40",
        "--metrics-out", str(metrics)])
    assert rc == 0
    out = capsys.readouterr().out.strip().splitlines()
    summary = json.loads(out[-1])
    assert summary["schema"] == "chainermn_tpu.serve.v1"
    assert len(summary["requests"]) == 3
    for row in summary["requests"]:
        assert row["status"] == "done", row
    assert summary["metrics"]["serving/tokens_total"] == 9.0
    # strict schema validation of the stream + the summary roll-up
    records = read_metrics_jsonl(str(metrics), strict=True)
    kinds = [r["kind"] for r in records]
    assert "serving_step" in kinds and kinds[-1] == "serving_summary"
    assert records[-1]["serving/tokens_per_sec"] > 0
    # ISSUE 5 acceptance: the goodput ledger PARTITIONS wall time — the
    # bucket sums reconcile against the wall clock within 5%
    g = summary["goodput"]
    assert g["coverage_frac"] >= 0.95, g
    # report fields are independently rounded to 6 decimals: tolerance
    # is one ulp-of-rounding per bucket
    assert abs(sum(g["buckets_s"].values()) - g["attributed_s"]) < 1e-5
    assert g["buckets_s"]["compile"] > 0  # first prefill+tick compiles


def test_latency_stats_bounded_by_reservoir(devices):
    """Satellite (ISSUE 5): the engine's latency stats must be O(1)
    memory — submit MORE requests than ``stats_capacity`` and the
    reservoirs stay at capacity while total_seen counts every sample and
    the percentiles stay plausible."""
    from chainermn_tpu.serving import ServingEngine

    params = _params()
    mesh = _mesh(devices, 1)
    cap = 4
    eng = ServingEngine(params, head_dim=HEAD_DIM, n_slots=2, max_total=16,
                        mesh=mesh, queue_capacity=16,
                        max_prefills_per_tick=2, stats_capacity=cap)
    rng = np.random.RandomState(3)
    handles = [eng.submit(rng.randint(0, VOCAB, 4).astype(np.int32), 3)
               for _ in range(cap * 2)]          # 8 > capacity 4
    eng.run(steps_budget=200)
    for h in handles:
        assert h.status == "done", (h.id, h.status)
    assert len(eng._ttft_ms) <= cap
    assert eng._ttft_ms.total_seen == cap * 2     # every TTFT observed
    assert len(eng._tok_lat_ms) <= cap
    assert eng._tok_lat_ms.total_seen > cap       # many ticks sampled
    m = eng.metrics()
    assert m["serving/ttft_p50_ms"] > 0
    assert m["serving/ttft_p99_ms"] >= m["serving/ttft_p50_ms"]
    # close() retires the flight/statusz provider registration so a
    # dead engine is neither pinned in memory nor reported as live
    from chainermn_tpu.observability import flight
    assert flight._PROVIDERS.get("serving") is not None
    eng.close()
    assert "serving" not in flight._PROVIDERS


@pytest.mark.slow
def test_serve_cli_subprocess(tmp_path):
    """The real ``python -m chainermn_tpu.serve`` entry point in a fresh
    interpreter (test_examples_cli.py style), with metrics + prom out."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"
    metrics = tmp_path / "m.jsonl"
    prom = tmp_path / "m.prom"
    out = subprocess.run(
        [sys.executable, "-m", "chainermn_tpu.serve", "--devices", "8",
         "--tp", "2", "--train-steps", "5", "--requests", "5",
         "--max-new-tokens", "4", "--steps-budget", "60",
         "--metrics-out", str(metrics), "--prom-out", str(prom)],
        capture_output=True, text=True, timeout=600, env=env, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-2000:]
    summary = json.loads(out.stdout.strip().splitlines()[-1])
    assert summary["schema"] == "chainermn_tpu.serve.v1"
    assert prom.read_text().count("chainermn_tpu_serving_") >= 5
