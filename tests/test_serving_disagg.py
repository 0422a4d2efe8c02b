"""Disaggregated prefill/decode serving tests (ISSUE 9).

Four layers, cheapest first:

* **Policy invariants** (jax-free): transfer-destination reservations
  are first-class :class:`SlotAllocator` state — a reserved slot is
  invisible to ``acquire``/``free_count`` (the admission-vs-arriving-
  slab deadlock fix), and commit/cancel violations are hard errors.
  The transfer cost model and the request wire dict are pure host
  Python, checked directly.
* **Engine integration** (the exactness gate): fuzzed prefill →
  transfer → decode runs over BOTH transports (the compiled local
  reshard path and the lanes pack/unpack path), GQA + rope + TP=2,
  staging and decode slots recycled on both sides — every request
  TOKEN-EXACT vs ``lm_generate`` alone, every pool drained to all-free
  at the end.  Sampling plumbs per-request rng/temperature through the
  shared tick: mixed greedy+sampled batches match
  ``lm_generate(rng=...)`` at fixed keys, and the lanes path's comm-
  ledger booking is BYTE-EXACT vs ``transfer_cost(mode="lanes")``.
* **Chaos**: a prefill worker killed mid-transfer (injected permanent
  lane fault) leaves a flight bundle NAMING the lane; its request is
  re-queued on a survivor (re-prefill, still token-exact) or — with no
  survivors — shed machine-readably in the ``AdmissionError.to_dict()``
  wire shape; decode workers are never wedged (reservations cancel,
  nothing leaks).
* **Fused vs fleets + CLI**: the same request set through the fused
  engine and the 1:1 / 2:1 fleets under the role-parallel drive ends
  done and token-identical on all three; ``serve --disagg P:D`` runs
  end to end in a fresh interpreter (slow tier).
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from chainermn_tpu.serving import AdmissionError, Request
from chainermn_tpu.serving.cache_pool import SlotAllocator
from chainermn_tpu.serving.transfer import (
    LANE_AXIS,
    LANE_OP,
    WIRE_SCHEMA,
    slab_nbytes,
    transfer_cost,
)

ROOT = os.path.join(os.path.dirname(__file__), "..")

VOCAB, D, HEADS, LAYERS = 32, 16, 4, 2
HEAD_DIM = D // HEADS


# ---------------------------------------------------------------------------
# policy invariants (no jax)
# ---------------------------------------------------------------------------

def test_reservation_state_machine():
    alloc = SlotAllocator(3)
    r = alloc.reserve()
    assert r == 0
    # invisible to admission arithmetic AND to acquire
    assert alloc.free_count == 2
    assert alloc.acquire() == 1          # never hands out the reserved slot
    alloc.check_invariants()
    alloc.commit_reservation(r)          # slab landed: reserved -> busy
    assert alloc.busy_count == 2
    alloc.release(r)
    r2 = alloc.reserve()
    alloc.cancel_reservation(r2)         # transfer failed: back to free
    assert alloc.free_count == 2 and alloc.reserved_count == 0
    alloc.check_invariants()


def test_reservation_violations_are_hard_errors():
    alloc = SlotAllocator(2)
    r = alloc.reserve()
    alloc.commit_reservation(r)
    with pytest.raises(ValueError, match="not reserved"):
        alloc.commit_reservation(r)      # double commit
    with pytest.raises(ValueError, match="not reserved"):
        alloc.cancel_reservation(r)      # cancel after commit
    with pytest.raises(ValueError, match="not reserved"):
        alloc.cancel_reservation(1)      # never reserved
    # a saturated pool reserves nothing rather than lying
    alloc.reserve()
    assert alloc.reserve() is None


def test_admission_never_races_inflight_transfers():
    """The ISSUE 9 small fix, fuzzed: random interleavings of admission
    (acquire), transfer arrivals (reserve→commit) and failures
    (reserve→cancel) never double-book a slot and never deadlock —
    because ``free_count`` (what the scheduler's
    ``min(free_slots, max_prefills_per_tick)`` reads) excludes
    reservations, a burst of arriving slabs can always land on the
    slots it reserved."""
    import random
    rng = random.Random(7)
    for _ in range(200):
        alloc = SlotAllocator(4)
        busy, reserved = [], []
        for _ in range(60):
            roll = rng.random()
            if roll < 0.35:              # admission path
                got = alloc.acquire()
                if got is not None:
                    assert got not in reserved   # the fix, literally
                    busy.append(got)
            elif roll < 0.6:             # a transfer is chosen
                got = alloc.reserve()
                if got is not None:
                    reserved.append(got)
            elif roll < 0.8 and reserved:  # slab lands
                s = reserved.pop(rng.randrange(len(reserved)))
                alloc.commit_reservation(s)
                busy.append(s)
            elif roll < 0.9 and reserved:  # transfer fails
                alloc.cancel_reservation(
                    reserved.pop(rng.randrange(len(reserved))))
            elif busy:                   # eviction
                alloc.release(busy.pop(rng.randrange(len(busy))))
            alloc.check_invariants()
            assert alloc.free_count + alloc.busy_count \
                + alloc.reserved_count == 4


def test_transfer_cost_model():
    # lanes: raw K/V payload, one noted row per transfer
    c = transfer_cost(2, 10, 8, np.float32, mode="lanes")
    assert c["ledger_bytes"] == slab_nbytes(2, 10, 8, np.float32) \
        == 2 * 2 * 10 * 8 * 4
    assert c["messages"] == 1 and c["primitive"] == LANE_OP
    # local, matching pool specs: the reshard is identity — zero wire
    c = transfer_cost(2, 10, 8, np.float32, mode="local", axis_size=2,
                      src_spec=2, dst_spec=2, copy_rows=16)
    assert c["ledger_bytes"] == 0 and c["messages"] == 0
    # local, differing specs: one accounted collective per K/V row,
    # 2 * n_layers of them — priced by the SAME reshard_cost formula
    # the parallel.reshard lint entry reconciles byte-exact
    from chainermn_tpu.parallel.reshard import reshard_cost
    c = transfer_cost(2, 10, 8, np.float32, mode="local", axis_size=2,
                      src_spec=2, dst_spec=None, copy_rows=16)
    per_row = reshard_cost((1, 16, 8), np.float32, 2, None, 2)
    assert c["ledger_bytes"] == 4 * per_row["ledger_bytes"] > 0
    with pytest.raises(ValueError, match="local.*lanes|lanes.*local"):
        transfer_cost(1, 1, 1, np.float32, mode="bogus")


def test_request_wire_shape():
    """The metadata dict that rides the lane with a slab: everything a
    decode worker needs to continue exactly, deadline shipped RELATIVE
    (monotonic clocks do not cross processes)."""
    import time

    from chainermn_tpu.serving.disagg import request_wire

    req = Request([1, 2, 3], 8, eos_id=7,
                  deadline_t=time.monotonic() + 5.0,
                  temperature=0.7, rng=np.array([1, 2], np.uint32))
    wire = request_wire(req, [4])
    assert wire["prompt"] == [1, 2, 3] and wire["tokens"] == [4]
    assert wire["max_new_tokens"] == 8 and wire["eos_id"] == 7
    assert 4.0 < wire["deadline_rel_s"] <= 5.0
    assert wire["temperature"] == pytest.approx(0.7)
    assert wire["rng"] == [1, 2]
    assert json.dumps(wire)              # JSON-serializable metadata


# ---------------------------------------------------------------------------
# integration fixtures (devices)
# ---------------------------------------------------------------------------

def _params(pos_impl="rope", n_kv_heads=None, seed=0):
    import jax
    from chainermn_tpu.parallel import init_tp_transformer_lm

    return init_tp_transformer_lm(
        jax.random.PRNGKey(seed), VOCAB, D, HEADS, LAYERS, max_len=64,
        pos_impl=pos_impl, n_kv_heads=n_kv_heads)


def _mesh(devices, tp):
    import chainermn_tpu as mn

    return mn.make_nd_mesh(("model",), (tp,), devices[:tp])


def _oracle(params, mesh, prompt, max_new, temperature=0.0, rng=None):
    from chainermn_tpu.parallel import make_lm_generator

    gen = make_lm_generator(mesh, "model", head_dim=HEAD_DIM,
                            max_new_tokens=max_new,
                            temperature=temperature)
    args = (params, np.asarray(prompt)[None])
    if rng is not None:
        args = args + (rng,)
    return np.asarray(gen(*args))[0].tolist()


def _drained(fleet):
    """Every pool back to all-free: no leaked slots, no stuck
    reservations, no pending inbox entries — on both roles."""
    for pw in fleet.prefill_workers:
        alloc = pw.pool.allocator
        alloc.check_invariants()
        assert alloc.busy_count == 0 and alloc.reserved_count == 0, \
            (pw.name, alloc.busy_count, alloc.reserved_count)
    for dw in fleet.decode_workers:
        alloc = dw.engine.pool.allocator
        alloc.check_invariants()
        assert alloc.busy_count == 0 and alloc.reserved_count == 0, \
            (dw.name, alloc.busy_count, alloc.reserved_count)
        assert not dw.inbox


# ---------------------------------------------------------------------------
# transfer exactness (the acceptance gate)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("transport", ["local", "lanes"])
def test_transfer_exactness_fuzz(devices, transport):
    """Fuzzed prefill→transfer→decode vs the fused path's oracle: GQA
    (2 KV heads over 4 query heads) + rope + TP=2, 12 staggered
    requests of mixed lengths through 2 staging slots per prefill
    worker and 3 decode slots per decode worker — both sides recycle
    slots several times over.  Every request must be token-exact vs
    ``lm_generate`` alone (which doubles as the no-cross-talk oracle:
    a transferred slab landing on a recycled slot with stale rows
    above ``pos`` must never leak into another sequence), and every
    allocator must drain to all-free."""
    from chainermn_tpu.serving import build_disagg_fleet

    params = _params(pos_impl="rope", n_kv_heads=2)
    mesh = _mesh(devices, 2)
    fleet = build_disagg_fleet(
        params, 2, 2, head_dim=HEAD_DIM, max_total=16, n_slots=3,
        staging_slots=2, mesh=mesh, queue_capacity=16,
        transport_mode=transport)
    try:
        rng = np.random.RandomState(5)
        prompts = [rng.randint(0, VOCAB, rng.randint(3, 7))
                   .astype(np.int32) for _ in range(12)]
        max_new = [int(rng.randint(2, 8)) for _ in range(12)]
        handles = []
        for i in range(12):
            handles.append(fleet.submit(prompts[i], max_new[i]))
            if i % 3 == 2:
                fleet.step()             # stagger arrivals across rounds
        fleet.run(steps_budget=600)

        for i, h in enumerate(handles):
            assert h.status == "done", (i, h.status, h.finish_reason)
            want = _oracle(params, mesh, prompts[i], max_new[i])
            assert h.tokens == want, (i, h.tokens, want)
        m = fleet.metrics()
        assert m["disagg/transfers_total"] == 12.0
        # the transfer wall landed in its OWN goodput bucket, not host
        assert sum(pw.goodput.buckets()["transfer"]
                   for pw in fleet.prefill_workers) > 0.0
        # role split is real: decode workers never prefilled, prefill
        # workers never ticked
        for dw in fleet.decode_workers:
            assert dw.engine.engine.prefill_calls == 0
        for pw in fleet.prefill_workers:
            assert pw.engine.tick_calls == 0
            assert pw.engine.prefill_calls > 0
        _drained(fleet)
    finally:
        fleet.close()


@pytest.mark.parametrize("transport", ["local", "lanes"])
def test_role_parallel_drive_shares_a_donated_staging_pool(devices,
                                                           transport):
    """The role-parallel drive: the prefill thread dispatches (donating)
    prefills into a staging pool while the decode thread reads that pool
    for a transfer (local) or the prefill thread packs it while ticks run
    (lanes).  Every program call takes the pool's lock for its launch, so
    a few hundred transfers run with no "Array has been deleted" — which,
    un-locked, the donation turns the old silent race into."""
    from chainermn_tpu.serving import build_disagg_fleet

    params = _params(pos_impl="rope", n_kv_heads=2)
    mesh = _mesh(devices, 1)
    fleet = build_disagg_fleet(
        params, 1, 1, head_dim=HEAD_DIM, max_total=16, n_slots=4,
        staging_slots=3, mesh=mesh, queue_capacity=512,
        transport_mode=transport)
    n = 200
    rng = np.random.RandomState(9)
    prompts = [rng.randint(0, VOCAB, rng.randint(3, 7)).astype(np.int32)
               for _ in range(n)]
    try:
        fleet.start()
        handles = [fleet.submit(p, 2) for p in prompts]
        for h in handles:
            assert h.wait(120), "the drive stalled (a role driver died?)"
        fleet.stop()
        assert not fleet._threads            # both drivers joined
        assert all(h.status == "done" for h in handles)
        for i in (0, n // 2, n - 1):
            assert handles[i].tokens == _oracle(params, mesh, prompts[i], 2)
        m = fleet.metrics()
        assert m["disagg/transfers_total"] >= float(n)
        for pool in ([pw.pool for pw in fleet.prefill_workers]
                     + [dw.engine.pool for dw in fleet.decode_workers]):
            assert pool.calls_donated == pool.calls > 0
        _drained(fleet)
    finally:
        fleet.close()


def test_sampling_token_exact_vs_lm_generate(devices):
    """The ISSUE 9 sampling satellite: per-request rng/temperature ride
    ``Request`` through the shared decode tick, and a sampled request
    served in a shared pool (fused engine AND a disaggregated fleet,
    where the key crosses the transfer plane) emits the exact tokens
    ``lm_generate(rng=...)`` draws alone at the same key.  Greedy rows
    share the tick unchanged — mixed batches keep both exact."""
    import jax

    from chainermn_tpu.serving import ServingEngine, build_disagg_fleet

    params = _params(pos_impl="rope", n_kv_heads=2)
    mesh = _mesh(devices, 2)
    rng = np.random.RandomState(3)
    prompts = [rng.randint(0, VOCAB, 5).astype(np.int32)
               for _ in range(4)]
    temps = [0.0, 0.7, 1.3, 0.7]
    keys = [None if t == 0 else jax.random.PRNGKey(100 + i)
            for i, t in enumerate(temps)]
    oracles = [_oracle(params, mesh, p, 6, temperature=t, rng=k)
               for p, t, k in zip(prompts, temps, keys)]
    # two requests, same temperature, different keys: sampling is live
    assert oracles[1] != oracles[3] or temps[1] == 0.0

    eng = ServingEngine(params, head_dim=HEAD_DIM, n_slots=4,
                        max_total=16, mesh=mesh, queue_capacity=8,
                        max_prefills_per_tick=4)
    try:
        hs = [eng.submit(p, 6, temperature=t, rng=k)
              for p, t, k in zip(prompts, temps, keys)]
        eng.run(steps_budget=100)
        for h, want in zip(hs, oracles):
            assert h.tokens == want, ("fused", h.tokens, want)
    finally:
        eng.close()

    fleet = build_disagg_fleet(params, 1, 1, head_dim=HEAD_DIM,
                               max_total=16, n_slots=4, staging_slots=2,
                               mesh=mesh, queue_capacity=8,
                               transport_mode="lanes")
    try:
        hs = [fleet.submit(p, 6, temperature=t, rng=k)
              for p, t, k in zip(prompts, temps, keys)]
        fleet.run(steps_budget=400)
        for h, want in zip(hs, oracles):
            assert h.tokens == want, ("disagg", h.tokens, want)
        _drained(fleet)
    finally:
        fleet.close()


def test_sampling_requires_explicit_rng(devices):
    """The lm_generate rng contract holds at every submit face: a
    silent default key would make every sampled request draw identical
    sequences."""
    from chainermn_tpu.serving import ServingEngine, build_disagg_fleet

    params = _params()
    mesh = _mesh(devices, 2)
    eng = ServingEngine(params, head_dim=HEAD_DIM, n_slots=2,
                        max_total=16, mesh=mesh, queue_capacity=4)
    try:
        with pytest.raises(ValueError, match="explicit"):
            eng.submit([1, 2], 4, temperature=0.8)
    finally:
        eng.close()
    fleet = build_disagg_fleet(params, 1, 1, head_dim=HEAD_DIM,
                               max_total=16, n_slots=2, staging_slots=1,
                               mesh=mesh, queue_capacity=4)
    try:
        with pytest.raises(ValueError, match="explicit"):
            fleet.submit([1, 2], 4, temperature=0.8)
    finally:
        fleet.close()


def test_lanes_ledger_bytes_reconcile(devices):
    """Acceptance: every lanes-mode transfer books its RAW slab bytes
    as a noted ``kv_transfer_lane@dcn`` comm-ledger row, byte-exact vs
    the static ``transfer_cost(mode='lanes')`` prediction — the shard-
    flow discipline applied to the transfer plane (the local path's
    zero-collective contract is held by the ``serving.kv_transfer``
    lint entry point)."""
    from chainermn_tpu import observability as obs
    from chainermn_tpu.serving import build_disagg_fleet

    params = _params(pos_impl="rope", n_kv_heads=2)
    mesh = _mesh(devices, 2)
    obs.reset_all()
    obs.enable()
    try:
        fleet = build_disagg_fleet(
            params, 1, 1, head_dim=HEAD_DIM, max_total=16, n_slots=3,
            staging_slots=2, mesh=mesh, queue_capacity=8,
            transport_mode="lanes")
        rng = np.random.RandomState(1)
        lens = [3, 5, 6]
        handles = [fleet.submit(rng.randint(0, VOCAB, n)
                                .astype(np.int32), 4) for n in lens]
        fleet.run(steps_budget=300)
        assert all(h.status == "done" for h in handles)
        pool = fleet.prefill_workers[0].pool
        want = sum(
            transfer_cost(pool.n_layers, n, pool.kv_dim,
                          pool.caches[0][0].dtype,
                          mode="lanes")["ledger_bytes"]
            for n in lens)
        row = obs.comm_report()["per_op"][f"{LANE_OP}@{LANE_AXIS}"]
        assert row["bytes"] == want, (row, want)
        assert row["calls"] == len(lens)
        assert fleet.plane.bytes_moved == want
        fleet.close()
    finally:
        obs.disable()
        obs.reset_all()


def test_comm_kv_lane_transport_backs_the_plane(devices):
    """The cross-process wire is REACHABLE: ``build_disagg_fleet(
    comm=..., transport_mode='lanes')`` runs every transfer through
    ``CommunicatorBase.kv_lane_transport()`` — the jax.distributed KV
    store on a multi-controller gang, the shared per-communicator
    loopback store here — not a private plane-internal dict."""
    import chainermn_tpu as mn
    from chainermn_tpu.serving import build_disagg_fleet

    comm = mn.create_communicator("xla")
    transport = comm.kv_lane_transport()
    # one store per communicator (publisher and consumer must see the
    # same tags), stable across calls
    assert comm.kv_lane_transport() is transport

    params = _params(pos_impl="rope", n_kv_heads=2)
    mesh = _mesh(devices, 2)
    fleet = build_disagg_fleet(
        params, 1, 1, head_dim=HEAD_DIM, max_total=16, n_slots=2,
        staging_slots=1, mesh=mesh, queue_capacity=4,
        transport_mode="lanes", comm=comm)
    assert fleet.plane.transport is transport
    rng = np.random.RandomState(3)
    prompt = rng.randint(0, VOCAB, 5).astype(np.int32)
    h = fleet.submit(prompt, 4)
    fleet.run(steps_budget=200)
    assert h.status == "done"
    assert fleet.plane.lane_transfers == 1
    assert h.tokens == _oracle(params, mesh, prompt, 4)
    _drained(fleet)
    # consumed tags are GC'd from the shared store, not leaked
    assert not transport._store
    fleet.close()


def test_unpack_refuses_foreign_slabs(devices):
    """A receiver must refuse a slab it cannot interpret, never guess:
    wrong schema, mismatched layer/kv geometry, or an over-long slab
    are all hard errors BEFORE any buffer is touched."""
    import pickle

    from chainermn_tpu.serving.cache_pool import CachePool
    from chainermn_tpu.serving.transfer import KvTransferPlane

    mesh = _mesh(devices, 2)
    pool = CachePool(2, 8, LAYERS, 2 * HEAD_DIM, np.float32, mesh,
                     "model")
    plane = KvTransferPlane()
    ok = {"schema": WIRE_SCHEMA, "meta": {}, "pos": 2,
          "n_layers": LAYERS, "kv_dim": 2 * HEAD_DIM,
          "dtype": "float32",
          "rows": [(np.zeros((2, 2 * HEAD_DIM), np.float32),) * 2
                   for _ in range(LAYERS)]}
    with pytest.raises(ValueError, match="schema"):
        plane.unpack_into(pickle.dumps(dict(ok, schema="bogus.v9")),
                          pool, 0)
    with pytest.raises(ValueError, match="mismatch"):
        plane.unpack_into(pickle.dumps(dict(ok, n_layers=7)), pool, 0)
    with pytest.raises(ValueError, match="capacity"):
        plane.unpack_into(pickle.dumps(dict(ok, pos=99)), pool, 0)


def test_reservations_gate_admission_no_deadlock(devices):
    """The small-fix end to end: while a decode slot is held by an
    in-flight transfer's reservation, the prefill worker's admission
    budget (``min(free staging, decode free slots)``) sees ZERO decode
    capacity and defers — it can never hand a queued prompt the slot
    an arriving slab owns.  When the reservation resolves, the fleet
    drains normally."""
    from chainermn_tpu.serving import build_disagg_fleet

    params = _params()
    mesh = _mesh(devices, 2)
    fleet = build_disagg_fleet(params, 1, 1, head_dim=HEAD_DIM,
                               max_total=16, n_slots=1, staging_slots=2,
                               mesh=mesh, queue_capacity=8,
                               transport_mode="local")
    try:
        h = fleet.submit([1, 2, 3], 4)
        dpool = fleet.decode_workers[0].engine.pool
        held = dpool.reserve()           # a foreign in-flight transfer
        assert fleet.decode_free_slots() == 0
        for _ in range(5):
            fleet.step()
        # deferred, not deadlocked and not stolen: still queued, the
        # reserved slot untouched
        assert h.status == "queued", (h.status, h.finish_reason)
        assert dpool.allocator.reserved_count == 1
        dpool.cancel_reservation(held)   # the slab's owner resolves it
        fleet.run(steps_budget=200)
        assert h.status == "done"
        assert h.tokens == _oracle(params, mesh, [1, 2, 3], 4)
        _drained(fleet)
    finally:
        fleet.close()


def test_transfer_backpressure_requeues_not_strands(devices):
    """A finished slab whose destination pool saturated between the
    admission-budget check and the transfer (the race the requeue
    fallback exists for): the request goes back to the HEAD of the
    prefill queue — never shed, never stranded — the staging slot is
    recycled, and the fleet completes it token-exactly once capacity
    frees."""
    from chainermn_tpu.serving import build_disagg_fleet
    from chainermn_tpu.serving.frontend import RequestHandle

    params = _params()
    mesh = _mesh(devices, 2)
    fleet = build_disagg_fleet(params, 1, 1, head_dim=HEAD_DIM,
                               max_total=16, n_slots=1, staging_slots=2,
                               mesh=mesh, queue_capacity=8,
                               transport_mode="local")
    try:
        pw = fleet.prefill_workers[0]
        dpool = fleet.decode_workers[0].engine.pool
        import time as _time

        req = Request([1, 2, 3], 4, trace_id="req-test-backpressure")
        req.timestamps["submitted"] = _time.monotonic()
        handle = RequestHandle(req)
        slot = pw.pool.acquire()
        first = pw.engine.prefill_into_slot([1, 2, 3], slot)
        held = dpool.reserve()           # destination saturates
        assert fleet.transfer_out(pw, req, slot, first) is False
        assert fleet.metrics()["disagg/requeued_total"] == 1.0
        assert pw.scheduler.queue_depth == 1          # back at the head
        assert pw.pool.allocator.busy_count == 0      # staging recycled
        dpool.cancel_reservation(held)
        fleet.run(steps_budget=200)
        assert handle.status == "done"
        assert handle.tokens == _oracle(params, mesh, [1, 2, 3], 4)
        _drained(fleet)
    finally:
        fleet.close()


# ---------------------------------------------------------------------------
# chaos: kill a prefill worker mid-transfer
# ---------------------------------------------------------------------------

@pytest.fixture
def lane_injector():
    from chainermn_tpu.communicators.base import set_lane_fault_injector

    set_lane_fault_injector(None)
    yield set_lane_fault_injector
    set_lane_fault_injector(None)


def test_chaos_kill_prefill_worker_mid_transfer(devices, lane_injector,
                                                tmp_path):
    """THE chaos satellite: an injected permanent fault on the first
    transfer's publish lane kills prefill0 mid-transfer.  The fleet
    must (a) mark the victim dead and dump a flight bundle whose ring
    NAMES the lane, (b) re-queue the in-flight request on the survivor
    — a re-prefill, still token-exact — plus re-dispatch the victim's
    queued work, (c) never wedge a decode worker: the destination
    reservation cancels and every pool drains."""
    from chainermn_tpu.serving import build_disagg_fleet

    params = _params(pos_impl="rope", n_kv_heads=2)
    mesh = _mesh(devices, 2)
    bundles = tmp_path / "bundles"
    fleet = build_disagg_fleet(
        params, 2, 1, head_dim=HEAD_DIM, max_total=16, n_slots=3,
        staging_slots=2, mesh=mesh, queue_capacity=8,
        transport_mode="lanes", bundle_dir=str(bundles))
    fired = {"n": 0}

    def injector(lane, attempt):
        if lane.startswith("kv_transfer/put/") and fired["n"] < 1:
            fired["n"] += 1
            raise RuntimeError("injected permanent lane fault (chaos)")

    lane_injector(injector)
    try:
        rng = np.random.RandomState(1)
        prompts = [rng.randint(0, VOCAB, 5).astype(np.int32)
                   for _ in range(4)]
        handles = [fleet.submit(p, 5) for p in prompts]
        fleet.run(steps_budget=600)

        assert [w.dead for w in fleet.prefill_workers] == [True, False]
        for i, h in enumerate(handles):
            assert h.status == "done", (i, h.status, h.finish_reason)
            assert h.tokens == _oracle(params, mesh, prompts[i], 5)
        m = fleet.metrics()
        assert m["disagg/requeued_total"] >= 1
        assert m["disagg/dead_prefill_workers"] == 1.0
        _drained(fleet)

        # the evidence: a kv_transfer_fault bundle whose ring names the
        # victim lane
        dirs = sorted(os.listdir(bundles))
        assert dirs and "kv_transfer_fault" in dirs[-1], dirs
        ring = (bundles / dirs[-1] / "flight.jsonl").read_text()
        assert "kv_transfer/put/" in ring
        assert "worker_lost" in ring
    finally:
        fleet.close()


def test_chaos_no_survivors_sheds_machine_readably(devices,
                                                   lane_injector):
    """Every prefill worker dead: already-accepted requests are shed
    with the FULL ``AdmissionError.to_dict()`` wire shape attached to
    their handles (reason ``worker_lost`` + retry_after_ms +
    queue_depth), new submits reject with the same reason, and the
    decode worker is left clean — never wedged."""
    from chainermn_tpu.serving import build_disagg_fleet

    params = _params()
    mesh = _mesh(devices, 2)
    fleet = build_disagg_fleet(params, 1, 1, head_dim=HEAD_DIM,
                               max_total=16, n_slots=2, staging_slots=2,
                               mesh=mesh, queue_capacity=8,
                               transport_mode="lanes")
    lane_injector(lambda lane, attempt: (_ for _ in ()).throw(
        RuntimeError("injected permanent lane fault (chaos)"))
        if lane.startswith("kv_transfer/put/") else None)
    try:
        h1 = fleet.submit([1, 2, 3], 4)
        h2 = fleet.submit([4, 5, 6], 4)
        fleet.run(steps_budget=200)
        for h in (h1, h2):
            assert h.finish_reason == "shed", (h.status, h.finish_reason)
            pay = h.shed_payload
            assert pay is not None
            assert pay["reason"] == "worker_lost"
            assert set(pay) >= {"reason", "detail", "retry_after_ms",
                                "queue_depth"}
            assert json.dumps(pay)       # 429-body serializable
        # a new submit against the dead fleet rejects the same way
        with pytest.raises(AdmissionError) as e:
            fleet.submit([7, 8], 4)
        assert e.value.reason == "worker_lost"
        assert fleet.rejection_counters()["worker_lost"] >= 3
        _drained(fleet)
    finally:
        fleet.close()


# ---------------------------------------------------------------------------
# fused engine vs disaggregated fleets: the same requests, the same tokens
# ---------------------------------------------------------------------------

def test_fused_and_disagg_fleets_finish_the_same_requests(devices):
    """The same 16 requests through the fused engine and through 1:1 and
    2:1 P:D fleets under the role-parallel drive: every request reaches
    exactly one terminal outcome (done, none shed), the fleets emit the
    fused engine's tokens, each request crossed the transfer plane, and
    the decode side's goodput ledger carries the queue-wait/compute
    split.  (Whether disaggregation tightens the decode tick gap is a
    chip measurement, ROADMAP R5; nothing here reads a clock.)"""
    from chainermn_tpu.serving import ServingEngine, build_disagg_fleet

    params = _params()
    mesh = _mesh(devices, 1)
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, VOCAB, 8).astype(np.int32) for _ in range(16)]
    new = 6

    def finish(service, handles):
        for h in handles:
            assert h.wait(120), "the drive stalled"
        service.stop()
        assert [h.status for h in handles] == ["done"] * len(handles)
        return [h.tokens for h in handles]

    eng = ServingEngine(params, head_dim=HEAD_DIM, n_slots=4,
                        max_total=16, mesh=mesh, queue_capacity=16)
    try:
        eng.start()
        fused = finish(eng, [eng.submit(p, new) for p in prompts])
        assert eng.metrics()["serving/tokens_per_sec"] > 0
    finally:
        eng.close()

    for n_p, n_d in ((1, 1), (2, 1)):
        fleet = build_disagg_fleet(
            params, n_p, n_d, head_dim=HEAD_DIM, max_total=16, n_slots=4,
            staging_slots=2, mesh=mesh, queue_capacity=16,
            transport_mode="local")
        try:
            fleet.start()
            tokens = finish(fleet, [fleet.submit(p, new) for p in prompts])
            assert tokens == fused, (n_p, n_d)
            m = fleet.metrics()
            assert m["disagg/transfers_total"] >= len(prompts)
            assert m["disagg/transfer_p50_ms"] >= 0
            assert m["disagg/fleet_tokens_per_sec"] > 0
            for dw in fleet.decode_workers:
                buckets = dw.engine.goodput.buckets()
                assert buckets["compute"] > 0 and "queue_wait" in buckets
            _drained(fleet)
        finally:
            fleet.close()


def test_concurrent_submissions_during_worker_loss(devices,
                                                   lane_injector):
    """ISSUE 10 satellite: fuzz the worker_lost shed path under
    CONCURRENT submissions — N threads submitting while a prefill
    worker dies mid-transfer.  Invariants: every accepted request has
    exactly ONE terminal outcome (done with tokens XOR shed with the
    machine-readable payload — never both, never neither), refcounts
    drain to 0, and no reservation leaks on any pool."""
    import threading

    from chainermn_tpu.serving import build_disagg_fleet

    params = _params()
    mesh = _mesh(devices, 2)
    fleet = build_disagg_fleet(
        params, 2, 1, head_dim=HEAD_DIM, max_total=16, n_slots=3,
        staging_slots=2, mesh=mesh, queue_capacity=32,
        transport_mode="lanes", max_transfer_attempts=2)
    fired = {"n": 0}

    def injector(lane, attempt):
        # the 3rd publish dies permanently: the fleet is mid-burst,
        # with queued work on the victim and threads still submitting
        if lane.startswith("kv_transfer/put/"):
            fired["n"] += 1
            if fired["n"] == 3:
                raise RuntimeError(
                    "injected permanent lane fault (chaos)")

    import time

    lane_injector(injector)
    n_threads, per_thread = 4, 3
    handles, rejected = [], []
    lock = threading.Lock()
    rng = np.random.RandomState(5)
    prompts = [rng.randint(0, VOCAB, 4).astype(np.int32)
               for _ in range(n_threads * per_thread)]

    def submitter(t):
        for i in range(per_thread):
            p = prompts[t * per_thread + i]
            try:
                h = fleet.submit(p, 4)
                with lock:
                    handles.append((p, h))
            except AdmissionError as e:
                with lock:
                    rejected.append(e.to_dict())
            # interleave against the main thread's driving steps
            time.sleep(0.001 * (t + 1))

    submitters = [threading.Thread(target=submitter, args=(t,))
                  for t in range(n_threads)]
    for s in submitters:
        s.start()
    # ONE driving thread (the disagg drive contract) stepping while
    # the N submitter threads race it
    t0 = time.time()
    while any(s.is_alive() for s in submitters):
        assert time.time() - t0 < 120, "submitter thread hung"
        fleet.step()
    for s in submitters:
        s.join(timeout=10)
    while fleet.run(steps_budget=50):
        assert time.time() - t0 < 180, "fleet did not drain"
    try:
        fleet.run(steps_budget=600)      # settle any tail
        assert fired["n"] >= 3           # the fault actually fired
        done = shed = 0
        for p, h in handles:
            if h.status == "done":
                done += 1
                # done XOR shed: a completed request never carries a
                # shed payload (re-dispatched-and-completed is NOT
                # also shed)
                assert h.shed_payload is None, h.shed_payload
                assert h.tokens == _oracle(params, mesh, p, 4)
            else:
                shed += 1
                assert h.finish_reason == "shed", (h.status,
                                                   h.finish_reason)
                pay = h.shed_payload
                assert pay is not None and pay["reason"] == "worker_lost"
                assert h.tokens == []    # never half-served
        # every accepted request reached exactly one terminal state
        assert done + shed == len(handles)
        assert done > 0                  # the survivor kept serving
        # no reservation leaks, refcounts drained, invariants hold
        _drained(fleet)
        m = fleet.metrics()
        assert m["disagg/dead_prefill_workers"] == 1.0
        for r in rejected:
            assert r["reason"] in ("queue_full", "worker_lost",
                                   "shed_slo")
    finally:
        fleet.close()


# ---------------------------------------------------------------------------
# CLI (slow tier)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_serve_cli_disagg_subprocess(tmp_path):
    """``python -m chainermn_tpu.serve --disagg 1:2 --transport lanes
    --temperature 0.8`` in a fresh interpreter: every request done,
    transfers booked, disagg gauges in the Prometheus textfile."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"
    metrics = tmp_path / "m.jsonl"
    prom = tmp_path / "m.prom"
    out = subprocess.run(
        [sys.executable, "-m", "chainermn_tpu.serve", "--devices", "8",
         "--tp", "2", "--train-steps", "5", "--requests", "5",
         "--max-new-tokens", "4", "--steps-budget", "300",
         "--disagg", "1:2", "--transport", "lanes",
         "--temperature", "0.8",
         "--metrics-out", str(metrics), "--prom-out", str(prom)],
        capture_output=True, text=True, timeout=600, env=env, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-2000:]
    summary = json.loads(out.stdout.strip().splitlines()[-1])
    assert summary["schema"] == "chainermn_tpu.serve.v1"
    assert summary["disagg"] == "1:2"
    assert all(r["status"] == "done" for r in summary["requests"])
    assert summary["metrics"]["disagg/transfers_total"] == 5.0
    assert summary["metrics"]["disagg/plane/bytes_moved"] > 0
    assert prom.read_text().count("chainermn_tpu_disagg_") >= 5
    # the metrics stream carries the disagg summary record
    kinds = [json.loads(line).get("kind")
             for line in metrics.read_text().splitlines() if line]
    assert "disagg_summary" in kinds
