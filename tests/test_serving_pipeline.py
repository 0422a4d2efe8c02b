"""One served tick in flight (ISSUE 32): ``ServingEngine.step`` launches
tick N+1, fed tick N's tokens where they are on the device, before it reads
tick N back.  Tiny models on the CPU, three block vocabularies: plain rows
(GPT-2 style), latent rows with experts (DeepSeek style: the result carries
counts and routes), and state layers beside rows (Kimi style: a stray row
touches memory that outlives it).

The oracle is each request ALONE, one tick at a time read back before the
next (``DecodeEngine.tick``, the direct callers' face, every token handed in
by the host); the plain model is also held to ``lm_generate``, which has no
``arch`` and so cannot judge the other two."""

import importlib.util
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import chainermn_tpu as mn

HERE = os.path.dirname(os.path.abspath(__file__))
VOCAB, D, HEADS, LAYERS = 32, 16, 4, 2


def _load(name):
    spec = importlib.util.spec_from_file_location(
        "pipeline_" + name, os.path.join(HERE, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def mesh(devices):
    return mn.make_nd_mesh(("model",), (1,), devices[:1])


@pytest.fixture(scope="module")
def models():
    """``name -> (params, head_dim, arch, vocab)``, built on first use."""
    built = {}

    def get(name):
        if name not in built:
            if name == "plain":
                from chainermn_tpu.parallel import init_tp_transformer_lm
                built[name] = (init_tp_transformer_lm(
                    jax.random.PRNGKey(0), VOCAB, D, HEADS, LAYERS,
                    max_len=64, pos_impl="learned"), D // HEADS, None, VOCAB)
            else:
                t = _load({"expert": "test_deepseek_serving",
                           "state": "test_kimi_linear_serving",
                           "ring": "test_laguna_serving"}[name])
                built[name] = (
                    t.ref.init_params(jax.random.PRNGKey(3), t.CFG,
                                      jnp.float32),
                    t.HEAD_DIM, t.arch_of(t.CFG), t.CFG["vocab_size"])
        return built[name]
    return get


def _engine(model, mesh, **kw):
    from chainermn_tpu.serving import ServingEngine

    params, head_dim, arch, _ = model
    kw = dict(dict(n_slots=3, max_total=48, prefill_bucket=8,
                   queue_capacity=16, spill_bytes=0), **kw)
    return ServingEngine(params, head_dim=head_dim, mesh=mesh, arch=arch,
                         **kw)


def _alone(eng, prompt, max_new, rng=None, temperature=0.0):
    """``(tokens, routes)`` of one request served alone on ``eng``'s own
    programs, every tick read back before the next is launched."""
    de, pool = eng.engine, eng.pool
    n = pool.n_slots
    slot = eng._acquire_slot()           # a free slot, or a cached one's
    toks = [de.prefill_into_slot(prompt, slot, rng=rng,
                                 temperature=temperature)]
    routes = [de.prefill_routes]
    keys, temps = np.zeros((n, 2), np.uint32), np.zeros(n, np.float32)
    if rng is not None:
        keys[slot], temps[slot] = np.asarray(rng, np.uint32), temperature
    last = np.zeros(n, np.int32)
    while len(toks) < max_new:
        last[slot] = toks[-1]
        toks.append(int(de.tick(last, keys, temps)[slot]))
        routes.append(None if de.tick_routes is None
                      else de.tick_routes[slot].copy())
    pool.release(slot)
    return toks, routes


def _drive(eng, limit=400):
    n = 0
    while eng.scheduler.queue_depth or eng.pool.busy_count:
        eng.step()
        n += 1
        assert n < limit, "the engine does not drain"
    assert eng._in_flight is None      # nothing busy => nothing in flight


def _requests(vocab, seed, n):
    rng = np.random.RandomState(seed)
    out = []
    for i in range(n):
        sampled = i % 3 == 1
        out.append({
            "prompt": rng.randint(0, vocab, rng.randint(3, 14)).astype(
                np.int32),
            "max_new": int(rng.randint(1, 12)),
            "temperature": 0.8 if sampled else 0.0,
            "rng": jax.random.PRNGKey(100 + i) if sampled else None})
    return out


# --------------------------------------------------------------------------
# (a) token-exact, (d) drain, (f) routes and counts of the collected tick
# --------------------------------------------------------------------------

@pytest.mark.parametrize("name", ["plain", "expert", "state"])
def test_pipelined_step_is_token_exact(models, mesh, name):
    model = models(name)
    eng = _engine(model, mesh, prefix_cache=False)
    reqs = _requests(model[3], 11, 7)
    want = [_alone(eng, r["prompt"], r["max_new"], r["rng"],
                   r["temperature"]) for r in reqs]
    eng.reset_stats()
    ticks0 = eng.engine.tick_calls
    over0 = eng.engine.tick_launches_overlapped
    streamed = {}
    handles = []
    for i, r in enumerate(reqs):       # admitted at different steps
        handles.append(eng.submit(
            r["prompt"], r["max_new"], temperature=r["temperature"],
            rng=r["rng"], on_token=lambda t, rid: streamed.setdefault(
                rid, []).append(t)))
        if i % 2:
            eng.step()
            eng.step()
    _drive(eng)
    for h, r, (toks, routes) in zip(handles, reqs, want):
        assert h.status == "done" and h.finish_reason == "max_tokens"
        assert h.tokens == toks, (name, h.id)
        assert streamed[h.id] == toks
        if routes[0] is not None:      # the routes of the tick that emitted
            assert len(h.routes) == len(toks)
            for got, ref in zip(h.routes, routes):
                np.testing.assert_array_equal(got, ref)
    m = eng.metrics()
    ticks = m["serving/tick_calls"] - ticks0
    overlapped = m["serving/tick_launches_overlapped"] - over0
    assert 0 < overlapped <= ticks
    # every end was foreseen by its token count: no row was launched for a
    # request that had all its tokens, so none was dropped
    assert m["serving/tick_rows_discarded"] == 0
    if name == "plain":
        from chainermn_tpu.parallel import make_lm_generator
        for h, r in zip(handles, reqs):
            gen = make_lm_generator(
                mesh, "model", head_dim=model[1], max_new_tokens=r["max_new"],
                temperature=r["temperature"])
            args = (model[0], r["prompt"][None]) + (
                (r["rng"],) if r["rng"] is not None else ())
            assert h.tokens == np.asarray(gen(*args))[0].tolist()
    else:
        # the counts are the collected ticks': a row a token consumed, and
        # no row for a slot whose request had every token in flight
        arch = model[2]
        per_row = arch.moe.top_k * sum(k == "moe" for k in arch.layer_kinds)
        rows = sum(len(r["prompt"]) + r["max_new"] - 1 for r in reqs)
        assert m["serving/moe_assignments_total"] == rows * per_row
    eng.close()


# --------------------------------------------------------------------------
# (b) an end the host cannot foresee, with a tick in flight
# --------------------------------------------------------------------------

@pytest.mark.parametrize("how", ["eos", "deadline"])
@pytest.mark.parametrize("name", ["plain", "state"])
def test_unforeseen_end_drops_the_row_in_flight(models, mesh, name, how):
    model = models(name)
    eng = _engine(model, mesh)
    rng = np.random.RandomState(5)
    vocab = model[3]
    p_end, p_long, p_next = (rng.randint(0, vocab, n).astype(np.int32)
                             for n in (5, 7, 6))
    full, _ = _alone(eng, p_end, 12)
    want_long, _ = _alone(eng, p_long, 14)
    want_next, _ = _alone(eng, p_next, 5)
    # the first token that has not occurred before it, past the prefill's
    k = next(i for i in range(1, 11) if full[i] not in full[:i])
    eng.reset_stats()
    streamed = {}
    on_token = lambda t, rid: streamed.setdefault(rid, []).append(t)
    h_long = eng.submit(p_long, 14, on_token=on_token)
    h_end = eng.submit(p_end, 12, on_token=on_token,
                       eos_id=full[k] if how == "eos" else None,
                       deadline_s=1e3)
    while h_end.status != "running" or len(h_end.tokens) < (
            k + 1 if how == "eos" else 3):
        eng.step()
        if how == "deadline" and len(h_end.tokens) >= 2 \
                and h_end.status == "running":
            h_end._req.deadline_t = time.monotonic()   # due at the next read
        if h_end.status != "running" and h_end.finish_reason:
            break
    slot = next(iter(set(range(3)) - set(eng._running)))  # the slot it left
    n_end = len(h_end.tokens)
    assert h_end.finish_reason == how
    assert h_end.tokens == full[:n_end] and (how != "eos" or n_end == k + 1)
    # its row of the tick in flight consumed its last token: the slot is
    # booked where the device stands, all of prompt + generated
    assert eng.pool.pos[slot] == len(p_end) + n_end
    entry = eng.prefix_cache.match(list(p_end) + h_end.tokens + [0])[0]
    assert entry is not None and entry.slot == slot
    assert entry.length == len(p_end) + n_end
    # the next occupant of the pool, and a hit ON the donated slot, which
    # reads the stray row (and on the state layout, the state it left)
    h_next = eng.submit(p_next, 5, on_token=on_token)
    p_hit = np.asarray(list(p_end) + h_end.tokens + [3, 1], np.int32)
    h_hit = eng.submit(p_hit, 4, on_token=on_token)
    _drive(eng)
    m = eng.metrics()
    assert m["serving/tick_rows_discarded"] == 1
    assert streamed[h_end.id] == h_end.tokens       # no stray token, anywhere
    assert h_long.tokens == want_long == streamed[h_long.id]
    assert h_next.tokens == want_next
    assert m["serving/prefix/hits"] >= 1
    assert m["serving/prefix/state_misses"] == 0
    hit_alone, _ = _alone(eng, p_hit, 4)
    assert h_hit.tokens == hit_alone
    eng.close()


# --------------------------------------------------------------------------
# (c) the override path: a prefix hit's owed prompt tokens, an install
# --------------------------------------------------------------------------

@pytest.mark.parametrize("name", ["plain", "expert"])
def test_prefix_hit_suffix_feeds_through_the_override(models, mesh, name):
    model = models(name)
    eng = _engine(model, mesh)
    rng = np.random.RandomState(9)
    vocab = model[3]
    base = rng.randint(0, vocab, 8).astype(np.int32)
    other = rng.randint(0, vocab, 6).astype(np.int32)
    hit = np.concatenate([base, rng.randint(0, vocab, 4).astype(np.int32)])
    want_other, _ = _alone(eng, other, 12)
    want_hit, _ = _alone(eng, hit, 6)
    first = eng.submit(base, 3)
    _drive(eng)                          # donates ``base``'s rows
    assert first.status == "done"
    eng.reset_stats()
    h_other = eng.submit(other, 12)
    for _ in range(3):
        eng.step()                       # a tick is in flight when it lands
    assert eng._in_flight is not None
    h_hit = eng.submit(hit, 6)
    _drive(eng)
    m = eng.metrics()
    assert m["serving/prefix/hits"] == 1
    assert h_hit.tokens == want_hit and h_other.tokens == want_other
    if name == "expert":                 # an owed prompt token emits nothing
        assert len(h_hit.routes) == len(h_hit.tokens)
    eng.close()


def test_installed_request_feeds_through_the_override(devices):
    """The disaggregated decode worker never prefills: ``install_request``
    hands it the first token, which its pipelined ticks take from the host
    once and from the device after."""
    from chainermn_tpu.parallel import (init_tp_transformer_lm,
                                        make_lm_generator)
    from chainermn_tpu.serving import build_disagg_fleet

    params = init_tp_transformer_lm(jax.random.PRNGKey(0), VOCAB, D, HEADS,
                                    LAYERS, max_len=64, pos_impl="rope")
    mesh = mn.make_nd_mesh(("model",), (1,), devices[:1])
    fleet = build_disagg_fleet(params, 1, 1, head_dim=D // HEADS,
                               max_total=24, n_slots=3, staging_slots=2,
                               mesh=mesh, queue_capacity=8)
    try:
        rng = np.random.RandomState(2)
        prompts = [rng.randint(0, VOCAB, n).astype(np.int32)
                   for n in (4, 6, 5, 3)]
        handles = [fleet.submit(p, 9) for p in prompts]
        fleet.run(steps_budget=400)
        gen = make_lm_generator(mesh, "model", head_dim=D // HEADS,
                                max_new_tokens=9)
        for h, p in zip(handles, prompts):
            assert h.status == "done"
            assert h.tokens == np.asarray(gen(params, p[None]))[0].tolist()
        dec = fleet.decode_workers[0].engine
        assert dec.engine.prefill_calls == 0
        assert dec.engine.tick_launches_overlapped > 0
        assert dec._in_flight is None
    finally:
        fleet.close()


# --------------------------------------------------------------------------
# (d) no driver leaves a launched tick unread
# --------------------------------------------------------------------------

@pytest.mark.parametrize("driver", ["run", "run_budget", "stop", "close"])
def test_no_launched_tick_is_left_unread(models, mesh, driver):
    model = models("plain")
    eng = _engine(model, mesh)
    rng = np.random.RandomState(3)
    prompts = [rng.randint(0, VOCAB, 5).astype(np.int32) for _ in range(4)]
    want = [_alone(eng, p, 10)[0] for p in prompts]
    handles = [eng.submit(p, 10) for p in prompts]
    if driver == "run":
        eng.run()
    elif driver == "run_budget":
        assert eng.run(steps_budget=4, drain=False) == 4
        assert eng._in_flight is None
        # what was in flight at the budget's end was read AND emitted: the
        # tokens so far are a prefix, and the run goes on from them
        assert all(h.tokens == w[:len(h.tokens)]
                   for h, w in zip(handles, want))
        eng.run()
    else:
        eng.start()
        for h in handles:
            assert h.wait(timeout=120)
        eng.stop() if driver == "stop" else eng.close()
    assert eng._in_flight is None
    assert eng.engine._uncollected == 0
    assert all(h.status == "done" and h.wait(0) for h in handles)
    assert [h.tokens for h in handles] == want
    m = eng.metrics()
    assert m["serving/tick_launches_overlapped"] <= m["serving/tick_calls"]
    if driver != "close":
        eng.close()


def test_stop_reads_back_a_tick_left_in_flight(models, mesh):
    eng = _engine(models("plain"), mesh)
    h = eng.submit(np.arange(5, dtype=np.int32), 10)
    for _ in range(3):
        eng.step()
    assert eng._in_flight is not None and len(h.tokens) == 3
    eng.stop()                           # no thread: still drains
    assert eng._in_flight is None and len(h.tokens) == 4
    _drive(eng)                          # goes on from what was read
    assert h.status == "done" and len(h.tokens) == 10
    eng.close()


# --------------------------------------------------------------------------
# (e) DecodeEngine.tick() is launch + collect
# --------------------------------------------------------------------------

@pytest.mark.parametrize("name", ["plain", "expert", "state"])
def test_tick_equals_launch_then_collect(models, mesh, name):
    model = models(name)
    eng = _engine(model, mesh, prefix_cache=False)
    de, pool = eng.engine, eng.pool
    prompt = np.random.RandomState(4).randint(0, model[3], 6).astype(np.int32)
    want, want_routes = _alone(eng, prompt, 8)
    slot = pool.acquire()
    toks = [de.prefill_into_slot(prompt, slot)]
    override = np.zeros(pool.n_slots, np.int32)
    override[slot] = toks[0]             # the host's once ...
    flying = de.launch_tick(override)
    override[slot] = -1                  # ... the device's after
    for i in range(6):
        nxt = de.launch_tick(override)   # launched before ``flying`` is read
        toks.append(int(de.collect_tick(flying)[slot]))
        if want_routes[0] is not None:   # the routes are the read tick's
            np.testing.assert_array_equal(de.tick_routes[slot],
                                          want_routes[i + 1])
        flying = nxt
    toks.append(int(de.collect_tick(flying)[slot]))
    assert toks == want
    assert de._uncollected == 0
    assert de.tick_launches_overlapped >= 6
    pool.release(slot)
    eng.close()


# --------------------------------------------------------------------------
# (g) the tick's attention is the busy slots' alone (ISSUE 34)
# --------------------------------------------------------------------------

@pytest.mark.parametrize("name", ["plain", "expert", "ring"])
def test_ticks_leave_the_slots_that_serve_nobody_alone(models, mesh, name):
    """A pool with a busy, a cached and a free slot.  The busy slot's
    tokens are the request's served alone; after all its ticks the cached
    slot's and the free slots' buffers — rows, rings, states — are bit for
    bit what they were (the tick writes a busy slot's row and no other:
    ISSUE 37); a prefix hit on the cached slot the ticks skipped serves
    what a cold engine serves; and the engine's counters are the host twin
    of the kernels' work list and of the writer's busy list, summed over
    the ticks."""
    from chainermn_tpu.ops.decode_attention import live_blocks

    model = models(name)
    vocab = model[3]
    rng = np.random.RandomState(34)
    first = rng.randint(0, vocab, 13).astype(np.int32)
    second = rng.randint(0, vocab, 9).astype(np.int32)
    tail = rng.randint(0, vocab, 3).astype(np.int32)

    def serve(eng, prompt, max_new):
        h = eng.submit(prompt, max_new)
        _drive(eng)
        assert h.status == "done"
        return h.tokens

    eng = _engine(model, mesh, max_total=64)
    pool = eng.pool
    want_second, _ = _alone(eng, second, 20)
    donated = np.concatenate([first, serve(eng, first, 11)])
    (cached,) = [s for s in range(pool.n_slots)
                 if pool.allocator.refcount(s) is not None]
    before = jax.tree_util.tree_map(np.asarray, pool.caches)
    held = pool.pos.copy()
    eng.reset_stats()
    calls0 = eng.engine.tick_calls

    # the second request's ticks, each launch's positions and mask noted
    launches, launch = [], eng.engine.launch_tick

    def noted(override, keys, temps, live):
        launches.append((pool.pos.copy(), np.array(live, bool)))
        return launch(override, keys, temps, live)

    eng.engine.launch_tick = noted
    h = eng.submit(second, 20)
    _drive(eng)
    blocks = sum(live_blocks(pos, pool.max_total, busy=live)[0]
                 for pos, live in launches)
    rows = sum(int(pos[live].sum()) + int(live.sum())
               for pos, live in launches)
    assert h.tokens == want_second
    ticks = eng.engine.tick_calls - calls0
    assert ticks == len(launches) >= 19
    (busy_slot,) = [s for s in range(pool.n_slots)
                    if pool.pos[s] != held[s]]
    assert busy_slot != cached and pool.pos[cached] == held[cached]

    m = eng.metrics()
    if not len(pool.ring_windows):              # rows alone: one layer's
        assert m["serving/tick_cache_blocks_read"] == blocks == ticks
        assert m["serving/tick_cache_rows_live"] == rows
    else:
        assert m["serving/tick_ring_rows_live"] == sum(
            int(np.minimum(pos[live][None] + 1,
                           pool.ring_windows[:, None]).sum())
            for pos, live in launches)
    assert m["serving/tick_row_bytes"] == rows * pool.bytes_per_token
    assert m["serving/tick_cache_blocks_total"] >= 3 * ticks * bool(blocks)
    n_bufs = sum(leaf.ndim == 3
                 for leaf in jax.tree_util.tree_leaves(before))
    assert n_bufs == pool.n_row_buffers > 0
    assert m["serving/tick_cache_rows_written"] == n_bufs * sum(
        int(live.sum()) for _, live in launches)
    assert m["serving/tick_cache_rows_offered"] == (
        n_bufs * pool.n_slots * ticks)

    after = jax.tree_util.tree_map(np.asarray, pool.caches)
    free = [s for s in range(pool.n_slots) if s not in (cached, busy_slot)]
    for was, now in zip(jax.tree_util.tree_leaves(before),
                        jax.tree_util.tree_leaves(after)):
        for s in [cached] + free:
            np.testing.assert_array_equal(now[s], was[s])
        assert not np.array_equal(now[busy_slot], was[busy_slot])

    # a prefix hit on the slot every one of those ticks skipped
    follow = np.concatenate([donated, tail])
    got = serve(eng, follow, 9)
    assert eng.metrics()["serving/prefix/hits"] >= 1
    eng.close()
    cold = _engine(model, mesh, max_total=64, prefix_cache=False)
    assert got == serve(cold, follow, 9)
    cold.close()
