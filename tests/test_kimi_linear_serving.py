"""Kimi-Linear-style blocks at tiny sizes on the CPU: gated delta-rule (KDA)
layers with a per-slot recurrent state BESIDE latent-attention layers with
a row a token, in one cache pool, on the serving engine's normal path —
against the plain float32 reference (``tests/kimi_linear_reference.py``,
the same text as ``benchmark/reference/kimi_linear.py``), whose KDA layer
is the token-by-token recurrence.

The ``kda_step`` and ``conv_step`` kernels run in interpret mode here; the
engine itself takes the kernels' plain twins on the CPU."""

import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import chainermn_tpu as mn
from chainermn_tpu._compat import shard_map
from chainermn_tpu.ops.conv_step import (SLOTS, busy_blocks, conv_step,
                                         conv_step_xla)
from chainermn_tpu.ops.kda_step import kda_step, kda_step_xla
from chainermn_tpu.parallel import blocks, kda
from chainermn_tpu.parallel.blocks import (KDAConfig, LMArch, MLAConfig,
                                           MoEConfig)

HERE = os.path.dirname(os.path.abspath(__file__))


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load(os.path.join(HERE, "kimi_linear_reference.py"), "kimi_reference")

# K K K M K K K M, a dense first layer, experts after it
CFG = {
    "hidden_size": 64, "num_hidden_layers": 8, "first_k_dense_replace": 1,
    "linear_attn_config": {
        "kda_layers": [1, 2, 3, 5, 6, 7], "full_attn_layers": [4, 8],
        "head_dim": 16, "num_heads": 4, "short_conv_kernel_size": 4},
    "kda_gate_rank": 8, "num_attention_heads": 4, "q_lora_rank": None,
    "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "mla_use_nope": True, "rope_theta": 10000,
    "intermediate_size": 96, "moe_intermediate_size": 32,
    "num_experts": 16, "num_experts_held": 4, "num_expert_group": 1,
    "topk_group": 1, "num_experts_per_token": 4, "moe_renormalize": True,
    "routed_scaling_factor": 2.446, "vocab_size": 97, "rms_norm_eps": 1e-5,
}
HEAD_DIM = CFG["v_head_dim"]
N_KDA, N_MLA = 6, 2
# what a slot keeps a KDA layer: S (4 x 16 x 16 float32) and 3 rows of
# [q|k|v] (3 x 192, float32 here)
STATE_BYTES = 4 * 16 * 16 * 4 + 3 * 192 * 4


def arch_of(cfg):
    lin = cfg["linear_attn_config"]
    n, dense = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    return LMArch(
        norm="rmsnorm", norm_eps=cfg["rms_norm_eps"], mlp="swiglu",
        attn="mla", tied_head=False, embed_scale=False,
        attn_kinds=tuple("kda" if i + 1 in lin["kda_layers"] else "mla"
                         for i in range(n)),
        layer_kinds=tuple("dense" if i < dense else "moe" for i in range(n)),
        kda=KDAConfig(lin["num_heads"], lin["head_dim"],
                      lin["short_conv_kernel_size"], cfg["kda_gate_rank"],
                      chunk=16),
        mla=MLAConfig(cfg["num_attention_heads"], cfg["q_lora_rank"],
                      cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
                      cfg["qk_rope_head_dim"], cfg["v_head_dim"],
                      rope=not cfg["mla_use_nope"]),
        moe=MoEConfig(cfg["num_experts"], cfg["num_experts_per_token"],
                      cfg["num_expert_group"], cfg["topk_group"],
                      cfg["routed_scaling_factor"], cfg["moe_renormalize"],
                      (0, cfg["num_experts_held"])))


ARCH = arch_of(CFG)


@pytest.fixture(scope="module")
def params():
    return ref.init_params(jax.random.PRNGKey(3), CFG, jnp.float32)


@pytest.fixture(scope="module")
def mesh(devices):
    return mn.make_nd_mesh(("model",), (1,), devices[:1])


def _engine(params, mesh, **kw):
    from chainermn_tpu.serving import ServingEngine

    kw = dict(dict(n_slots=4, max_total=48, prefill_bucket=8,
                   queue_capacity=8, spill_bytes=0), **kw)
    return ServingEngine(params, head_dim=HEAD_DIM, mesh=mesh, arch=ARCH,
                         **kw)


def _serve(eng, prompts, max_new):
    handles = [eng.submit(p, max_new) for p in prompts]
    while eng.scheduler.queue_depth or eng.pool.busy_count:
        eng.step()
    assert [h.status for h in handles] == ["done"] * len(prompts)
    return handles


def _in_mesh(fn, mesh, n_args):
    return jax.jit(shard_map(fn, mesh=mesh, in_specs=(P(),) * n_args,
                             out_specs=P()))


def _recurrence_inputs(seed, b, s, h=3, d=8, decay=3.0):
    """Unit keys, decays strong enough that a chunk's cumulative product
    underflows float32 (``exp(-64 * 3)``)."""
    rng = np.random.default_rng(seed)
    f = lambda *sh: jnp.asarray(rng.normal(size=sh), jnp.float32)
    k = f(b, s, h, d)
    return (f(b, s, h, d), k / jnp.linalg.norm(k, axis=-1, keepdims=True),
            f(b, s, h, d), -jnp.abs(f(b, s, h, d)) * decay,
            jax.nn.sigmoid(f(b, s, h)), f(b, h, d, d))


# --------------------------------------------------------------------------
# the two forms of the layer against the recurrence
# --------------------------------------------------------------------------

@pytest.mark.parametrize("s,chunk", [(5, 16), (16, 16), (17, 16), (33, 32),
                                     (64, 64), (130, 64)],
                         ids=lambda v: str(v))
def test_chunked_form_equals_the_recurrence(s, chunk):
    """Several lengths around the chunk's and the sub-chunk's edges, from
    a non-zero state: read-outs and final state are the recurrence's, and
    finite where a whole chunk's decay product underflows."""
    q, k, v, g, beta, s0 = _recurrence_inputs(s, 2, s)
    want_o, want_s = ref.kda_recurrence(q, k, v, g, beta, s0)
    got_o, got_s = jax.jit(kda.kda_chunked, static_argnames="chunk")(
        q, k, v, g, beta, s0, chunk=chunk)
    assert np.isfinite(np.asarray(got_o)).all()
    np.testing.assert_allclose(np.asarray(got_o), np.asarray(want_o),
                               rtol=1e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(got_s), np.asarray(want_s),
                               rtol=1e-4, atol=2e-5)


def test_chunked_form_with_a_frozen_and_a_dead_channel():
    """``g = 0`` (a state that never decays) and ``g = -80`` a token (one
    that forgets at once) in the same head: no overflow, the recurrence's
    numbers."""
    q, k, v, g, beta, s0 = _recurrence_inputs(9, 1, 40)
    g = g.at[..., 0].set(0.0).at[..., 1].set(-80.0)
    want_o, want_s = ref.kda_recurrence(q, k, v, g, beta, s0)
    got_o, got_s = kda.kda_chunked(q, k, v, g, beta, s0, chunk=32)
    np.testing.assert_allclose(np.asarray(got_o), np.asarray(want_o),
                               rtol=1e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(got_s), np.asarray(want_s),
                               rtol=1e-4, atol=2e-5)


@pytest.mark.parametrize("busy", [(1, 0, 1, 1, 0, 0), (0,) * 6, (1,) * 6,
                                  (0, 0, 0, 0, 0, 1)],
                         ids=["mixed", "none", "all", "last"])
def test_kda_step_is_one_step_of_the_recurrence(busy):
    """The kernel (interpret mode) and its plain twin: one token of the
    reference's recurrence for the busy slots; every other slot's state
    bit-identical and its read-out zero."""
    q, k, v, g, beta, s0 = _recurrence_inputs(4, 6, 1, h=8, d=16)
    busy = np.asarray(busy, bool)
    want_o, want_s = ref.kda_recurrence(q, k, v, g, beta, s0)
    args = (q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], s0,
            jnp.asarray(busy))
    for step in (kda_step_xla, lambda *a: kda_step(*a, interpret=True)):
        o, s1 = (np.asarray(x) for x in step(*args))
        np.testing.assert_allclose(o[busy], np.asarray(want_o)[busy, 0],
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(s1[busy], np.asarray(want_s)[busy],
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_array_equal(s1[~busy], np.asarray(s0)[~busy])
        assert (o[~busy] == 0).all()


def _busy_case(name, n):
    slot = np.arange(n)
    return {"none": slot < 0, "one": slot == n - 7, "alternating":
            slot % 2 == 0, "all": slot >= 0, "one_block":
            (slot >= SLOTS) & (slot < SLOTS + 3)}[name]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("busy", ["none", "one", "alternating", "all",
                                  "one_block"])
def test_conv_step_is_the_short_convolutions_tick(busy, dtype):
    """The kernel (interpret mode) and its plain twin against
    ``_short_conv`` at ``S == 1``, the idle slots' windows poisoned with
    NaN: a busy slot's ``y`` and window are ``_short_conv``'s, an idle
    slot's window comes back bit for bit and its ``y`` is exact 0."""
    n, width, c = 3 * SLOTS, 4, 256
    rng = np.random.default_rng(41)
    f = lambda *sh: jnp.asarray(rng.normal(size=sh), dtype)
    window, new, weight = f(n, width - 1, c), f(n, 1, c), f(width, c)
    busy = _busy_case(busy, n)
    poisoned = jnp.where(jnp.asarray(busy)[:, None, None], window, jnp.nan)
    want_y, want_w = jax.jit(kda._short_conv)(
        window, new, weight, jnp.asarray(busy, jnp.int32))
    as_bits = lambda x: np.asarray(jnp.asarray(x, jnp.float32)).view(
        np.uint32)
    for step in (jax.jit(conv_step_xla),
                 lambda *a: conv_step(*a, interpret=True)):
        y, got = step(poisoned, new, weight, jnp.asarray(busy))
        assert y.dtype == jnp.float32 and got.dtype == window.dtype
        # a compiled sum may contract a multiply-add; the order is the same
        np.testing.assert_allclose(np.asarray(y)[busy],
                                   np.asarray(want_y)[busy], rtol=2e-6,
                                   atol=2e-6)
        np.testing.assert_array_equal(as_bits(got)[busy],
                                      as_bits(want_w)[busy])
        np.testing.assert_array_equal(as_bits(got)[~busy],
                                      as_bits(poisoned)[~busy])
        assert (np.asarray(y)[~busy] == 0).all()


def test_busy_blocks_lists_the_blocks_that_hold_a_busy_slot():
    """In order, no sort; the entries past the count repeat the last, and
    a tick with nothing busy is one step on block 0."""
    n = 5 * SLOTS
    for held in ([], [3], [0, 2, 4], [1, 2], list(range(5))):
        busy = np.zeros(n, bool)
        busy[[b * SLOTS + 5 for b in held]] = True
        blocks = busy_blocks(jnp.asarray(busy), n)
        assert int(blocks.n[0]) == len(held)
        want = held + [held[-1] if held else 0] * (5 - len(held))
        assert np.asarray(blocks.slot).tolist() == want


def _layer(params, s_pad, s_real, seed=5):
    """One KDA layer of the model on a prompt padded to ``s_pad``."""
    blk = params["blocks"][1]["attn"]
    x = jax.random.normal(jax.random.PRNGKey(seed), (1, s_pad, 64))
    live = (jnp.arange(s_pad) < s_real)[None]
    state, window = (jnp.zeros((1,) + shape, jnp.float32)
                     for shape in ARCH.kda.state_shapes)
    return x, kda.kda_layer(ARCH.kda, x, blk, state, window, live, 1e-5)


@pytest.mark.parametrize("s_real", [1, 2, 3, 11, 16, 23])
def test_a_padded_prompts_state_is_the_state_at_its_last_real_token(
        params, s_real):
    """A prompt padded to its bucket hands the pool the state and the
    convolution window AT ``s_real`` (a padded row is harmless to a masked
    attention and wrong for a recurrence): both equal the unpadded
    prompt's, also where the prompt is shorter than the window."""
    x, (y, state, window) = _layer(params, 24, s_real)
    blk = params["blocks"][1]["attn"]
    zeros = [jnp.zeros((1,) + shape, jnp.float32)
             for shape in ARCH.kda.state_shapes]
    y0, state0, window0 = kda.kda_layer(ARCH.kda, x[:, :s_real], blk,
                                        *zeros, None, 1e-5)
    np.testing.assert_allclose(np.asarray(state), np.asarray(state0),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(window), np.asarray(window0),
                               rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(np.asarray(y[:, :s_real]), np.asarray(y0),
                               rtol=1e-4, atol=1e-5)
    # and it is not the state after the padding
    _, (_, padded, _) = _layer(params, 24, 24)
    if s_real < 24:
        assert np.abs(np.asarray(padded) - np.asarray(state)).max() > 1e-3


@pytest.mark.parametrize("s_real", [1, 2, 11, 24])
def test_the_tick_continues_the_window_a_padded_prefill_left(params, s_real):
    """A prompt padded to its bucket, then ONE tick: state, window and
    output are the unpadded prompt's with the token appended — the tick's
    window step starts from the window at ``s_real``, also where that
    window still reaches into the zeros before the prompt."""
    blk = params["blocks"][1]["attn"]
    x, (_, state, window) = _layer(params, 24, s_real)
    nxt = jax.random.normal(jax.random.PRNGKey(6), (1, 1, 64))
    y1, state1, window1 = kda.kda_layer(
        ARCH.kda, nxt, blk, state, window, jnp.ones((1, 1), bool), 1e-5)
    zeros = [jnp.zeros((1,) + shape, jnp.float32)
             for shape in ARCH.kda.state_shapes]
    y0, state0, window0 = kda.kda_layer(
        ARCH.kda, jnp.concatenate([x[:, :s_real], nxt], 1), blk, *zeros,
        None, 1e-5)
    np.testing.assert_allclose(np.asarray(window1), np.asarray(window0),
                               rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(np.asarray(state1), np.asarray(state0),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(y1[:, 0]), np.asarray(y0[:, -1]),
                               rtol=1e-4, atol=1e-5)
    # a row that carries no token keeps both, bit for bit
    _, state2, window2 = kda.kda_layer(
        ARCH.kda, nxt, blk, state, window, jnp.zeros((1, 1), bool), 1e-5)
    np.testing.assert_array_equal(np.asarray(window2), np.asarray(window))
    np.testing.assert_array_equal(np.asarray(state2), np.asarray(state))


def test_the_layer_is_the_references(params):
    """Projection, convolution, gates, recurrence, output norm and gate:
    the program's layer in its chunked form against the reference's."""
    blk = params["blocks"][0]["attn"]
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 21, 64))
    zeros = [jnp.zeros((2,) + shape, jnp.float32)
             for shape in ARCH.kda.state_shapes]
    got, _, _ = kda.kda_layer(ARCH.kda, x, blk, *zeros, None, 1e-5)
    want = ref.kda(x, blk, CFG, "float32")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4,
                               atol=2e-5)


# --------------------------------------------------------------------------
# the program against the reference
# --------------------------------------------------------------------------

def test_prefill_then_decode_logits_match_the_references_one_forward(
        params, mesh):
    """``lm_prefill`` runs the KDA layers in the chunked form and hands on
    state and window, ``lm_decode_tick`` moves them one token a call (and
    reads the MLA layers' latent rows in the absorbed form): the logits at
    every position equal the reference's single forward, whose KDA layers
    are the recurrence (float32 both sides)."""
    from chainermn_tpu.parallel.decode import lm_decode_tick, lm_prefill

    rng = np.random.default_rng(1)
    tokens = rng.integers(0, CFG["vocab_size"], (2, 30)).astype(np.int32)
    s_p, total = 19, 32
    want, _ = ref.forward(params, CFG, jnp.asarray(tokens))

    def program(p, tok):
        h, caches = lm_prefill(p, tok[:, :s_p], total, head_dim=HEAD_DIM,
                               axis_name="model", arch=ARCH)
        outs = [h @ p["head"].T]
        for t in range(s_p, tok.shape[1]):
            pos = jnp.full((tok.shape[0],), t, jnp.int32)
            h_last, caches = lm_decode_tick(
                p, tok[:, t], caches, pos, head_dim=HEAD_DIM,
                axis_name="model", arch=ARCH)
            outs.append((h_last @ p["head"].T)[:, None])
        return jnp.concatenate(outs, 1)

    got = _in_mesh(program, mesh, 2)(params, jnp.asarray(tokens))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=5e-4, atol=5e-4)


def _served_gaps(eng, params, prompts, handles, **kw):
    width = eng.pool.max_total + 1
    tokens = np.zeros((len(prompts), width), np.int32)
    for i, (p, h) in enumerate(zip(prompts, handles)):
        seq = np.concatenate([p, np.asarray(h.tokens, np.int32)])
        tokens[i, : len(seq)] = seq
    kw.setdefault("program_routes", [np.asarray(h.routes) for h in handles])
    return ref.served_gaps(
        params, CFG, tokens, [len(p) for p in prompts],
        [len(p) + len(h.tokens) for p, h in zip(prompts, handles)],
        rows_per_block=2, **kw)


def test_serving_engine_serves_the_references_tokens(params, mesh):
    """Through ``ServingEngine`` (scheduler, the two-kind pool, padded
    prefill programs, the tick with its busy mask; more requests than
    slots, so slots are recycled and donated): every served token is the
    reference's argmax on its prefix, the experts read back are the
    reference's, and the counters count what was touched."""
    eng = _engine(params, mesh)
    rng = np.random.default_rng(0)
    lens = (5, 11, 17, 9, 20, 3, 14)
    prompts = [rng.integers(0, CFG["vocab_size"], n, dtype=np.int32)
               for n in lens]
    handles = _serve(eng, prompts, 10)
    got = _served_gaps(eng, params, prompts, handles)
    assert got["gap_max"] < 2e-4 and got["n"] == 70
    assert got["disagreement"] == 0.0 and got["agree"] == 1.0
    m = eng.metrics()
    assert m["serving/cache_bytes_per_token"] == N_MLA * 128 * 4  # f32 here
    assert m["serving/cache_state_bytes_per_slot"] == N_KDA * STATE_BYTES
    # a tick touches the busy slots' state and no other slot's
    ticked = sum(len(h.tokens) - 1 for h in handles)
    assert m["serving/tick_state_slots_live"] == ticked * N_KDA
    assert m["serving/tick_state_bytes"] == ticked * N_KDA * STATE_BYTES
    assert m["serving/tick_latent_bytes"] == \
        m["serving/tick_cache_rows_live"] * N_MLA * 128 * 4
    assert m["serving/prefix/state_misses"] == 0
    eng.close()


def _eqns(jaxpr):
    """Every equation of ``jaxpr``, sub-jaxprs walked (a Pallas kernel's
    body is one operation)."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call":
            continue
        for value in eqn.params.values():
            for x in (value if isinstance(value, (tuple, list)) else (value,)):
                x = getattr(x, "jaxpr", x)
                if hasattr(x, "eqns"):
                    yield from _eqns(x)


def test_the_tick_builds_one_busy_list_for_all_its_layers(params, mesh,
                                                          monkeypatch):
    """The tick as the chip runs it (``jax.default_backend`` steered, a
    pool of whole blocks of slots): every delta-rule layer takes
    ``conv_step`` then ``kda_step``, and the jaxpr holds ONE sort of the
    slots for all of them and for the latent layers' row writer."""
    from chainermn_tpu.serving.engine import result_size

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    n = SLOTS
    eng = _engine(params, mesh, n_slots=n)
    dec = eng.engine
    tick = jax.make_jaxpr(dec._build_tick())(
        dec._params, eng.pool.read(lambda c: c),
        np.zeros(result_size(dec.arch, n), np.int32), np.zeros(n, np.int32),
        np.zeros(n, np.int32), np.zeros((n, 2), np.uint32),
        np.zeros(n, np.float32), np.ones(n, bool))
    eqns = list(_eqns(tick.jaxpr))
    kernels = [e.params["name"] for e in eqns
               if e.primitive.name == "pallas_call"]
    assert kernels.count("conv_step") == N_KDA
    assert kernels.count("kda_step") == N_KDA
    assert kernels.count("cache_write_rows") == N_MLA
    # interleaved a layer: the window first, then the state
    order = [k for k in kernels if k in ("conv_step", "kda_step")]
    assert order == ["conv_step", "kda_step"] * N_KDA
    slot_sorts = [e for e in eqns if e.primitive.name == "sort"
                  and e.invars[0].aval.shape == (n,)]
    assert len(slot_sorts) == 1
    eng.close()


def test_a_tick_moves_exactly_the_windows_the_engine_counts(params, mesh):
    """``serving/tick_state_slots_live`` counts (busy slot, state layer)
    pairs a tick; the windows (and states) a tick changes are exactly
    those pairs — every other slot's come back bit for bit."""
    eng = _engine(params, mesh, n_slots=8, max_total=64)
    rng = np.random.default_rng(2)
    for n_prompt in (5, 9, 3):
        eng.submit(rng.integers(0, CFG["vocab_size"], n_prompt,
                                dtype=np.int32), 30)
    while eng.scheduler.queue_depth or eng.engine.tick_calls < 3:
        eng.step()
    kda_layers = [i for i in range(CFG["num_hidden_layers"])
                  if ARCH.attn_kind(i) == "kda"]
    snap = lambda: [[np.asarray(b) for b in eng.pool.caches[i]]
                    for i in kda_layers]
    live = lambda: eng.metrics()["serving/tick_state_slots_live"]
    before, counted = snap(), live()
    eng.step()                                   # launches ONE tick
    after, counted = snap(), live() - counted
    moved = {(i, slot) for i, (was, now) in enumerate(zip(before, after))
             for slot in range(8) if (was[1][slot] != now[1][slot]).any()}
    assert counted == len(moved) == 3 * N_KDA
    assert {slot for _, slot in moved} == set(
        np.flatnonzero(eng.pool.busy_mask()).tolist())
    for i, (was, now) in enumerate(zip(before, after)):
        for slot in range(8):
            changed = [(w[slot] != x[slot]).any() for w, x in zip(was, now)]
            assert changed == [(i, slot) in moved] * 2
    eng.close()


# --------------------------------------------------------------------------
# the pool: two kinds of buffer, two invariants
# --------------------------------------------------------------------------

@pytest.mark.parametrize("path", ["prefill", "tick"])
def test_the_kda_entry_keeps_what_it_declares(
        params, mesh, kept_as_declared, path):
    """Layer 0, a delta-rule layer: ``(state, window)``, the state float32."""
    kept_as_declared(params, ARCH, HEAD_DIM, 0, path, mesh)


def test_cache_layout_declares_rows_or_state_per_layer():
    layout = blocks.cache_layout(ARCH, 8, 0, "model")
    kinds = ["state" if blocks.is_state(bufs[0]) else "rows"
             for bufs in layout]
    assert kinds == ["state"] * 3 + ["rows"] + ["state"] * 3 + ["rows"]
    assert layout[3] == ((128, P()),)
    assert layout[0] == (((4, 16, 16), jnp.float32, P()),
                         ((3, 192), None, P()))
    # the published widths: 32 x 128 x 128 float32 and 3 x 12288 bf16
    state, window = KDAConfig(32, 128).state_shapes
    assert int(np.prod(state)) * 4 == 2_097_152
    assert int(np.prod(window)) * 2 == 73_728
    # a state is no ring, and no layer of this model has a window (PR 33)
    assert not ARCH.has_ring and not any(
        blocks.is_ring(buf) for bufs in layout for buf in bufs)
    assert (ARCH.windows, ARCH.rotary, ARCH.attn_gate) == (None, None, False)


def test_pool_allocates_both_kinds_and_counts_both(params, mesh):
    eng = _engine(params, mesh)
    pool = eng.pool
    shapes = [tuple(buf.shape for buf in layer) for layer in pool.caches]
    assert pool.ring_bytes_per_slot == 0 and len(pool.ring_windows) == 0
    assert pool.n_row_layers == N_MLA
    assert shapes[3] == ((4, 48, 128),)
    assert shapes[0] == ((4, 4, 16, 16), (4, 3, 192))
    assert pool.caches[0][0].dtype == jnp.float32
    assert pool.bytes_per_token == N_MLA * 128 * 4
    assert pool.state_bytes_per_slot == N_KDA * STATE_BYTES
    assert pool.n_state_layers == N_KDA
    fresh = pool.fresh_buffers()
    assert [tuple(b.shape for b in l) for l in fresh] == shapes
    eng.close()


def _states(pool, slot):
    return [np.asarray(buf[slot]) for layer in pool.caches
            for buf in layer if buf.ndim != 3 or buf.shape[1] != 48]


def test_a_cached_slots_state_is_untouched_and_a_recycled_one_starts_anew(
        params, mesh):
    """The invariant of the state kind.  A finished request's slot is
    donated (cached): while OTHER slots tick, its state stays bit for bit
    the state of its donated length.  A slot that is recycled serves its
    next occupant the tokens that occupant gets alone in a fresh engine:
    it started from its own prefill's state, not its predecessor's."""
    rng = np.random.default_rng(21)
    draw = lambda n: rng.integers(0, CFG["vocab_size"], n, dtype=np.int32)
    first, others = draw(9), [draw(7), draw(12), draw(5)]
    eng = _engine(params, mesh, n_slots=2)
    _serve(eng, [first], 6)
    slot = next(iter(eng.prefix_cache.entries())).slot
    assert eng.pool.cached_count == 1
    before = _states(eng.pool, slot)
    assert all(np.abs(s).sum() > 0 for s in before)
    # the other slot serves three requests in turn (it is recycled twice,
    # and once the donated slot is scavenged too)
    got = [_serve(eng, [p], 8)[0].tokens for p in others[:1]]
    for a, b in zip(before, _states(eng.pool, slot)):
        np.testing.assert_array_equal(a, b)
    got += [_serve(eng, [p], 8)[0].tokens for p in others[1:]]
    eng.close()
    for p, tokens in zip(others, got):
        alone = _engine(params, mesh, prefix_cache=False)
        assert _serve(alone, [p], 8)[0].tokens == tokens
        alone.close()


def test_prefix_cache_on_a_state_layout(params, mesh):
    """A donated slot holds rows for every position but a state for ONE.
    A prompt that shares a shorter prefix with it is a miss (counted) and
    takes the whole prefill; a prompt that continues the WHOLE donated
    sequence copies rows and state and is served token for token as
    without the cache."""
    rng = np.random.default_rng(6)
    draw = lambda n: rng.integers(0, CFG["vocab_size"], n, dtype=np.int32)
    base = draw(12)
    plain = _engine(params, mesh, prefix_cache=False)
    first = _serve(plain, [base], 5)[0].tokens
    donated = np.concatenate([base, np.asarray(first[:-1], np.int32)])
    shorter = np.concatenate([base[:10], draw(4)])
    longer = np.concatenate([donated, draw(3)])
    want = [_serve(plain, [p], 6)[0].tokens for p in (shorter, longer)]
    plain.close()

    eng = _engine(params, mesh)
    assert _serve(eng, [base], 5)[0].tokens == first
    entry = next(iter(eng.prefix_cache.entries()))
    assert entry.length == len(donated) == eng.pool.pos[entry.slot]
    got_short = _serve(eng, [shorter], 6)[0].tokens
    assert eng.prefix_cache.hits == 0
    assert eng.metrics()["serving/prefix/state_misses"] == 1
    assert eng.engine.prefix_copies == 0
    got_long = _serve(eng, [longer], 6)[0].tokens
    assert eng.prefix_cache.hits == 1 and eng.engine.prefix_copies == 1
    assert [got_short, got_long] == want
    # the engine refuses a copy at any other length outright
    with pytest.raises(ValueError, match="no state to copy"):
        eng.engine.copy_prefix(entry.slot, 3, 4)
    eng.close()


def test_whole_only_prefix_cache_keeps_shorter_entries():
    """On a state layout a longer donation neither covers nor subsumes a
    shorter one: each serves only the prompts that continue ALL of it."""
    from chainermn_tpu.serving.prefix_cache import PrefixCache

    cache = PrefixCache(whole_only=True)
    a = cache.insert([1, 2, 3, 4], 0, 4)
    b = cache.insert([1, 2, 3, 4, 5, 6], 1, 6)
    assert a is not None and b is not None and cache.n_entries == 2
    assert cache.insert([1, 2, 3, 4], 2, 4) is None        # the same: dedup
    assert cache.match([1, 2, 3, 4, 5, 9])[0] is a          # whole of a
    assert cache.match([1, 2, 3, 4, 5, 6, 7]) == (b, 6)
    assert cache.match([1, 2, 3, 9]) == (None, 0)
    assert cache.state_misses == 1
    assert cache.peek_len([1, 2, 3, 4, 5, 6, 7]) == 6
    assert cache.peek_len([1, 2, 3, 9]) == 0
    cache.check_invariants()


def test_spill_and_transfer_refuse_a_state_layout(params, mesh):
    """Both pack "rows [0, len)" of each buffer: they refuse a pool that
    holds state, at construction and at the plane's every door, with an
    error that names the layer kind — they do not drop it in silence."""
    from chainermn_tpu.serving import ServingEngine
    from chainermn_tpu.serving.transfer import KvTransferPlane

    with pytest.raises(ValueError, match="'kda' layers"):
        ServingEngine(params, head_dim=HEAD_DIM, mesh=mesh, arch=ARCH,
                      n_slots=2, max_total=48, spill_bytes=1 << 20)
    eng = _engine(params, mesh)
    slot = eng.pool.acquire()
    eng.engine.prefill_into_slot(np.arange(9, dtype=np.int32), slot)
    plane = KvTransferPlane()
    with pytest.raises(ValueError, match="'kda' layers"):
        plane.pack(eng.pool, slot, 9, meta={})
    with pytest.raises(ValueError, match="'kda' layers"):
        plane.transfer_local(eng.pool, slot, eng.pool, 1, 9)
    with pytest.raises(ValueError, match="'kda' layers"):
        plane.inject_program(eng.pool)
    eng.close()


# --------------------------------------------------------------------------
# the description
# --------------------------------------------------------------------------

def test_training_loss_runs_the_chunked_form(params, mesh):
    """``tp_transformer_lm_loss`` with the model's description: the KDA
    layers run the chunked form from a zero state (plain XLA), and the
    mean NLL is the reference's."""
    from chainermn_tpu.parallel.transformer import tp_transformer_lm_loss

    rng = np.random.default_rng(12)
    tokens = jnp.asarray(rng.integers(0, CFG["vocab_size"], (2, 20)),
                         jnp.int32)
    loss = _in_mesh(lambda p, t: tp_transformer_lm_loss(
        p, (t,), head_dim=HEAD_DIM, axis_name="model", attn_impl="xla",
        ce_impl="xla", arch=ARCH), mesh, 2)(params, tokens)
    logits, _ = ref.forward(params, CFG, tokens[:, :-1])
    logp = jax.nn.log_softmax(logits, -1)
    want = -jnp.take_along_axis(logp, tokens[:, 1:, None], -1).mean()
    np.testing.assert_allclose(float(loss), float(want), rtol=1e-4)


def test_mla_without_query_compression_or_rotation_is_the_references(params):
    blk = params["blocks"][3]["attn"]
    assert "wq" in blk and "wdq" not in blk
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 12, 64))
    q_nope, q_pe, c_kv, k_pe = blocks.mla_project(
        ARCH.mla, x, blk, jnp.arange(12), 1e-5)
    ctx = blocks.mla_attend_prefill(ARCH.mla, q_nope, q_pe, c_kv, k_pe, blk,
                                    "xla")
    got = ctx @ blk["wo"]
    np.testing.assert_allclose(np.asarray(got), np.asarray(
        ref.mla(x, blk, CFG, "float32")), rtol=2e-4, atol=2e-5)
    # rotation off: the position does not enter
    again = blocks.mla_project(ARCH.mla, x, blk, jnp.arange(12) + 7, 1e-5)
    np.testing.assert_array_equal(np.asarray(again[1]), np.asarray(q_pe))
    rotated = blocks.mla_project(
        dataclasses.replace(ARCH.mla, rope=True), x, blk,
        jnp.arange(12) + 7, 1e-5)
    assert np.abs(np.asarray(rotated[1]) - np.asarray(q_pe)).max() > 1e-3


def test_the_lower_precision_control_is_told_apart(params):
    """The reference's fp8 forward picks tokens and routes that the
    float32 forward does not."""
    rng = np.random.default_rng(2)
    tokens = rng.integers(0, CFG["vocab_size"], (4, 33)).astype(np.int32)
    got = ref.served_gaps(
        params, CFG, tokens, [4] * 4, [32] * 4, precision="fp8",
        rows_per_block=4)
    assert got["disagreement"] > 0.05 and got["gap_max"] > 0.05
    assert got["gap_mean"] > 1e-3 and got["agree"] < 1.0


def test_the_decays_are_spread_as_the_published_initialiser_spreads_them(
        params):
    """Neither a dead nor a frozen state hides the mechanism: at a zero
    gate input the per-token decay ``exp(-A dt)`` lies in 0.2 .. 0.999."""
    a = params["blocks"][0]["attn"]
    alpha = np.exp(-np.exp(np.asarray(a["a_log"]))[:, None]
                   * np.log1p(np.exp(np.asarray(a["dt_bias"]))
                              ).reshape(4, 16))
    assert 0.19 < alpha.min() < 0.95 and 0.99 < alpha.max() < 1.0


def test_the_reference_under_tests_is_the_benchmarks_text():
    """The comparison that decides the cell's ``correct`` and these tests
    read ONE reference: the two files are the same text."""
    theirs = os.path.join(os.path.dirname(HERE), "benchmark", "reference",
                          "kimi_linear.py")
    with open(theirs) as a, open(os.path.join(
            HERE, "kimi_linear_reference.py")) as b:
        assert a.read() == b.read()
