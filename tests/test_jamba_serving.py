"""Jamba-style blocks at tiny sizes on the CPU: Mamba-1 selective state-space
layers with a per-slot state BESIDE multi-query attention layers with a row
a token, in one cache pool, on the serving engine's normal path, in a model
that declares NO positions — against the plain float32 reference
(``tests/jamba_reference.py``, the same text as
``benchmark/reference/jamba.py``), whose Mamba layer is the token-by-token
recurrence.

The ``selective_scan`` and ``ssm_step`` kernels run in interpret mode here;
the engine itself takes their plain twins on the CPU."""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import chainermn_tpu as mn
from chainermn_tpu._compat import shard_map
from chainermn_tpu.ops.selective_scan import (selective_scan,
                                              selective_scan_xla, walked)
from chainermn_tpu.ops.ssm_step import lanes, ssm_step, ssm_step_xla
from chainermn_tpu.parallel import blocks, mamba
from chainermn_tpu.parallel.blocks import LMArch, MambaConfig, Rotary

HERE = os.path.dirname(os.path.abspath(__file__))


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load(os.path.join(HERE, "jamba_reference.py"), "jamba_reference")

# M A M M A M: attention where l % 3 == 1; one KV head under 4 query heads
CFG = {
    "attn_layer_offset": 1, "attn_layer_period": 3, "hidden_size": 64,
    "intermediate_size": 96, "mamba_d_conv": 4, "mamba_d_state": 4,
    "mamba_dt_rank": 8, "mamba_expand": 2, "num_attention_heads": 4,
    "num_key_value_heads": 1, "num_hidden_layers": 6, "rms_norm_eps": 1e-6,
    "vocab_size": 97,
}
HEAD_DIM = CFG["hidden_size"] // CFG["num_attention_heads"]
N_MAMBA, N_ATTN = 4, 2
E, N = 128, 4
# what a slot keeps a Mamba layer: s (4 x 128 float32) and 3 rows of u
# (3 x 128, float32 here)
STATE_BYTES = N * E * 4 + 3 * E * 4


def arch_of(cfg):
    n = cfg["num_hidden_layers"]
    return LMArch(
        norm="rmsnorm", norm_eps=cfg["rms_norm_eps"], mlp="swiglu",
        attn="mha", tied_head=True, embed_scale=False, attn_bias=False,
        positions=False,
        attn_kinds=tuple("mha" if ref.is_attention(cfg, i) else "mamba"
                         for i in range(n)),
        mamba=MambaConfig(cfg["mamba_expand"] * cfg["hidden_size"],
                          cfg["mamba_d_state"], cfg["mamba_d_conv"],
                          cfg["mamba_dt_rank"]))


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


def _scan_inputs(seed, b, s, e=64, n=4):
    """``c, dt, B, C, a (N, E), d, start state`` of a scan: steps spread
    over two decades, rates 1 .. N as the published initialiser sets them."""
    rng = np.random.default_rng(seed)
    f = lambda *sh: jnp.asarray(rng.normal(size=sh), jnp.float32)
    lane = lanes(e)
    dt = jnp.exp(jnp.asarray(rng.uniform(np.log(1e-3), np.log(0.3),
                                         (b, s, e)), jnp.float32))
    a_log = jnp.broadcast_to(jnp.log(jnp.arange(1.0, n + 1))[:, None], (n, e))
    return (f(b, s, e), dt, f(b, s, n), f(b, s, n), a_log, 1.0 + 0.1 * f(e),
            f(b, n, e // lane, lane))


# --------------------------------------------------------------------------
# the two kernels against the recurrence
# --------------------------------------------------------------------------

@pytest.mark.parametrize("form", ["kernel", "twin"])
@pytest.mark.parametrize("s,n_real", [(8, (8, 5)), (24, (24, 1)),
                                      (256, (130, 256)), (256, (100, 3)),
                                      (384, (384, 257))],
                         ids=lambda v: str(v))
def test_selective_scan_is_the_recurrence(form, s, n_real):
    """Kernel (interpret mode) and twin against the reference's
    token-by-token recurrence, from a NON-ZERO start state, with prompts
    that end inside the bucket: rows at and after ``n_real`` carry ``dt =
    0``, so the state handed back is the state after the last real token
    (the recurrence run over the real tokens alone), and the kernel skips
    the chunks wholly beyond it."""
    c, dt, bm, cm, a_log, d, state = _scan_inputs(s, 2, s)
    n_real = jnp.asarray(n_real, jnp.int32)
    live = jnp.arange(s)[None, :] < n_real[:, None]
    dt = jnp.where(live[..., None], dt, 0.0)
    rate = -jnp.exp(a_log)
    if form == "kernel":
        y, new = selective_scan(c, dt, bm, cm, rate, d, state, n_real,
                                interpret=True)
    else:
        y, new = selective_scan_xla(c, dt, bm, cm, rate, d, state)
    flat = state.reshape(2, N, -1)
    for row in range(2):
        r = int(n_real[row])
        want_y, want_s = ref.mamba_recurrence(
            c[row:row + 1, :r], dt[row:row + 1, :r], bm[row:row + 1, :r],
            cm[row:row + 1, :r], a_log, d, flat[row:row + 1])
        np.testing.assert_allclose(np.asarray(y[row, :r]),
                                   np.asarray(want_y[0]), rtol=1e-5,
                                   atol=1e-5)
        np.testing.assert_allclose(np.asarray(new[row]).reshape(N, -1),
                                   np.asarray(want_s[0]), rtol=1e-5,
                                   atol=1e-5)


def test_walked_counts_whole_chunks_up_to_the_last_real_token():
    assert [walked(r, 256) for r in (1, 128, 129, 256)] == [128, 128, 256,
                                                            256]
    assert walked(40, 768) == 128 and walked(700, 768) == 768
    assert walked(3, 24) == 24          # a bucket that is one chunk


@pytest.mark.parametrize("busy", [(1, 0, 1, 1, 0, 0), (0,) * 6, (1,) * 6,
                                  (0, 0, 0, 0, 0, 1)],
                         ids=lambda v: "".join(map(str, v)))
def test_ssm_step_is_one_step_of_the_recurrence(busy):
    """Kernel (interpret mode) against its twin and the reference's
    recurrence over one token; every slot that is not busy keeps its state
    BIT FOR BIT and reads 0."""
    from chainermn_tpu.ops.kv_cache import busy_slots

    c, dt, bm, cm, a_log, d, state = _scan_inputs(11, 6, 1)
    busy = jnp.asarray(busy, bool)
    rate = -jnp.exp(a_log)
    args = (c[:, 0], dt[:, 0], bm[:, 0], cm[:, 0], rate, d, state, busy)
    y, new = ssm_step(*args, interpret=True)
    y_twin, new_twin = ssm_step_xla(*args)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_twin), rtol=1e-6,
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(new), np.asarray(new_twin),
                               rtol=1e-6, atol=1e-6)
    # given the tick's own list, the same result
    y2, new2 = ssm_step(*args, busy_slots(busy, 6), interpret=True)
    np.testing.assert_array_equal(np.asarray(new), np.asarray(new2))
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y2))
    want_y, want_s = ref.mamba_recurrence(c, dt, bm, cm, a_log, d,
                                          state.reshape(6, N, -1))
    idle = ~np.asarray(busy)
    for got_y, got_s in ((y, new), (y_twin, new_twin)):
        got_s = np.asarray(got_s)
        np.testing.assert_array_equal(got_s[idle], np.asarray(state)[idle])
        assert not np.asarray(got_y)[idle].any()
        np.testing.assert_allclose(
            got_s[~idle].reshape(-1, N, 64), np.asarray(want_s)[~idle],
            rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(
            np.asarray(got_y)[~idle], np.asarray(want_y)[~idle, 0],
            rtol=1e-5, atol=1e-5)


# --------------------------------------------------------------------------
# the layer in its two forms
# --------------------------------------------------------------------------

def _layer(params, s_pad, s_real, seed=5):
    blk = params["blocks"][0]["attn"]
    x = jax.random.normal(jax.random.PRNGKey(seed), (1, s_pad, 64))
    zeros = [jnp.zeros((1,) + shape, jnp.float32)
             for shape in ARCH.mamba.state_shapes]
    live = (jnp.arange(s_pad) < s_real)[None]
    return x, mamba.mamba_layer(ARCH.mamba, x, blk, *zeros, live, 1e-6)


@pytest.mark.parametrize("s_real", [1, 2, 3, 11, 16, 23])
def test_a_padded_prompts_state_is_the_state_at_its_last_real_token(
        params, s_real):
    """A prompt padded to its bucket hands the pool the state and the
    window of its REAL length: what the unpadded prompt leaves, whatever
    stands in the padded rows."""
    x, (_, state, window) = _layer(params, 24, s_real)
    blk = params["blocks"][0]["attn"]
    zeros = [jnp.zeros((1,) + shape, jnp.float32)
             for shape in ARCH.mamba.state_shapes]
    y, want_state, want_window = mamba.mamba_layer(
        ARCH.mamba, x[:, :s_real], blk, *zeros, None, 1e-6)
    np.testing.assert_allclose(np.asarray(state), np.asarray(want_state),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(window),
                                  np.asarray(want_window))
    # the window is the last three rows of u, zeros before position 0
    u = (x[:, :s_real] @ blk["w_in"])[..., :E]
    rows = jnp.concatenate([jnp.zeros((1, 3, E)), u], 1)[:, -3:]
    np.testing.assert_allclose(np.asarray(window), np.asarray(rows),
                               rtol=1e-5, atol=1e-6)


def test_the_layer_is_the_references(params):
    """Projections, convolution with its bias, the three inner norms, the
    step's softplus, the recurrence, ``D``, the gate and ``W_out``: the
    program's layer against the reference's."""
    blk = params["blocks"][0]["attn"]
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 21, 64))
    zeros = [jnp.zeros((2,) + shape, jnp.float32)
             for shape in ARCH.mamba.state_shapes]
    got, _, _ = mamba.mamba_layer(ARCH.mamba, x, blk, *zeros, None, 1e-6)
    with jax.default_matmul_precision("highest"):
        want = ref.mamba(x, blk, CFG, "float32")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4,
                               atol=2e-5)


# --------------------------------------------------------------------------
# the program against the reference
# --------------------------------------------------------------------------

def test_prefill_then_decode_logits_match_the_references_one_forward(
        params, mesh):
    """``lm_prefill`` runs the Mamba layers as one scan and hands on state
    and window, ``lm_decode_tick`` moves them one token a call (and reads
    the attention layers' rows, one KV head under four query heads, with no
    rotation): the LOGITS at every position equal the reference's single
    forward, whose Mamba layers are the recurrence (float32 both sides: the
    tolerance is float32 rounding through 6 layers and a different order of
    summation, nothing else)."""
    from chainermn_tpu.parallel.decode import lm_decode_tick, lm_prefill

    rng = np.random.default_rng(1)
    tokens = rng.integers(0, CFG["vocab_size"], (2, 30)).astype(np.int32)
    s_p, total = 19, 32
    with jax.default_matmul_precision("highest"):
        want = ref.forward(params, CFG, jnp.asarray(tokens))

    def program(p, tok):
        h, caches = lm_prefill(p, tok[:, :s_p], total, head_dim=HEAD_DIM,
                               axis_name="model", arch=ARCH)
        outs = [h @ p["embed"].T]
        for t in range(s_p, tok.shape[1]):
            pos = jnp.full((tok.shape[0],), t, jnp.int32)
            h_last, caches = lm_decode_tick(
                p, tok[:, t], caches, pos, head_dim=HEAD_DIM,
                axis_name="model", arch=ARCH)
            outs.append((h_last @ p["embed"].T)[:, None])
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
    with jax.default_matmul_precision("highest"):
        return ref.served_gaps(
            params, CFG, tokens, [len(p) for p in prompts],
            [len(p) + len(h.tokens) for p, h in zip(prompts, handles)],
            rows_per_block=2, **kw)


def test_serving_engine_serves_the_references_tokens(params, mesh):
    """Through ``ServingEngine`` (scheduler, the two-kind pool, padded
    prefill programs — every prompt here ends inside its bucket of 8 — the
    tick with its busy mask; more requests than slots, so slots are re-used
    by later requests): every served token's float32 logit lies within 2e-4
    of the reference's best on its prefix (float32 rounding: the program is
    float32 here) and is the reference's argmax, and the counters count
    what was touched."""
    eng = _engine(params, mesh)
    rng = np.random.default_rng(0)
    lens = (5, 11, 17, 9, 20, 3, 14)
    prompts = [rng.integers(0, CFG["vocab_size"], n, dtype=np.int32)
               for n in lens]
    handles = _serve(eng, prompts, 10)
    got = _served_gaps(eng, params, prompts, handles)
    assert got["gap_max"] < 2e-4 and got["n"] == 70
    assert got["agree"] == 1.0
    m = eng.metrics()
    assert m["serving/cache_bytes_per_token"] == N_ATTN * 2 * HEAD_DIM * 4
    assert m["serving/cache_state_bytes_per_slot"] == N_MAMBA * STATE_BYTES
    # a tick touches the busy slots' state and no other slot's: the
    # engine's state counters need no code for the new kind
    ticked = sum(len(h.tokens) - 1 for h in handles)
    assert m["serving/tick_state_slots_live"] == ticked * N_MAMBA
    assert m["serving/tick_state_bytes"] == ticked * N_MAMBA * STATE_BYTES
    # the scan's counters: (real token, scan layer) pairs, and the pairs
    # walked (a bucket of 8 is one chunk: the padded length)
    assert m["serving/prefill_scan_tokens"] == sum(lens) * N_MAMBA
    assert m["serving/prefill_scan_tokens_padded"] == N_MAMBA * sum(
        -(-n // 8) * 8 for n in lens)
    assert m["serving/prefix/state_misses"] == 0
    eng.close()


def test_a_model_without_scan_layers_counts_no_scan_tokens(devices):
    from chainermn_tpu.serving import ServingEngine

    mesh = mn.make_nd_mesh(("model",), (1,), devices[:1])
    p = mn.parallel.init_tp_transformer_lm(jax.random.PRNGKey(0), 32, 16, 4,
                                           2, max_len=64)
    eng = ServingEngine(p, head_dim=4, mesh=mesh, n_slots=2, max_total=32,
                        prefill_bucket=8, spill_bytes=0)
    h = eng.submit(np.arange(5, dtype=np.int32), 3)
    while eng.scheduler.queue_depth or eng.pool.busy_count:
        eng.step()
    assert h.status == "done"
    m = eng.metrics()
    assert m["serving/prefill_scan_tokens"] == 0
    assert m["serving/prefill_scan_tokens_padded"] == 0
    eng.close()


# --------------------------------------------------------------------------
# the pool: two kinds of buffer
# --------------------------------------------------------------------------

@pytest.mark.parametrize("path", ["prefill", "tick"])
def test_the_mamba_entry_keeps_what_it_declares(
        params, mesh, kept_as_declared, path):
    """Layer 0, a selective-scan layer: ``(state, window)``, the state float32."""
    kept_as_declared(params, ARCH, HEAD_DIM, 0, path, mesh)


def test_cache_layout_declares_rows_or_state_per_layer():
    layout = blocks.cache_layout(ARCH, 6, HEAD_DIM, "model")
    kinds = ["state" if blocks.is_state(bufs[0]) else "rows"
             for bufs in layout]
    assert kinds == ["state", "rows", "state", "state", "rows", "state"]
    pair = (HEAD_DIM, P(None, None, "model"))
    assert layout[1] == (pair, pair)
    assert layout[0] == (((4, 1, 128), jnp.float32, P()),
                         ((3, 128), None, P()))
    assert not ARCH.has_ring
    assert (ARCH.windows, ARCH.rotary) == (None, None)


def test_the_published_widths_keep_358400_bytes_a_slot_a_layer():
    """Shapes only, nothing allocated: 28 layers, attention at 7 and 21; a
    Mamba layer keeps ``(16, 40, 128)`` float32 — the ``(5120, 16)`` state
    with the channels on the lanes — and 3 rows of 5120 in bfloat16; an
    attention layer a ``(k, v)`` pair of 128 columns a token (1 KB a token
    for the model)."""
    cfg = MambaConfig(5120, 16, 4, 160)
    assert cfg.state_shapes == ((16, 40, 128), (3, 5120))
    arch = LMArch(attn="mha", positions=False, mamba=cfg, attn_kinds=tuple(
        "mha" if i % 14 == 7 else "mamba" for i in range(28)))
    layout = blocks.cache_layout(arch, 28, 128, "model")
    state_bytes = row_bytes = 0
    for bufs in layout:
        for buf in bufs:
            if blocks.is_state(buf):
                shape = blocks.buffer_shape(buf, 1, 2048)[1:]
                state_bytes += int(np.prod(shape)) * jnp.dtype(
                    buf[1] or jnp.bfloat16).itemsize
            else:
                assert blocks.buffer_shape(buf, 128, 2048) == (128, 2048,
                                                               128)
                row_bytes += buf[0] * 2
    assert state_bytes == 26 * 358_400
    assert row_bytes == 1024


def test_pool_allocates_both_kinds_and_counts_both(params, mesh):
    eng = _engine(params, mesh)
    pool = eng.pool
    shapes = [tuple(buf.shape for buf in layer) for layer in pool.caches]
    assert pool.ring_bytes_per_slot == 0 and len(pool.ring_windows) == 0
    assert pool.n_row_layers == N_ATTN
    assert shapes[1] == ((4, 48, HEAD_DIM),) * 2
    assert shapes[0] == ((4, 4, 1, 128), (4, 3, 128))
    assert pool.caches[0][0].dtype == jnp.float32
    assert pool.bytes_per_token == N_ATTN * 2 * HEAD_DIM * 4
    assert pool.state_bytes_per_slot == N_MAMBA * STATE_BYTES
    assert pool.n_state_layers == N_MAMBA
    fresh = pool.fresh_buffers()
    assert [tuple(b.shape for b in l) for l in fresh] == shapes
    eng.close()


def _states(pool, slot):
    return [np.asarray(buf[slot]) for layer in pool.caches
            for buf in layer if buf.ndim != 3 or buf.shape[1] != 48]


def test_a_cached_slots_state_is_untouched_and_a_reused_one_starts_anew(
        params, mesh):
    """A finished request's slot is donated (cached): while OTHER slots
    tick, its state stays bit for bit the state of its donated length.  A
    slot that a second request re-uses serves it the tokens it gets alone
    in a fresh engine: it started from its own prefill's state."""
    rng = np.random.default_rng(21)
    draw = lambda n: rng.integers(0, CFG["vocab_size"], n, dtype=np.int32)
    first, others = draw(9), [draw(7), draw(12), draw(5)]
    eng = _engine(params, mesh, n_slots=2)
    _serve(eng, [first], 6)
    slot = next(iter(eng.prefix_cache.entries())).slot
    before = _states(eng.pool, slot)
    assert len(before) == 2 * N_MAMBA
    assert all(np.abs(s).sum() > 0 for s in before)
    got = [_serve(eng, [p], 8)[0].tokens for p in others[:1]]
    for a, b in zip(before, _states(eng.pool, slot)):
        np.testing.assert_array_equal(a, b)
    got += [_serve(eng, [p], 8)[0].tokens for p in others[1:]]
    eng.close()
    for p, tokens in zip(others, got):
        alone = _engine(params, mesh, prefix_cache=False)
        assert _serve(alone, [p], 8)[0].tokens == tokens
        alone.close()


def test_prefix_hit_needs_the_donated_length_exactly(params, mesh):
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
    got_short = _serve(eng, [shorter], 6)[0].tokens
    assert eng.metrics()["serving/prefix/state_misses"] == 1
    got_long = _serve(eng, [longer], 6)[0].tokens
    assert eng.prefix_cache.hits == 1 and eng.engine.prefix_copies == 1
    assert [got_short, got_long] == want
    eng.close()


def test_spill_and_transfer_refuse_the_state(params, mesh):
    from chainermn_tpu.serving import ServingEngine
    from chainermn_tpu.serving.transfer import KvTransferPlane

    with pytest.raises(ValueError, match="'mamba' layers"):
        ServingEngine(params, head_dim=HEAD_DIM, mesh=mesh, arch=ARCH,
                      n_slots=2, max_total=48, spill_bytes=1 << 20)
    eng = _engine(params, mesh)
    slot = eng.pool.acquire()
    eng.engine.prefill_into_slot(np.arange(9, dtype=np.int32), slot)
    with pytest.raises(ValueError, match="'mamba' layers"):
        KvTransferPlane().pack(eng.pool, slot, 9, meta={})
    eng.close()


# --------------------------------------------------------------------------
# a model with no positions; one KV head under many query heads
# --------------------------------------------------------------------------

def _qk(seed=0, heads=(4, 1)):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(2, 5, heads[0], 16)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, 5, heads[1], 16)), jnp.float32)
    return q, k, jnp.arange(3, 8)


@pytest.mark.parametrize("rope", [True, False])
def test_turn_qk_leaves_a_model_without_positions_untouched(rope):
    q, k, pos = _qk()
    got_q, got_k = blocks.turn_qk(ARCH, 1, q, k, pos, rope)
    assert got_q is q and got_k is k


@pytest.mark.parametrize("case", ["default-rope", "default-table",
                                  "rotary-record"])
def test_every_other_models_rotation_is_what_it_was(case):
    """The new field changes no model that passes none: the default
    description still rotates with ``apply_rope`` where the parameters hold
    no position table and not at all where they do, and a layer's own
    ``Rotary`` record still wins."""
    from chainermn_tpu.parallel.transformer import apply_rope

    q, k, pos = _qk(1)
    assert blocks.DEFAULT_ARCH.positions
    if case == "default-rope":
        got = blocks.turn_qk(blocks.DEFAULT_ARCH, 0, q, k, pos, True)
        want = (apply_rope(q, pos), apply_rope(k, pos))
    elif case == "default-table":
        got = blocks.turn_qk(blocks.DEFAULT_ARCH, 0, q, k, pos, False)
        want = (q, k)
    else:
        turn = Rotary(theta=5e5, fraction=0.5)
        arch = LMArch(rotary=(None, turn))
        got = blocks.turn_qk(arch, 1, q, k, pos, True)
        want = (blocks.rotate(turn, q, pos), blocks.rotate(turn, k, pos))
        assert not np.array_equal(np.asarray(got[0]), np.asarray(q))
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def _mqa_einsum(q, k, v, valid):
    """``q (B, Sq, H, d)`` on ONE KV head ``k, v (B, S, d)``; query ``i``
    of row ``b`` sees keys ``[0, valid[b, i])``."""
    s = jnp.einsum("bqhd,bkd->bhqk", q, k) / q.shape[-1] ** 0.5
    mask = jnp.arange(k.shape[1])[None, None, None, :] \
        < valid[:, None, :, None]
    p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), -1)
    return jnp.einsum("bhqk,bkd->bqhd", p, v)


@pytest.mark.parametrize("face", ["decode", "prefill"])
def test_twenty_query_heads_on_one_kv_head_at_head_128(face):
    """The published head geometry (group 20, one KV head, head 128) through
    the tick's ``decode_attend_gqa`` — the group padded to 24 sublane rows —
    and through the flash prefill, against an einsum."""
    from chainermn_tpu.ops.decode_attention import decode_attend_gqa
    from chainermn_tpu.ops.flash_attention import flash_attention

    rng = np.random.default_rng(4)
    f = lambda *sh: jnp.asarray(rng.normal(size=sh), jnp.float32)
    if face == "decode":
        b, s = 3, 64
        q, kc, vc = f(b, 20 * 128), f(b, s, 128), f(b, s, 128)
        pos = jnp.asarray([5, 63, 17], jnp.int32)
        busy = jnp.asarray([True, True, False])
        got = decode_attend_gqa(q, kc, vc, pos, busy, n_q_heads=20,
                                n_kv_heads=1, head_dim=128, interpret=True)
        want = _mqa_einsum(q.reshape(b, 1, 20, 128), kc, vc,
                           (pos + 1)[:, None]).reshape(b, -1)
        np.testing.assert_allclose(np.asarray(got[:2]), np.asarray(want[:2]),
                                   rtol=2e-5, atol=2e-5)
        assert not np.asarray(got[2]).any()       # an idle row reads 0
    else:
        b, s = 1, 256
        q, k, v = f(b, s, 20, 128), f(b, s, 1, 128), f(b, s, 1, 128)
        got = flash_attention(q, k, v, causal=True)
        valid = jnp.broadcast_to(jnp.arange(1, s + 1)[None], (b, s))
        want = _mqa_einsum(q, k[:, :, 0], v[:, :, 0], valid)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)


# --------------------------------------------------------------------------
# the reference
# --------------------------------------------------------------------------

def test_the_lower_precision_control_is_told_apart(params, mesh):
    """The control — fp8 matmul operands and a bfloat16 state — reads a
    mean logit gap and a disagreement far above the program's own, which
    read 0 here (float32 program)."""
    eng = _engine(params, mesh)
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, CFG["vocab_size"], n, dtype=np.int32)
               for n in (12, 7, 19)]
    handles = _serve(eng, prompts, 12)
    sound = _served_gaps(eng, params, prompts, handles)
    control = _served_gaps(eng, params, prompts, handles, precision="fp8")
    eng.close()
    assert sound["agree"] == 1.0 and sound["gap_mean"] < 1e-5
    assert control["agree"] < 0.9 and control["gap_mean"] > 1e-2


def test_the_state_alone_in_bfloat16_moves_the_recurrence():
    """The control's state rounding is seen by itself: over 200 tokens a
    bfloat16 state drifts from the float32 one by far more than float32
    rounding."""
    c, dt, bm, cm, a_log, d, _ = _scan_inputs(9, 1, 200)
    y, s = ref.mamba_recurrence(c, dt, bm, cm, a_log, d)
    y_low, s_low = ref.mamba_recurrence(c, dt, bm, cm, a_log, d,
                                        low_state=True)
    drift = float(jnp.abs(s - s_low).max() / jnp.abs(s).max())
    assert 1e-4 < drift < 1e-1


def test_the_seeded_steps_and_rates_are_the_published_initialisers(params):
    """``A_log = log(1 .. N)`` a channel and ``dt_bias`` the inverse
    softplus of steps spread over 1e-3 .. 1e-1: at a zero step input the
    per-token decay ``exp(-A dt)`` lies between the fast channel's last
    state and the slow channel's first."""
    a = params["blocks"][0]["attn"]
    np.testing.assert_allclose(np.asarray(jnp.exp(a["a_log"][:, 0])),
                               np.arange(1, N + 1), rtol=1e-6)
    dt = np.asarray(jax.nn.softplus(a["dt_bias"]))
    assert 1e-3 <= dt.min() < 3e-3 and 3e-2 < dt.max() <= 1e-1
    assert a["a_log"].shape == (N, E)


def test_the_reference_under_tests_is_the_benchmarks_text():
    with open(os.path.join(HERE, "jamba_reference.py")) as f:
        mine = f.read()
    with open(os.path.join(os.path.dirname(HERE), "benchmark", "reference",
                           "jamba.py")) as f:
        assert mine == f.read()
