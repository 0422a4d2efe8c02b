"""Laguna-style blocks at tiny sizes on the CPU: sliding-window GQA layers
that keep a RING of their window's rows BESIDE full-attention GQA layers that
keep every row, in one cache pool, with a head count that changes by layer,
rotary parameters by layer kind (partial, YaRN), a per-head sigmoid gate on
the context and no attention biases, a dense first layer and sigmoid-routed
experts after it — on the serving engine's normal path, against the plain
float32 reference (``tests/laguna_reference.py``, the same text as
``benchmark/reference/laguna.py``), which has no ring, no cache and no
kernel: a masked softmax with the band written out.

The banded flash forward and the GQA decode kernel run in interpret mode
here; the engine's tick takes the einsum path on the CPU."""

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
from chainermn_tpu.ops.decode_attention import decode_attend_gqa
from chainermn_tpu.ops.flash_attention import (causal_schedule,
                                               flash_attention)
from chainermn_tpu.parallel import blocks
from chainermn_tpu.parallel.blocks import LMArch, MoEConfig, Rotary
from chainermn_tpu.parallel.decode import lm_decode_tick, lm_prefill

HERE = os.path.dirname(os.path.abspath(__file__))


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load(os.path.join(HERE, "laguna_reference.py"), "laguna_reference")

W = 8
# F S S S F S S S: unequal head counts, a dense first layer, experts after
CFG = {
    "hidden_size": 64, "num_hidden_layers": 8, "head_dim": 16,
    "num_key_value_heads": 2, "num_attention_heads": 4,
    "num_attention_heads_per_layer": [4, 6, 6, 6, 4, 6, 6, 6],
    "layer_types": ["full_attention"] + ["sliding_attention"] * 3
    + ["full_attention"] + ["sliding_attention"] * 3,
    "mlp_layer_types": ["dense"] + ["sparse"] * 7,
    "sliding_window": W, "attention_bias": False, "gating": True,
    "rope_parameters": {
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 4,
            "original_max_position_embeddings": 16, "beta_slow": 1,
            "beta_fast": 4, "attention_factor": 1.1386294361119891,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1}},
    "intermediate_size": 96, "moe_intermediate_size": 32,
    "shared_expert_intermediate_size": 32, "num_experts": 16,
    "num_experts_held": 4, "num_experts_per_tok": 4,
    "moe_routed_scaling_factor": 2.5, "vocab_size": 97,
    "rms_norm_eps": 1e-6, "tie_word_embeddings": False,
    "assumed": {"init": {"query_gain": 4.0}},
}
HEAD_DIM = CFG["head_dim"]
KV_DIM = CFG["num_key_value_heads"] * HEAD_DIM
N_FULL, N_RING = 2, 6


def arch_of(cfg):
    """As ``benchmark/families/laguna.py::arch_of``."""
    def rotary(kind):
        rp = cfg["rope_parameters"][kind]
        yarn = rp["rope_type"] == "yarn"
        return Rotary(
            theta=float(rp["rope_theta"]),
            fraction=rp["partial_rotary_factor"],
            yarn=(rp["factor"], rp["original_max_position_embeddings"],
                  rp["beta_fast"], rp["beta_slow"]) if yarn else None,
            attention_factor=rp["attention_factor"] if yarn else 1.0)

    kinds = {k: rotary(k) for k in cfg["rope_parameters"]
             if isinstance(cfg["rope_parameters"][k], dict)}
    return LMArch(
        norm="rmsnorm", norm_eps=cfg["rms_norm_eps"], mlp="swiglu",
        attn="mha", tied_head=cfg["tie_word_embeddings"], embed_scale=False,
        layer_kinds=tuple("moe" if t == "sparse" else "dense"
                          for t in cfg["mlp_layer_types"]),
        windows=tuple(cfg["sliding_window"] if t == "sliding_attention"
                      else None for t in cfg["layer_types"]),
        rotary=tuple(kinds[t] for t in cfg["layer_types"]),
        attn_gate=bool(cfg["gating"]), attn_bias=cfg["attention_bias"],
        moe=MoEConfig(
            n_experts=cfg["num_experts"], top_k=cfg["num_experts_per_tok"],
            n_group=1, topk_group=1,
            routed_scaling_factor=cfg["moe_routed_scaling_factor"],
            norm_topk_prob=True,
            held=(0, cfg.get("num_experts_held", cfg["num_experts"]))))


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


def _prompt(seed, n):
    return np.random.default_rng(seed).integers(
        0, CFG["vocab_size"], n, dtype=np.int32)


# --------------------------------------------------------------------------
# the band in the flash forward's schedule
# --------------------------------------------------------------------------

def _masked_softmax(q, k, v, window):
    b, s, h, d = q.shape
    g = h // k.shape[2]
    k, v = jnp.repeat(k, g, 2), jnp.repeat(v, g, 2)
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, k) / d ** 0.5
    dist = jnp.arange(s)[:, None] - jnp.arange(s)[None, :]
    seen = (dist >= 0) & (dist < window)
    p = jax.nn.softmax(jnp.where(seen, sc, -jnp.inf), -1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


@pytest.mark.parametrize("s,window,block_q,block_k,heads", [
    (24, 32, None, None, (2, 2)),       # below the window: plain causal
    (32, 32, None, None, (2, 1)),       # at it
    (64, 16, None, None, (4, 2)),       # above it, one undivided cell
    (100, 32, None, None, (2, 1)),      # S padded to 128: band and tail
    (512, 128, 256, 256, (2, 1)),       # sub-blocks of 128: the band's edges
    (512, 200, 256, 512, (2, 2)),       # on and off a sub-block's edge
    (768, 256, 256, 256, (1, 1)),       # grid cells wholly left of the band
    (384, 130, 128, 128, (1, 1)),
], ids=lambda v: str(v))
def test_banded_flash_forward_is_the_masked_softmax(s, window, block_q,
                                                    block_k, heads):
    h, h_kv = heads
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(s + window), 3)
    q = jax.random.normal(kq, (1, s, h, 16))
    k = jax.random.normal(kk, (1, s, h_kv, 16))
    v = jax.random.normal(kv, (1, s, h_kv, 16))
    got = flash_attention(q, k, v, causal=True, window=window,
                          block_q=block_q, block_k=block_k)
    np.testing.assert_allclose(np.asarray(got), np.asarray(
        _masked_softmax(q, k, v, window)), rtol=2e-5, atol=2e-5)


def test_band_schedule_skips_what_lies_left_of_it():
    """The prefill's widest shape: a sliding layer computes 110 of the 576
    sub-block pairs where a causal layer computes 300, and without a
    window the schedule is what it was."""
    band = causal_schedule(3072, 1024, 1024, True, None, 512)
    full = causal_schedule(3072, 1024, 1024, True, None)
    assert (full["run"], full["masked"], full["total"]) == (300, 24, 576)
    assert (band["run"], band["masked"]) == (110, 44)
    assert all(len(entry) == 2 for plan in full["plans"] for entry in plan)
    assert all(len(entry) == 4 for plan in band["plans"] for entry in plan)
    # every row of Q sub-blocks: the diagonal, three whole, the left edge
    assert max(sum(e[1:]) for plan in band["plans"] for e in plan) == 5
    assert full == dict(causal_schedule(3072, 1024, 1024), window=None)
    with pytest.raises(ValueError, match="causal"):
        causal_schedule(256, 128, 128, False, None, 64)


def test_banded_flash_is_forward_only():
    """It was, until ISSUE 38 (the name stays so that the count does): the
    band now differentiates, against the masked softmax's own gradient;
    a window without ``causal`` is still refused."""
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 32, 2, 16))
    got = jax.grad(lambda q: (flash_attention(
        q, q, q, causal=True, window=8) ** 2).sum())(q)
    want = jax.grad(lambda q: (_masked_softmax(q, q, q, 8) ** 2).sum())(q)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)
    with pytest.raises(ValueError, match="window"):
        flash_attention(q, q, q, causal=False, window=8)


# --------------------------------------------------------------------------
# GQA through the one-position-per-slot decode face; a ring behind it
# --------------------------------------------------------------------------

def _gqa_einsum(q, kc, vc, pos, hq, hkv, hd):
    b, s, _ = kc.shape
    q5 = q.reshape(b, hkv, hq // hkv, hd)
    sc = jnp.einsum("bhgd,bkhd->bhgk", q5, kc.reshape(b, s, hkv, hd)) \
        / hd ** 0.5
    seen = jnp.arange(s)[None, None, None, :] <= pos[:, None, None, None]
    p = jax.nn.softmax(jnp.where(seen, sc, -jnp.inf), -1)
    return jnp.einsum("bhgk,bkhd->bhgd", p,
                      vc.reshape(b, s, hkv, hd)).reshape(b, hq * hd)


@pytest.mark.parametrize("group", [6, 8])
def test_gqa_decode_kernel_one_position_per_row(group):
    """6 and 8 query heads a KV head of 128 columns (the kernel pads a
    group to whole sublane tiles), each row at its own position: the first
    row, mid-block, and BEYOND the buffer — a ring that has wrapped, read
    whole."""
    b, s, hkv, hd = 3, 64, 2, 128
    hq = hkv * group
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(group), 3)
    q = jax.random.normal(kq, (b, hq * hd))
    kc = jax.random.normal(kk, (b, s, hkv * hd))
    vc = jax.random.normal(kv, (b, s, hkv * hd))
    pos = jnp.asarray([0, 21, s + 37], jnp.int32)
    got = decode_attend_gqa(q, kc, vc, pos, n_q_heads=hq, n_kv_heads=hkv,
                            head_dim=hd, block_s=16, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(
        _gqa_einsum(q, kc, vc, pos, hq, hkv, hd)), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("s_real", [3, W, W + 1, 2 * W + 5, 3 * W])
def test_ring_after_a_padded_prefill_is_the_ring_a_token_by_token_fill_leaves(
        s_real):
    """``ring_rows``: the ring a prompt padded to 32 rows leaves is the
    ring that writing rows ``0 .. s_real - 1`` one by one at ``p % W``
    leaves — never a padded row."""
    rows = jax.random.normal(jax.random.PRNGKey(s_real), (2, 32, 5))
    got = blocks.ring_rows(rows, jnp.asarray([s_real, 1]), W)
    want = np.zeros((W, 5), np.float32)
    for p in range(s_real):
        want[p % W] = np.asarray(rows[0, p])
    np.testing.assert_array_equal(np.asarray(got[0]), want)
    first = np.zeros((W, 5), np.float32)
    first[0] = np.asarray(rows[1, 0])
    np.testing.assert_array_equal(np.asarray(got[1]), first)


# --------------------------------------------------------------------------
# prefill, then decode through the ring, against the reference's forward
# --------------------------------------------------------------------------

def _decode_logits(params, mesh, tokens, s_real, s_pad, arch=ARCH):
    """Logits of positions ``s_real - 1 ..`` from a prefill of the first
    ``s_real`` tokens PADDED to ``s_pad`` and one tick a further token."""
    n, total = tokens.shape
    layout = blocks.cache_layout(arch, 8, KV_DIM, "model")

    def fn(p, tok):
        live = jnp.broadcast_to(jnp.arange(s_pad) < s_real, (n, s_pad))
        prompt = jnp.where(live, tok[:, :s_pad], 0)
        h, slabs = lm_prefill(p, prompt, s_pad, head_dim=HEAD_DIM,
                              axis_name="model", arch=arch, live=live)
        caches = [tuple(
            jnp.zeros(blocks.buffer_shape(buf, n, total), jnp.float32
                      ).at[:, :slab.shape[1]].set(slab)
            for buf, slab in zip(bufs, layer))
            for bufs, layer in zip(layout, slabs)]
        def tick(caches, t):
            h_last, caches = lm_decode_tick(
                p, jnp.take(tok, t, axis=1), caches,
                jnp.full((n,), t, jnp.int32), head_dim=HEAD_DIM,
                axis_name="model", arch=arch, live=jnp.ones((n,), bool))
            return caches, h_last @ p["head"].T

        _, ticks = jax.lax.scan(tick, caches, jnp.arange(s_real, total))
        return jnp.concatenate([(h[:, s_real - 1] @ p["head"].T)[:, None],
                                jnp.moveaxis(ticks, 0, 1)], 1)

    return _in_mesh(fn, mesh, 2)(params, tokens)


@pytest.mark.parametrize("s_real,s_pad", [(3, 8), (W, 8), (W + 3, 16),
                                          (2 * W + 1, 24)])
def test_prefill_then_ring_decode_is_the_reference_forward(params, mesh,
                                                           s_real, s_pad):
    """Contexts past 2 W, from prompts below, at and above the window,
    each padded to its bucket: the logits of the prefill's last real
    position and of every tick are the reference's full forward's."""
    total = 3 * W + 4
    tokens = jnp.asarray(np.stack([_prompt(s_real, total),
                                   _prompt(s_real + 50, total)]))
    got = _decode_logits(params, mesh, tokens, s_real, s_pad)
    with jax.default_matmul_precision("highest"):
        want, _ = ref.forward(params, CFG, jnp.pad(tokens, ((0, 0), (0, 4))))
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(want[:, s_real - 1: total]),
                               rtol=2e-3, atol=2e-3)


def test_a_window_that_sees_everything_is_another_model(params, mesh):
    """The check is not blind to the window: the reference with the band
    taken away differs from it past W positions, by far more than the
    program does."""
    tokens = jnp.asarray(_prompt(9, 32))[None]
    want, _ = ref.forward(params, CFG, tokens)
    wide, _ = ref.forward(params, dict(CFG, sliding_window=1 << 20), tokens)
    assert float(jnp.abs(want - wide)[:, :W].max()) < 1e-4
    assert float(jnp.abs(want - wide)[:, 2 * W:].max()) > 0.1


def test_served_requests_are_the_reference(params, mesh):
    """Through ``ServingEngine``: prompts below and above the window and
    past a bucket, answers that wrap the ring twice — every served token is
    the reference's first (gap ~ 0 in float32), and the served routes are
    the reference's."""
    eng = _engine(params, mesh)
    pool = eng.pool
    assert [tuple(c.shape for c in layer) for layer in pool.caches[:2]] == [
        ((4, 48, KV_DIM),) * 2, ((4, W, KV_DIM),) * 2]
    assert pool.bytes_per_token == N_FULL * 2 * KV_DIM * 4
    assert pool.ring_bytes_per_slot == N_RING * 2 * W * KV_DIM * 4
    assert pool.state_bytes_per_slot == 0
    prompts = [_prompt(1, 5), _prompt(2, 11), _prompt(3, 19)]
    handles = _serve(eng, prompts, 2 * W + 4)
    tokens = np.zeros((3, 49), np.int32)
    for i, (p, h) in enumerate(zip(prompts, handles)):
        tokens[i, :len(p)] = p
        tokens[i, len(p):len(p) + len(h.tokens)] = h.tokens
    with jax.default_matmul_precision("highest"):
        got = ref.served_gaps(
            params, CFG, tokens, [len(p) for p in prompts],
            [len(p) + len(h.tokens) for p, h in zip(prompts, handles)],
            program_routes=[np.asarray(h.routes) for h in handles], block=8)
    assert got["n"] == 3 * (2 * W + 4)
    assert got["gap_mean"] < 1e-4 and got["disagreement"] < 0.02
    m = eng.metrics()
    # the ticks' needed ring rows: min(pos + 1, W) a busy slot a ring layer
    assert m["serving/cache_ring_bytes_per_slot"] == pool.ring_bytes_per_slot
    assert 0 < m["serving/tick_ring_rows_live"] <= (
        m["serving/tick_calls"] * 3 * N_RING * W)
    assert m["serving/tick_ring_bytes"] \
        == m["serving/tick_ring_rows_live"] * 2 * KV_DIM * 4
    assert m["serving/tick_row_bytes"] > 0
    # sum over real query positions of the keys in their band, x 6 layers
    band = lambda s: sum(min(t + 1, W) for t in range(s))
    assert m["serving/prefill_band_pairs"] == N_RING * (
        band(5) + band(11) + band(19))
    assert m["serving/tick_cache_blocks_read"] \
        <= m["serving/tick_cache_blocks_total"]
    eng.close()


# --------------------------------------------------------------------------
# recycling: the ring's invariant
# --------------------------------------------------------------------------

def test_a_cached_slots_ring_survives_the_ticks_of_other_slots(params, mesh):
    """The tick runs every slot, and a cached slot's garbage write lands
    on ring row ``pos % W``: the position ``pos - W``, one outside the next
    query's window.  So a request that continues a donated sequence at its
    whole length — a prefix hit — is served token-identically with the same
    request served cold, however long other slots ticked in between."""
    first = _prompt(4, 13)
    eng = _engine(params, mesh)
    (a,) = _serve(eng, [first], W + 3)
    donated = np.concatenate([first, a.tokens])
    assert eng.pool.cached_count == 1
    _serve(eng, [_prompt(5, 9)], 3 * W)       # 3 W ticks over the cached slot
    follow = np.concatenate([donated, _prompt(6, 3)])
    (hit,) = _serve(eng, [follow], 2 * W)
    m = eng.metrics()
    assert m["serving/prefix/hits"] == 1
    assert m["serving/prefix/tokens_reused"] == len(donated) - 1 \
        or m["serving/prefix/tokens_reused"] == len(donated)
    eng.close()
    cold = _engine(params, mesh, prefix_cache=False)
    (want,) = _serve(cold, [follow], 2 * W)
    cold.close()
    assert hit.tokens == want.tokens


def test_a_recycled_slot_never_reads_its_predecessors_ring(params, mesh):
    """One slot, two occupants: the second, short one is served as in a
    fresh engine though the ring still holds the first one's rows."""
    eng = _engine(params, mesh, n_slots=1, prefix_cache=False)
    _serve(eng, [_prompt(7, 21)], 3 * W)
    assert float(jnp.abs(eng.pool.caches[1][0]).sum()) > 0
    (got,) = _serve(eng, [_prompt(8, 3)], 2 * W + 3)
    eng.close()
    fresh = _engine(params, mesh, n_slots=1, prefix_cache=False)
    (want,) = _serve(fresh, [_prompt(8, 3)], 2 * W + 3)
    fresh.close()
    assert got.tokens == want.tokens


# --------------------------------------------------------------------------
# prefix cache, spill and transfer on a layout with a ring
# --------------------------------------------------------------------------

def test_prefix_cache_on_a_ring_layout_serves_whole_entries_only(params,
                                                                 mesh):
    """A donated slot's ring holds the window before its donated length
    alone: a match shorter than that has lost rows and is a MISS
    (``window_misses``); a match at the donated length is a hit."""
    eng = _engine(params, mesh)
    assert eng.prefix_cache.whole_only
    base = _prompt(10, 14)
    (a,) = _serve(eng, [base], W + 2)
    # shares the first 10 tokens only: rows alone could have served them
    (b,) = _serve(eng, [np.concatenate([base[:10], _prompt(11, 6)])], 4)
    m = eng.metrics()
    assert (m["serving/prefix/hits"], m["serving/prefix/window_misses"],
            m["serving/prefix/state_misses"]) == (0, 1, 1)
    assert eng.engine.prefix_copies == 0
    with pytest.raises(ValueError, match="ring"):
        eng.engine.copy_prefix(0, 3, 5)
    eng.close()


@pytest.mark.parametrize("what", ["spill", "transfer"])
def test_spill_and_transfer_refuse_a_ring(params, mesh, what):
    """Both pack "rows [0, len)" of each buffer: a ring is no such rows,
    and dropping it in silence is the fault — refused at construction,
    naming the declaration."""
    from chainermn_tpu.serving.transfer import KvTransferPlane

    if what == "spill":
        with pytest.raises(ValueError, match=r"ring \(columns, spec, window"):
            _engine(params, mesh, spill_bytes=1 << 20)
        return
    eng = _engine(params, mesh, prefix_cache=False)
    with pytest.raises(ValueError, match="ring declaration"):
        KvTransferPlane().pack(eng.pool, 0, 4, meta={})
    eng.close()


# --------------------------------------------------------------------------
# the description's new vocabulary
# --------------------------------------------------------------------------

@pytest.mark.parametrize("kind", ["full_attention", "sliding_attention"])
def test_partial_rotary_and_yarn_against_the_direct_formula(kind):
    """``blocks.rotate``: the first half of a full layer's columns turned
    with YaRN's blended frequencies and its factor on cos and sin, all of a
    sliding layer's plainly — against the formula written out
    (``laguna_reference.rotary_tables``) and, for YaRN, by hand."""
    sliding = kind == "sliding_attention"
    cfg = ARCH.rotary[1 if sliding else 0]
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 40, 3, HEAD_DIM))
    pos = jnp.arange(40)
    cos, sin, rot = ref.rotary_tables(CFG, sliding, 40)
    assert rot == (16 if sliding else 8)
    got = blocks.rotate(cfg, x, pos)
    np.testing.assert_allclose(np.asarray(got), np.asarray(
        ref.rotate(x, cos, sin, rot)), rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(got[..., rot:]),
                                  np.asarray(x[..., rot:]))
    per_row = blocks.rotate(cfg, x, jnp.broadcast_to(pos, (2, 40)))
    np.testing.assert_allclose(np.asarray(per_row), np.asarray(got),
                               rtol=1e-6, atol=1e-6)
    if not sliding:
        # theta 500000 over 8 columns, original length 16, factor 4:
        # dimension 0 turns 16 / 2 pi > beta_fast times (kept), dimension
        # 1 is the ramp's end (divided by 4), 2 and 3 beyond it
        theta = 500000.0 ** -(np.arange(4) / 4.0)
        want = theta * np.array([1.0, 0.25, 0.25, 0.25])
        ang = 7 * want
        np.testing.assert_allclose(
            np.asarray(cos[7]), np.cos(ang) * 1.1386294361119891, rtol=1e-6)


def test_zero_gate_weights_halve_the_context(params, mesh):
    """``g = sigmoid(W_g u)``: at ``W_g = 0`` every head's context is
    halved, so the block's attention output is half the ungated one's."""
    blk = params["blocks"][1]
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 6, 64))
    zero = dict(blk, attn=dict(blk["attn"], wg=jnp.zeros_like(
        blk["attn"]["wg"])))
    ungated = dataclasses.replace(ARCH, attn_gate=False)

    def attn_out(arch, b):
        # the MLP's residual taken away: a dense layer (index 0) whose MLP
        # weights are zero adds nothing
        b = dict(b, mlp={k: jnp.zeros((64, 96) if k != "w_down" else
                                      (96, 64)) for k in
                         ("w_gate", "w_up", "w_down")})

        def fn(x, b):
            from chainermn_tpu.parallel.decode import _Core, _Work
            core = _Core(
                {"embed": jnp.zeros((8, 64)), "blocks": [b]}, HEAD_DIM,
                "model", dataclasses.replace(
                    arch, layer_kinds=("dense",), windows=(None,),
                    rotary=(arch.rotary[1],)))
            k = jnp.zeros((2, 6, KV_DIM))
            return blocks.layer_kind(core.arch, 0).serve(
                core, x, b, (k, k), 0, _Work(jnp.arange(6), 0))[0] - x
        return _in_mesh(fn, mesh, 2)(x, b)

    np.testing.assert_allclose(np.asarray(attn_out(ARCH, zero)),
                               0.5 * np.asarray(attn_out(ungated, blk)),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("path", ["prefill", "tick"])
def test_the_windowed_mha_entry_keeps_what_it_declares(
        params, mesh, kept_as_declared, path):
    """Layer 1, a sliding layer: a ``(k, v)`` pair of RINGS of ``W`` rows."""
    kept_as_declared(params, ARCH, HEAD_DIM, 1, path, mesh)


def test_the_description_declares_rows_and_rings(params):
    layout = blocks.cache_layout(ARCH, 8, KV_DIM, "model")
    spec = P(None, None, "model")
    assert layout[0] == ((KV_DIM, spec),) * 2
    assert layout[1] == ((KV_DIM, spec, W),) * 2
    assert [blocks.is_ring(bufs[0]) for bufs in layout] == [
        False, True, True, True] * 2
    assert not any(blocks.is_state(buf) for bufs in layout for buf in bufs)
    assert blocks.buffer_shape(layout[1][0], 3, 48) == (3, W, KV_DIM)
    assert blocks.buffer_shape(layout[0][0], 3, 48) == (3, 48, KV_DIM)
    assert ARCH.has_ring
    assert [ARCH.window(i) for i in range(3)] == [None, W, W]
    # no biases anywhere, a gate a head, the head count from the weights
    a = params["blocks"][1]["attn"]
    assert sorted(a) == ["wg", "wkv", "wo", "wq"]
    assert a["wq"].shape == (64, 6 * 16) and a["wg"].shape == (64, 6)
    assert params["blocks"][0]["attn"]["wq"].shape == (64, 4 * 16)
    # replicated blocks, vocab-sharded tables
    specs = blocks.lm_specs(ARCH, params, "model")
    assert specs["embed"] == specs["head"] == P("model", None)
    assert specs["blocks"][1]["attn"]["wg"] == P()


def test_the_training_block_refuses_what_it_cannot_run(params, mesh):
    """No silent full-attention substitute in the loss path."""
    from chainermn_tpu.parallel.transformer import tp_block

    x = jnp.zeros((1, 8, 64))
    with pytest.raises(NotImplementedError, match="window=8"):
        tp_block(x, params["blocks"][1], head_dim=HEAD_DIM,
                 axis_name="model", arch=ARCH, layer=1)


def test_a_chunk_behind_a_ring_is_refused(params, mesh):
    from chainermn_tpu.parallel.decode import _Core, _Work

    def fn(x, b):
        core = _Core(
            {"embed": jnp.zeros((8, 64)), "blocks": [b]}, HEAD_DIM, "model",
            ARCH)
        k = jnp.zeros((1, W, KV_DIM))
        return blocks.layer_kind(ARCH, 1).serve(
            core, x, b, (k, k), 1, _Work(jnp.arange(4, 7), 4))[0]

    with pytest.raises(NotImplementedError, match="ring of 8 rows"):
        _in_mesh(fn, mesh, 2)(jnp.zeros((1, 3, 64)), params["blocks"][1])
