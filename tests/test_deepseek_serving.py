"""DeepSeek-V3-style blocks at tiny sizes on the CPU: latent attention with
a latent cache, dropless sigmoid-routed experts with a shared expert, on
the serving engine's normal path — against the plain float32 reference
(``tests/deepseek_v3_reference.py``, the same text as
``benchmark/reference/deepseek_v3.py``) and against the equations.

The kernels (``decode_attn_mla``, ``moe_gmm``) run in interpret mode here;
the engine itself takes its einsum / dense-loop paths on the CPU."""

import importlib.util
import os
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import chainermn_tpu as mn
from chainermn_tpu._compat import shard_map
from chainermn_tpu.parallel import blocks
from chainermn_tpu.parallel.blocks import LMArch, MLAConfig, MoEConfig
from chainermn_tpu.parallel.moe import (COUNT_FIELDS, moe_dropless,
                                        sigmoid_group_route)

HERE = os.path.dirname(os.path.abspath(__file__))


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load(os.path.join(HERE, "deepseek_v3_reference.py"), "ds_reference")

CFG = {
    "hidden_size": 64, "num_hidden_layers": 3, "first_k_dense_replace": 1,
    "num_attention_heads": 4, "q_lora_rank": 24, "kv_lora_rank": 32,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "intermediate_size": 96, "moe_intermediate_size": 32,
    "n_routed_experts": 16, "n_routed_experts_held": 4, "n_group": 4,
    "topk_group": 2, "num_experts_per_tok": 4, "norm_topk_prob": True,
    "routed_scaling_factor": 2.5, "vocab_size": 97, "rms_norm_eps": 1e-6,
    "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 16, "type": "yarn"},
}
HEAD_DIM = CFG["v_head_dim"]


def arch_of(cfg, held=None):
    rs = cfg["rope_scaling"]
    n, dense = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    return LMArch(
        norm="rmsnorm", norm_eps=cfg["rms_norm_eps"], mlp="swiglu",
        attn="mla", tied_head=False, embed_scale=False,
        layer_kinds=tuple("dense" if i < dense else "moe" for i in range(n)),
        mla=MLAConfig(cfg["num_attention_heads"], cfg["q_lora_rank"],
                      cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
                      cfg["qk_rope_head_dim"], cfg["v_head_dim"],
                      float(cfg["rope_theta"]),
                      (rs["factor"], rs["original_max_position_embeddings"],
                       rs["beta_fast"], rs["beta_slow"], rs["mscale"],
                       rs["mscale_all_dim"])),
        moe=MoEConfig(cfg["n_routed_experts"], cfg["num_experts_per_tok"],
                      cfg["n_group"], cfg["topk_group"],
                      cfg["routed_scaling_factor"], cfg["norm_topk_prob"],
                      held or (0, cfg["n_routed_experts_held"])))


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
    return ServingEngine(params, head_dim=HEAD_DIM, mesh=mesh,
                         arch=arch_of(CFG), **kw)


def _serve(eng, prompts, max_new):
    handles = [eng.submit(p, max_new) for p in prompts]
    while eng.scheduler.queue_depth or eng.pool.busy_count:
        eng.step()
    assert [h.status for h in handles] == ["done"] * len(prompts)
    return handles


def _in_mesh(fn, mesh, n_args):
    return jax.jit(shard_map(fn, mesh=mesh, in_specs=(P(),) * n_args,
                             out_specs=P()))


# --------------------------------------------------------------------------
# the program against the reference
# --------------------------------------------------------------------------

def test_prefill_then_decode_logits_match_the_references_one_forward(
        params, mesh):
    """``lm_prefill`` writes the latent cache, ``lm_decode_tick`` reads it
    in the absorbed form: the logits at every position equal the
    reference's single prefill-form forward (float32 both sides)."""
    from chainermn_tpu.parallel.decode import lm_decode_tick, lm_prefill

    arch = arch_of(CFG)
    rng = np.random.default_rng(1)
    tokens = rng.integers(0, CFG["vocab_size"], (2, 14)).astype(np.int32)
    s_p, total = 9, 16
    want, _ = ref.forward(params, CFG, jnp.asarray(tokens))

    def program(p, tok):
        h, caches = lm_prefill(p, tok[:, :s_p], total, head_dim=HEAD_DIM,
                               axis_name="model", arch=arch)
        outs = [h @ p["head"].T]
        for t in range(s_p, tok.shape[1]):
            pos = jnp.full((tok.shape[0],), t, jnp.int32)
            h_last, caches = lm_decode_tick(
                p, tok[:, t], caches, pos, head_dim=HEAD_DIM,
                axis_name="model", arch=arch)
            outs.append((h_last @ p["head"].T)[:, None])
        return jnp.concatenate(outs, 1)

    got = _in_mesh(program, mesh, 2)(params, jnp.asarray(tokens))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


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
    """Through ``ServingEngine`` (scheduler, latent pool, prefill programs,
    the tick): every served token is the reference's argmax on its prefix,
    and the experts the prefill and the ticks READ BACK beside each token
    are the reference's."""
    eng = _engine(params, mesh)
    rng = np.random.default_rng(0)
    lens = (5, 11, 17, 9, 20)
    prompts = [rng.integers(0, CFG["vocab_size"], n, dtype=np.int32)
               for n in lens]
    handles = _serve(eng, prompts, 10)
    for h in handles:           # one (expert layers, top_k) set a token
        assert np.asarray(h.routes).shape == (10, 2, 4)
        assert np.asarray(h.routes).max() < CFG["n_routed_experts"]
    got = _served_gaps(eng, params, prompts, handles)
    assert got["gap_max"] < 1e-4 and got["n"] == 50
    assert got["disagreement"] == 0.0 and got["agree"] == 1.0
    m = eng.metrics()
    assert m["serving/cache_bytes_per_token"] == 3 * 128 * 4   # f32 here
    # every served token is counted once a layer, and nothing else: the
    # prompts' real positions (not their padding to the bucket) and the
    # ticks' busy slots (not the free ones)
    fed = sum(lens) + sum(len(h.tokens) - 1 for h in handles)
    assert m["serving/moe_assignments_total"] == fed * 2 * 4
    assert 0 < m["serving/moe_assignments_held"] \
        < m["serving/moe_assignments_total"]
    loads = [m[f"serving/moe_expert_tokens/{i}"] for i in range(4)]
    assert sum(loads) == m["serving/moe_assignments_held"]
    assert m["serving/moe_experts_hit"] >= m["serving/moe_tick_experts_hit"]
    eng.close()


def test_a_tick_that_routes_wrongly_is_seen(params, mesh, monkeypatch):
    """The routes compared are the serving programs' own: a tick whose
    router is off (a bias on one expert, in the tick alone) still serves
    tokens, and the comparison finds its routing against the reference's.
    """
    from chainermn_tpu.parallel import decode

    tick = decode.lm_decode_tick

    def off(p, *a, **kw):
        blks = [dict(b, moe=dict(b["moe"], router_bias=b["moe"][
            "router_bias"].at[5].add(9.0))) if "moe" in b else b
            for b in p["blocks"]]
        return tick(dict(p, blocks=blks), *a, **kw)

    monkeypatch.setattr(decode, "lm_decode_tick", off)
    eng = _engine(params, mesh, prefix_cache=False)
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, CFG["vocab_size"], n, dtype=np.int32)
               for n in (6, 13)]
    handles = _serve(eng, prompts, 8)
    got = _served_gaps(eng, params, prompts, handles)
    # the prefill's position routes as the reference does; the 7 ticked
    # positions of each request chose expert 5 where the reference did not
    assert 0.5 < got["disagreement"] <= 14 / 16
    # and a program that reports no routes agrees nowhere
    none = _served_gaps(eng, params, prompts, handles, program_routes=[[], []])
    assert none["disagreement"] == 1.0
    eng.close()


def test_free_slots_and_padding_go_to_no_expert():
    """``live`` rows alone are routed: the others read no expert, are in
    no count, and their routed part is zero (the shared expert stays)."""
    m, x = _full_layer(seed=4)
    cfg = MoEConfig(**dict(arch_of(CFG).moe.__dict__, held=(0, 16)))
    live = jnp.arange(x.shape[0]) % 3 != 0
    want, c_all, idx_all = moe_dropless(x, m, cfg)
    for interpret in (None, True):
        y, c, idx = moe_dropless(x, m, cfg, live=live, interpret=interpret)
        n = int(live.sum())
        assert int(c[0]) == n * 4 == int(c[1]) and int(c[3:].sum()) == n * 4
        assert (np.asarray(idx)[~np.asarray(live)] == 16).all()
        np.testing.assert_array_equal(np.asarray(idx)[np.asarray(live)],
                                      np.asarray(idx_all)[np.asarray(live)])
        np.testing.assert_allclose(
            np.asarray(y), np.where(
                np.asarray(live)[:, None], np.asarray(want),
                np.asarray(blocks.swiglu(x, m["shared"]))),
            rtol=1e-5, atol=1e-6)
    # rows that all choose alike (free slots at row 0) hit experts that no
    # token needs: dead, they hit none
    same = jnp.tile(x[:1], (8, 1))
    assert int(moe_dropless(same, m, cfg)[1][2]) == 4
    assert int(moe_dropless(same, m, cfg,
                            live=jnp.zeros(8, bool))[1][2]) == 0


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


def test_training_loss_through_the_shared_description(params, mesh):
    """``tp_transformer_lm_loss`` with the model's description is the
    reference's mean NLL: the training path reads the same block
    vocabulary as serving."""
    from chainermn_tpu.parallel.transformer import tp_transformer_lm_loss

    rng = np.random.default_rng(4)
    tokens = rng.integers(0, CFG["vocab_size"], (2, 13)).astype(np.int32)
    loss = _in_mesh(lambda p, t: tp_transformer_lm_loss(
        p, (t,), head_dim=HEAD_DIM, axis_name="model", attn_impl="xla",
        ce_impl="xla", arch=arch_of(CFG)), mesh, 2)(
        params, jnp.asarray(tokens))
    logits, _ = ref.forward(params, CFG, jnp.asarray(tokens[:, :-1]))
    logp = jax.nn.log_softmax(logits, -1)
    want = -np.take_along_axis(np.asarray(logp), tokens[:, 1:, None],
                               -1).mean()
    assert float(loss) == pytest.approx(float(want), rel=1e-4)


# --------------------------------------------------------------------------
# latent attention
# --------------------------------------------------------------------------

def _mla_inputs(seed=0, b=2, s=12):
    cfg = arch_of(CFG).mla
    p = ref.init_params(jax.random.PRNGKey(seed), CFG)["blocks"][0]["attn"]
    h = jax.random.normal(jax.random.PRNGKey(seed + 1),
                          (b, s, CFG["hidden_size"]))
    return cfg, p, h


def test_mla_absorbed_form_equals_prefill_form():
    cfg, a, h = _mla_inputs()
    b, s, _ = h.shape
    parts = blocks.mla_project(cfg, h, a, jnp.arange(s), 1e-6)
    want = blocks.mla_attend_prefill(cfg, *parts, a, "xla")
    cache = blocks.mla_latent_rows(cfg, parts[2], parts[3])
    assert cache.shape == (b, s, cfg.latent_width) == (b, s, 128)
    valid = jnp.broadcast_to(jnp.arange(s)[None] + 1, (b, s))
    got = blocks.mla_attend_absorbed(cfg, parts[0], parts[1], cache, valid,
                                     a, use_kernel=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_mla_prefill_form_equals_the_reference():
    cfg, a, h = _mla_inputs(seed=5)
    s = h.shape[1]
    parts = blocks.mla_project(cfg, h, a, jnp.arange(s), 1e-6)
    ctx = blocks.mla_attend_prefill(cfg, *parts, a, "xla")
    got = ctx @ a["wo"]
    want = ref.mla(h, a, CFG, "float32")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


def test_mla_flash_prefill_pads_v_and_carries_the_models_scale():
    cfg, a, h = _mla_inputs(seed=7, b=1, s=16)
    parts = blocks.mla_project(cfg, h, a, jnp.arange(16), 1e-6)
    want = blocks.mla_attend_prefill(cfg, *parts, a, "xla")
    got = blocks.mla_attend_prefill(cfg, *parts, a, "flash")   # interpret
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


def test_yarn_frequencies_are_the_references():
    cfg = arch_of(CFG).mla
    inv, cs = blocks.rope_inv_freq(cfg.qk_rope_head_dim, cfg.rope_theta,
                                   cfg.yarn)
    want_inv, want_cs, want_scale = ref.yarn_frequencies(CFG)
    np.testing.assert_allclose(np.asarray(inv), np.asarray(want_inv),
                               rtol=1e-6)
    assert cs == pytest.approx(want_cs)
    assert cfg.softmax_scale == pytest.approx(want_scale)
    # factor 40: m = 0.1 ln 40 + 1, squared on the 24-wide scale
    assert want_scale == pytest.approx(24 ** -0.5 * (0.1 * np.log(40) + 1) ** 2)


MLA_POS = {"all_first_row": [0, 0, 0], "ragged": [5, 31, 17],
           "full": [31, 31, 31], "beyond": [40, 3, 99]}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("case", sorted(MLA_POS))
def test_decode_attend_mla_matches_the_einsum(case, dtype):
    """The flash-decode kernel's latent face (interpret mode), one
    position per cache row, blocks of 16 in a cache of 32 rows."""
    from chainermn_tpu.ops.decode_attention import decode_attend_mla

    pos = jnp.asarray(MLA_POS[case], jnp.int32)
    b, s, h, rank, width = 3, 32, 4, 32, 128
    k1, k2 = jax.random.split(jax.random.PRNGKey(len(case)))
    q = jax.random.normal(k1, (b, h, width)).astype(dtype)
    cache = jax.random.normal(k2, (b, s, width)).astype(dtype)
    got = decode_attend_mla(q, cache, pos, rank=rank, scale=0.2, block_s=16,
                            interpret=True)
    sc = jnp.einsum("bhw,bkw->bhk", q.astype(jnp.float32),
                    cache.astype(jnp.float32)) * 0.2
    live = jnp.arange(s)[None, None, :] <= pos[:, None, None]
    p = jax.nn.softmax(jnp.where(live, sc, -1e30), -1)
    want = jnp.einsum("bhk,bkr->bhr", p, cache[..., :rank].astype(jnp.float32))
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want),
                               rtol=tol, atol=tol)


# --------------------------------------------------------------------------
# the router against the equations
# --------------------------------------------------------------------------

def _route_numpy(x, w, bias, cfg):
    """The equations, in plain numpy, one token at a time."""
    e, g = cfg.n_experts, cfg.n_group
    idx_all, gates_all = [], []
    for u in np.asarray(x, np.float64):
        s = 1.0 / (1.0 + np.exp(-(u @ np.asarray(w, np.float64))))
        sel = s + np.asarray(bias, np.float64)
        groups = sel.reshape(g, e // g)
        score = np.sort(groups, -1)[:, -2:].sum(-1)
        kept = np.argsort(-score, kind="stable")[: cfg.topk_group]
        masked = np.full(e, -np.inf)
        for k in kept:
            masked[k * (e // g):(k + 1) * (e // g)] = \
                sel[k * (e // g):(k + 1) * (e // g)]
        idx = np.argsort(-masked, kind="stable")[: cfg.top_k]
        gate = s[idx] / s[idx].sum() * cfg.routed_scaling_factor
        idx_all.append(idx)
        gates_all.append(gate)
    return np.asarray(idx_all), np.asarray(gates_all)


@pytest.mark.parametrize("bias_scale", [0.0, 0.01, 0.5],
                         ids=["no_bias", "small_bias", "large_bias"])
def test_router_follows_the_equations(bias_scale):
    cfg = arch_of(CFG).moe
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(11), 3)
    x = jax.random.normal(k1, (40, 64))
    w = jax.random.normal(k2, (64, 16)) / 8.0
    bias = jax.random.normal(k3, (16,)) * bias_scale
    idx, gates = sigmoid_group_route(x, w, bias, cfg)
    want_idx, want_gates = _route_numpy(x, w, bias, cfg)
    assert (np.sort(np.asarray(idx), -1) == np.sort(want_idx, -1)).all()
    order = np.argsort(np.asarray(idx), -1)
    worder = np.argsort(want_idx, -1)
    np.testing.assert_allclose(
        np.take_along_axis(np.asarray(gates), order, -1),
        np.take_along_axis(want_gates, worder, -1), rtol=1e-5)
    # renormalised over the chosen, times the scaling factor
    np.testing.assert_allclose(np.asarray(gates).sum(-1), 2.5, rtol=1e-5)
    # at most topk_group groups are touched
    assert (np.asarray([len(set(r // 4)) for r in np.asarray(idx)])
            <= cfg.topk_group).all()


def test_the_bias_selects_but_does_not_weigh():
    """A large bias on expert 5 makes every token choose it; its gate is
    still its sigmoid score's share, not the biased score's."""
    cfg = arch_of(CFG).moe
    x = jax.random.normal(jax.random.PRNGKey(2), (16, 64))
    w = jax.random.normal(jax.random.PRNGKey(3), (64, 16)) / 8.0
    bias = jnp.zeros((16,)).at[5].set(10.0)
    idx, gates = sigmoid_group_route(x, w, bias, cfg)
    assert (np.asarray(idx) == 5).any(-1).all()
    s = np.asarray(jax.nn.sigmoid(x @ w))
    chosen = np.take_along_axis(s, np.asarray(idx), -1)
    np.testing.assert_allclose(
        np.asarray(gates), chosen / chosen.sum(-1, keepdims=True) * 2.5,
        rtol=1e-5)


def test_the_references_router_is_the_programs():
    cfg = arch_of(CFG).moe
    m = ref.init_params(jax.random.PRNGKey(8), CFG)["blocks"][1]["moe"]
    x = jax.random.normal(jax.random.PRNGKey(9), (30, 64))
    idx, gates = sigmoid_group_route(x, m["router"], m["router_bias"], cfg)
    ridx, rgates = ref.route(x, m, CFG, "float32")
    assert (np.sort(np.asarray(idx), -1) == np.sort(np.asarray(ridx), -1)
            ).all()
    np.testing.assert_allclose(np.sort(np.asarray(gates), -1),
                               np.sort(np.asarray(rgates), -1), rtol=1e-5)


# --------------------------------------------------------------------------
# the dropless layer: shares, skew, kernel
# --------------------------------------------------------------------------

def _full_layer(seed=0, **sizes):
    """An expert layer holding all 16 experts, and tokens."""
    cfg = dict(CFG, n_routed_experts_held=16, **sizes)
    m = ref.init_params(jax.random.PRNGKey(seed), cfg)["blocks"][1]["moe"]
    x = jax.random.normal(jax.random.PRNGKey(seed + 100), (24, 64))
    return m, x


def _share(m, first, n):
    return dict(m, **{k: m[k][first:first + n]
                      for k in ("w_gate", "w_up", "w_down")})


#: the router's numbers: DeepSeek-V3's (4 groups of which 2 are kept, gates
#: times 2.5), Kimi Linear's (one group, all kept: plain top-k; times
#: 2.446) and Laguna's (one group, times 2.5, experts a quarter as wide as
#: the model where the others' are a half) -- one router, one dropless
#: layer, other numbers
ROUTERS = {"4-groups-x2.5": {},
           "1-group-x2.446": {"n_group": 1, "topk_group": 1,
                              "routed_scaling_factor": 2.446},
           "1-group-x2.5-narrow": {"n_group": 1, "topk_group": 1,
                                   "routed_scaling_factor": 2.5,
                                   "moe_intermediate_size": 16}}


@pytest.mark.parametrize("router", sorted(ROUTERS))
@pytest.mark.parametrize("interpret", [None, True], ids=["dense", "kernel"])
def test_the_shares_add_up_to_the_uncut_layer(interpret, router):
    """The routed parts of all 4 shares (4 experts each), plus the shared
    expert counted once, are the uncut layer: program and reference."""
    cfg = dict(CFG, **ROUTERS[router])
    m, x = _full_layer(moe_intermediate_size=cfg["moe_intermediate_size"])
    assert m["w_gate"].shape == (16, 64, cfg["moe_intermediate_size"])
    moe = arch_of(cfg).moe
    assert (moe.n_group, moe.routed_scaling_factor) == (
        cfg["n_group"], cfg["routed_scaling_factor"])
    whole = MoEConfig(**dict(moe.__dict__, held=(0, 16)))
    want, counts, _ = moe_dropless(x, m, whole, interpret=interpret)
    shared = blocks.swiglu(x, m["shared"])
    total = shared
    held_sum = 0
    for r in range(4):
        part = MoEConfig(**dict(moe.__dict__, held=(4 * r, 4)))
        y, c, _ = moe_dropless(x, _share(m, 4 * r, 4), part,
                               interpret=interpret)
        total = total + (y - shared)
        held_sum += int(c[1])
        assert int(c[0]) == x.shape[0] * 4
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
    assert held_sum == int(counts[1]) == x.shape[0] * 4   # nothing dropped
    # the reference's uncut layer, and its shares
    idx, gates = ref.route(x, m, cfg, "float32")
    ref_whole = ref.gated_mlp(x, m["shared"], "float32") + ref.moe_routed(
        x, m, idx, gates, (0, 16), "float32")
    np.testing.assert_allclose(np.asarray(want), np.asarray(ref_whole),
                               rtol=1e-4, atol=1e-5)
    ref_parts = sum(ref.moe_routed(x, _share(m, 4 * r, 4), idx, gates,
                                   (4 * r, 4), "float32") for r in range(4))
    np.testing.assert_allclose(
        np.asarray(ref_parts + ref.gated_mlp(x, m["shared"], "float32")),
        np.asarray(ref_whole), rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("held", [(0, 4), (4, 4), (12, 4), (0, 16)],
                         ids=lambda h: f"{h[0]}+{h[1]}")
def test_kernel_path_equals_dense_path(held):
    m, x = _full_layer(seed=3)
    cfg = MoEConfig(**dict(arch_of(CFG).moe.__dict__, held=held))
    p = _share(m, *held)
    yd, cd, idx_d = moe_dropless(x, p, cfg)
    yk, ck, idx_k = moe_dropless(x, p, cfg, interpret=True)
    np.testing.assert_allclose(np.asarray(yk), np.asarray(yd), rtol=1e-5,
                               atol=1e-6)
    assert (np.asarray(cd) == np.asarray(ck)).all()
    assert (np.asarray(idx_d) == np.asarray(idx_k)).all()
    assert len(cd) == len(COUNT_FIELDS) + held[1]


@pytest.mark.parametrize("interpret", [None, True], ids=["dense", "kernel"])
def test_dropless_under_extreme_skew(interpret):
    """Every token to the same experts (a bias far above every score): no
    capacity, nothing dropped — each chosen expert sees ALL tokens."""
    m, x = _full_layer(seed=5)
    x = jnp.tile(x, (4, 1))                                   # 96 tokens
    favoured = jnp.zeros((16,)).at[jnp.asarray([0, 1, 2, 3])].set(50.0)
    m = dict(m, router_bias=favoured)
    cfg = MoEConfig(**dict(arch_of(CFG).moe.__dict__, held=(0, 4)))
    y, counts, idx = moe_dropless(x, _share(m, 0, 4), cfg,
                                  interpret=interpret)
    assert (np.sort(np.asarray(idx), -1) == np.arange(4)).all()
    assert np.asarray(counts).tolist() == [96 * 4, 96 * 4, 4] + [96] * 4
    ridx, rgates = ref.route(x, m, CFG, "float32")
    want = ref.gated_mlp(x, m["shared"], "float32") + ref.moe_routed(
        x, _share(m, 0, 4), ridx, rgates, (0, 4), "float32")
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=1e-4,
                               atol=1e-5)


def test_no_token_for_a_share_costs_no_tile():
    """A share none of whose experts is chosen: the grouped product runs
    no tile and the layer is the shared expert alone."""
    m, x = _full_layer(seed=6)
    away = jnp.zeros((16,)).at[jnp.arange(4, 16)].set(50.0)
    m = dict(m, router_bias=away)
    cfg = MoEConfig(**dict(arch_of(CFG).moe.__dict__, held=(0, 4)))
    y, counts, _ = moe_dropless(x, _share(m, 0, 4), cfg, interpret=True)
    assert np.asarray(counts)[1:].tolist() == [0, 0, 0, 0, 0, 0]
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(blocks.swiglu(x, m["shared"])),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("m_k_n_tm", [(64, 32, 48, 8), (128, 64, 256, 16),
                                      (96, 16, 128, 32)],
                         ids=lambda t: "x".join(map(str, t)))
def test_moe_gmm_multiplies_each_tile_by_its_expert(m_k_n_tm):
    from chainermn_tpu.ops.moe_gmm import moe_gmm, pick_tn

    m, k, n, tm = m_k_n_tm
    k1, k2 = jax.random.split(jax.random.PRNGKey(m))
    x = jax.random.normal(k1, (m, k))
    w = jax.random.normal(k2, (3, k, n))
    tiles = m // tm
    tile_expert = jnp.asarray([(2 * t) % 3 for t in range(tiles)], jnp.int32)
    n_valid = tiles - 1
    got = moe_gmm(x, w, tile_expert, n_valid, tm=tm, interpret=True)
    for t in range(n_valid):
        rows = slice(t * tm, (t + 1) * tm)
        np.testing.assert_allclose(
            np.asarray(got[rows]), np.asarray(x[rows] @ w[(2 * t) % 3]),
            rtol=1e-5, atol=1e-5)
    assert pick_tn(7168, 2048) == 512 and pick_tn(2048, 7168) == 1792
    assert pick_tn(32, 48) == 48


# --------------------------------------------------------------------------
# the pool follows the layer's declaration
# --------------------------------------------------------------------------

@pytest.mark.parametrize("path", ["prefill", "tick"])
def test_the_mla_entry_keeps_what_it_declares(
        params, mesh, kept_as_declared, path):
    """Layer 0, a latent-attention layer: ONE buffer of latent rows."""
    kept_as_declared(params, arch_of(CFG), HEAD_DIM, 0, path, mesh)


def test_cache_layout_declares_what_each_attention_keeps():
    latent = blocks.cache_layout(arch_of(CFG), 3, 0, "model")
    assert [tuple(w for w, _ in layer) for layer in latent] == [(128,)] * 3
    assert all(spec == P() for layer in latent for _, spec in layer)
    pair = blocks.cache_layout(blocks.DEFAULT_ARCH, 2, 64, "model")
    assert [tuple(w for w, _ in layer) for layer in pair] == [(64, 64)] * 2
    assert pair[0][0][1] == P(None, None, "model")
    # the published widths: 512 + 64 columns, padded to 5 lane tiles
    v3 = MLAConfig(128, 1536, 512, 128, 64, 128)
    assert v3.latent_width == 640


def test_latent_pool_prefix_copy_and_transfer(params, mesh):
    """One latent buffer per layer: the prefix copy, the packed slab and
    its landing follow the pool's declaration."""
    from chainermn_tpu.serving.transfer import KvTransferPlane

    eng = _engine(params, mesh, prefix_cache=False)
    pool = eng.pool
    assert [len(layer) for layer in pool.caches] == [1, 1, 1]
    assert pool.caches[0][0].shape == (4, 48, 128)
    assert pool.bytes_per_token == 3 * 128 * 4
    rng = np.random.default_rng(5)
    prompt = rng.integers(0, CFG["vocab_size"], 13, dtype=np.int32)
    slot = pool.acquire()
    eng.engine.prefill_into_slot(prompt, slot)
    src = [np.asarray(layer[0][slot]) for layer in pool.caches]
    assert all(np.abs(s[:13]).sum() > 0 for s in src)
    dst = pool.acquire()
    eng.engine.copy_prefix(slot, dst, 13)
    assert pool.pos[dst] == 13
    for layer, s in zip(pool.caches, src):
        np.testing.assert_array_equal(np.asarray(layer[0][dst]), s)
    plane = KvTransferPlane()
    payload = plane.pack(pool, slot, 13, meta={"x": 1})
    third = pool.acquire()
    out = plane.unpack_into(payload, pool, third)
    assert out["length"] == 13 and pool.pos[third] == 13
    assert out["ledger_bytes"] == 3 * 13 * 128 * 4
    for layer, s in zip(pool.caches, src):
        np.testing.assert_array_equal(np.asarray(layer[0][third][:13]),
                                      s[:13])
    eng.close()


def test_prefix_hit_through_the_latent_pool_is_token_exact(params, mesh):
    """A second request sharing a long prefix copies the latent rows and
    feeds only its suffix: same tokens as without the prefix cache."""
    rng = np.random.default_rng(6)
    shared = rng.integers(0, CFG["vocab_size"], 16, dtype=np.int32)
    prompts = [np.concatenate([shared, rng.integers(
        0, CFG["vocab_size"], 3, dtype=np.int32)]) for _ in range(2)]
    plain = _engine(params, mesh, prefix_cache=False)
    want = [h.tokens for h in _serve(plain, prompts, 6)]
    plain.close()
    eng = _engine(params, mesh)
    got = []
    for p in prompts:            # one after the other: the first donates
        got.append(_serve(eng, [p], 6)[0].tokens)
    assert eng.prefix_cache.hits >= 1
    assert got == want
    eng.close()


# --------------------------------------------------------------------------
# the slot position is bounded in the program
# --------------------------------------------------------------------------

def test_a_free_slots_position_holds(params, mesh):
    eng = _engine(params, mesh, prefix_cache=False)
    rng = np.random.default_rng(7)
    h = eng.submit(rng.integers(0, CFG["vocab_size"], 6, dtype=np.int32), 30)
    seen = []
    while eng.scheduler.queue_depth or eng.pool.busy_count:
        eng.step()
        seen.append(eng.pool.pos.copy())
    assert h.status == "done"
    seen = np.asarray(seen)
    assert (seen[:, 1:] == 0).all()            # three free slots: held
    # the busy one advanced: the prefill gives the first token, 29 ticks
    # the rest; the last tick is launched a step before the step that reads
    # it back and releases the slot, so its advance is seen
    assert seen[:, 0].max() == 6 + 30 - 1
    assert (eng.pool.pos == 0).all()           # released: reset
    eng.close()


def test_gpt2_engine_never_walks_past_its_position_table(devices):
    """The fault PR 24's benchmark found: with learned positions a free
    slot used to advance past the table, read NaN and poison its row."""
    from chainermn_tpu.parallel import init_tp_transformer_lm
    from chainermn_tpu.serving import ServingEngine

    p = init_tp_transformer_lm(jax.random.PRNGKey(0), 50, 32, 2, 2,
                               max_len=24)
    mesh = mn.make_nd_mesh(("model",), (1,), devices[:1])
    eng = ServingEngine(p, head_dim=16, n_slots=3, max_total=24, mesh=mesh,
                        queue_capacity=8, spill_bytes=0, prefix_cache=False)
    rng = np.random.default_rng(8)
    for _ in range(4):           # 4 x 20 ticks > 24 positions
        h = eng.submit(rng.integers(0, 50, 3, dtype=np.int32), 20)
        while eng.scheduler.queue_depth or eng.pool.busy_count:
            eng.step()
            assert eng.pool.pos.max() < 24
        assert h.status == "done" and max(h.tokens) < 50
    eng.close()


# --------------------------------------------------------------------------
# GPT-2's path is the same description with other values
# --------------------------------------------------------------------------

def _gpt2_block(seed=0):
    from chainermn_tpu.parallel import init_tp_transformer_lm

    p = init_tp_transformer_lm(jax.random.PRNGKey(seed), 64, 32, 4, 2,
                               max_len=32)
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (2, 8, 32))
    return p, x


@pytest.mark.parametrize(
    "arch", [None, blocks.DEFAULT_ARCH, LMArch(attn_kinds=("mha", "mha"))],
    ids=["none", "default", "kind-per-layer"])
def test_gpt2_block_is_bit_identical_through_the_description(mesh, arch):
    """``tp_block`` through ``blocks`` against the block written out as it
    stood before the description: the same bits."""
    from chainermn_tpu.parallel.tensor_parallel import tp_mlp
    from chainermn_tpu.parallel.transformer import (_layer_norm,
                                                    tp_attention, tp_block)

    p, x = _gpt2_block()
    blk = p["blocks"][0]

    def old(x, b):
        h = _layer_norm(x, b["ln1_scale"], b["ln1_bias"])
        x = x + tp_attention(h, b["attn"], head_dim=8, axis_name="model",
                             attn_impl="xla")
        h = _layer_norm(x, b["ln2_scale"], b["ln2_bias"])
        return x + tp_mlp(h, b["mlp"], axis_name="model")

    new = partial(tp_block, head_dim=8, axis_name="model", attn_impl="xla",
                  arch=arch)
    got = _in_mesh(new, mesh, 2)(x, blk)
    want = _in_mesh(old, mesh, 2)(x, blk)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_gpt2_description_is_the_default():
    p, _ = _gpt2_block()
    a = blocks.resolve(None)
    assert (a.norm, a.mlp, a.attn, a.tied_head, a.embed_scale) == (
        "layernorm", "gelu", "mha", True, True)
    assert blocks.head_table(a, p) is p["embed"]
    assert blocks.n_count_entries(a) == 0
    # one attention kind for the whole model, no state: nothing of the
    # per-layer kinds (PR 31) shows in the defaults
    assert (a.attn_kinds, a.kda, a.mamba) == (None, None, None)
    # nor of a window, a rotation of the layer's own, a gate on the context
    # or absent biases (PR 33)
    assert (a.windows, a.rotary, a.attn_gate, a.attn_bias, a.has_ring) == (
        None, None, False, True, False)
    assert [a.window(i) for i in range(3)] == [None] * 3
    assert [a.attn_kind(i) for i in range(3)] == ["mha"] * 3
    assert blocks.cache_layout(a, 2, 32, "model") == [
        ((32, P(None, None, "model")),) * 2] * 2
    from chainermn_tpu.parallel.transformer import transformer_lm_specs
    assert blocks.lm_specs(a, p, "model") == transformer_lm_specs(p, "model")


def test_gpt2_tick_program_returns_tokens_alone(devices):
    """No experts, no counts: the tick's one int32 result is the tokens."""
    from chainermn_tpu.parallel import init_tp_transformer_lm
    from chainermn_tpu.serving import ServingEngine

    p = init_tp_transformer_lm(jax.random.PRNGKey(0), 50, 32, 2, 2,
                               max_len=32)
    mesh = mn.make_nd_mesh(("model",), (1,), devices[:1])
    eng = ServingEngine(p, head_dim=16, n_slots=3, max_total=32, mesh=mesh,
                        spill_bytes=0)
    out = eng.engine.tick(np.zeros(3, np.int32))
    assert out.shape == (3,) and eng.engine.n_counts == 0
    m = eng.metrics()
    assert not any("moe" in k for k in m)
    assert m["serving/cache_bytes_per_token"] == 2 * 2 * 32 * 4
    # rows alone: no state, nothing counted as state
    assert m["serving/cache_state_bytes_per_slot"] == 0
    assert m["serving/tick_state_slots_live"] == 0
    assert m["serving/tick_state_bytes"] == 0
    assert m["serving/tick_latent_bytes"] \
        == m["serving/tick_cache_rows_live"] * 2 * 2 * 32 * 4
    eng.close()


def test_deepseek_description_is_bit_identical_with_a_kind_per_layer(
        params, mesh):
    """``attn='mla'`` for the whole model and ``'mla'`` named layer by
    layer (``attn_kinds``, which a model that mixes kinds uses) are one
    description: the same declaration, the same logits bit for bit from
    the prefill and from a tick, the same served tokens and routes."""
    import dataclasses

    from chainermn_tpu.parallel.decode import lm_prefill
    from chainermn_tpu.serving import ServingEngine

    whole = arch_of(CFG)
    each = dataclasses.replace(whole, attn_kinds=("mla",) * 3)
    # a window names MHA/GQA layers: a latent layer has none, whatever the
    # tuple says, and keeps its one rows buffer
    windowed = dataclasses.replace(whole, windows=(8,) * 3)
    assert not whole.has_ring and not windowed.has_ring
    assert blocks.cache_layout(windowed, 3, 0, "model") \
        == blocks.cache_layout(whole, 3, 0, "model")
    assert blocks.cache_layout(whole, 3, 0, "model") \
        == blocks.cache_layout(each, 3, 0, "model")
    assert blocks.lm_specs(whole, params, "model") \
        == blocks.lm_specs(each, params, "model")
    prompt = jnp.asarray(
        np.random.RandomState(5).randint(0, CFG["vocab_size"], (2, 11)),
        jnp.int32)

    def hidden(arch):
        fn = lambda p, t: lm_prefill(p, t, 16, head_dim=HEAD_DIM,
                                     axis_name="model", arch=arch)[0]
        return np.asarray(_in_mesh(fn, mesh, 2)(params, prompt))

    np.testing.assert_array_equal(hidden(whole), hidden(each))
    prompts = [np.arange(3, 12, dtype=np.int32),
               np.arange(20, 27, dtype=np.int32)]
    served = []
    for arch in (whole, each):
        eng = ServingEngine(params, head_dim=HEAD_DIM, mesh=mesh, arch=arch,
                            n_slots=4, max_total=48, prefill_bucket=8,
                            queue_capacity=8, spill_bytes=0)
        handles = _serve(eng, prompts, 6)
        served.append([(list(h.tokens), np.asarray(h.routes).tolist())
                       for h in handles])
        eng.close()
    assert served[0] == served[1]


def test_the_reference_under_tests_is_the_benchmarks_text():
    """The comparison that decides the cell's ``correct`` and these tests
    read ONE reference: the two files are the same text."""
    theirs = os.path.join(os.path.dirname(HERE), "benchmark", "reference",
                          "deepseek_v3.py")
    with open(theirs) as a, open(os.path.join(
            HERE, "deepseek_v3_reference.py")) as b:
        assert a.read() == b.read()
