"""Mellum 2 (3 sliding-window + 1 full GQA layer a period, softmax-routed
experts, no shared expert) TRAINED through the program's normal path —
``tp_transformer_lm_loss(arch=LMArch(...))`` under
``make_hybrid_shard_map_step`` with optax AdamW — against the plain float32
reference (``tests/mellum2_reference.py``, a copy of
``benchmark/reference/mellum2.py``) at tiny widths on the CPU: loss, per-leaf
gradients, three AdamW steps, recomputation, the routing counts, and THE
SHARES ADD UP (ISSUE 38).  The flash kernels (banded and causal, forward and
backward) run in interpret mode throughout; the grouped expert product's
kernels (``moe_gmm``, ``moe_gmm_dw``) where a test says ``kernels`` — outside
``shard_map``'s vma typing, which interpreted scalar-prefetch index maps
cannot carry (the chip compiles them inside it:
``tests/test_chip_compile.py``)."""

import os
import sys
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

import chainermn_tpu as mn
from chainermn_tpu.parallel import (make_hybrid_shard_map_step,
                                    tp_transformer_lm_loss)
from chainermn_tpu.parallel import moe as moe_mod
from chainermn_tpu.parallel.blocks import (LMArch, MoEConfig, Rotary,
                                           lm_specs)

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import mellum2_reference as ref  # noqa: E402

HEAD_DIM, WINDOW, SEQ, VOCAB = 8, 8, 32, 64
#: one whole period at tiny widths: GQA group 8, 8 experts of which 4 held
CFG = {
    "hidden_size": 32, "num_hidden_layers": 4, "num_attention_heads": 8,
    "num_key_value_heads": 1, "head_dim": HEAD_DIM, "sliding_window": WINDOW,
    "layer_types": ["sliding_attention"] * 3 + ["full_attention"],
    "mlp_layer_types": ["sparse"] * 4, "moe_intermediate_size": 16,
    "num_experts": 8, "num_experts_held": 4, "num_experts_per_tok": 2,
    "norm_topk_prob": True, "vocab_size": VOCAB, "rms_norm_eps": 1e-6,
    "rope_parameters": {
        "full_attention": {"rope_type": "yarn", "rope_theta": 500000,
                           "factor": 4,
                           "original_max_position_embeddings": 16,
                           "beta_fast": 4, "beta_slow": 1,
                           "attention_factor": 1.1386294361119891},
        "sliding_attention": {"rope_type": "default", "rope_theta": 500000}},
    "assumed": {"init": {"query_gain": 2.0}}}
OPT = {"lr": 3e-4, "b1": 0.9, "b2": 0.95, "weight_decay": 0.1, "eps": 1e-8}


def arch_of(cfg, held=None, norm_topk_prob=None):
    rp = cfg["rope_parameters"]
    full, slide = rp["full_attention"], rp["sliding_attention"]
    turn = {"sliding_attention": Rotary(theta=float(slide["rope_theta"])),
            "full_attention": Rotary(
                theta=float(full["rope_theta"]),
                yarn=(full["factor"],
                      full["original_max_position_embeddings"],
                      full["beta_fast"], full["beta_slow"]),
                attention_factor=full["attention_factor"])}
    return LMArch(
        norm="rmsnorm", norm_eps=cfg["rms_norm_eps"], mlp="swiglu",
        attn="mha", tied_head=False, embed_scale=False, attn_bias=False,
        layer_kinds=("moe",) * cfg["num_hidden_layers"],
        windows=tuple(cfg["sliding_window"] if t == "sliding_attention"
                      else None for t in cfg["layer_types"]),
        rotary=tuple(turn[t] for t in cfg["layer_types"]),
        moe=MoEConfig(
            n_experts=cfg["num_experts"], top_k=cfg["num_experts_per_tok"],
            n_group=1, topk_group=1, routed_scaling_factor=1.0,
            norm_topk_prob=cfg["norm_topk_prob"] if norm_topk_prob is None
            else norm_topk_prob,
            held=held or (0, cfg["num_experts_held"]), router="softmax",
            n_shared=0))


ARCH = arch_of(CFG)


@pytest.fixture(scope="module")
def params():
    return ref.init_params(jax.random.PRNGKey(1), CFG)


@pytest.fixture(scope="module")
def tokens():
    return jax.random.randint(jax.random.PRNGKey(2), (2, SEQ + 1), 0, VOCAB)


@pytest.fixture(scope="module")
def want(tokens):
    """The reference's three AdamW steps on the same batch thrice."""
    return ref.train_steps(jax.random.PRNGKey(1), CFG, OPT, [tokens] * 3)


@pytest.fixture(scope="module")
def mesh(devices):
    return mn.make_nd_mesh(("data", "model"), (1, 1), devices[:1])


@pytest.fixture
def expert_kernels(monkeypatch):
    """The grouped product's kernel path, interpreted (``blocks.ffn`` looks
    ``moe_dropless`` up at call time)."""
    monkeypatch.setattr(moe_mod, "moe_dropless",
                        partial(moe_mod.moe_dropless, interpret=True))


def _loss(**kw):
    return partial(tp_transformer_lm_loss, head_dim=HEAD_DIM,
                   axis_name="model", attn_impl="flash", ce_impl="fused",
                   arch=ARCH, **kw)


def _grad(mesh, params, tokens, **kw):
    """``((loss, aux), grads)`` of the program's loss, its collectives
    bound and vma typing off (what interpreted expert kernels need)."""
    specs = lm_specs(ARCH, params, "model")
    fn = jax.jit(jax.shard_map(
        jax.value_and_grad(_loss(aux=True, **kw), has_aux=True),
        mesh=mesh, in_specs=(specs, P("data")),
        out_specs=((P(), {"counts": P(), "routes": P("data")}), specs),
        check_vma=False))
    return fn(params, (tokens,))


def test_loss_and_gradients_match_the_reference_through_the_kernels(
        mesh, params, tokens, want, expert_kernels):
    (loss, aux), grads = _grad(mesh, params, tokens)
    assert abs(float(loss) - want["losses"][0]) < 1e-5
    norms = np.asarray(ref.leaf_norms(grads))
    assert ref.worst_leaf_gap(norms, want["grad_norms"]) < 1e-4
    # every leaf, element by element, against the reference's own gradient
    n_tok = tokens.shape[0] * SEQ
    (_, routes), ref_grads = jax.value_and_grad(
        partial(ref.loss_sum, cfg=CFG), has_aux=True)(params, tokens)
    for got, exp in zip(jax.tree_util.tree_leaves(grads),
                        jax.tree_util.tree_leaves(ref_grads)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(exp) / n_tok,
                                   rtol=2e-3, atol=2e-6)
    # the counts are the reference's routes, counted: 2 x 32 tokens x top-2
    # x 4 layers in all, and each held expert's tokens
    counts, routes = np.asarray(aux["counts"]), np.asarray(routes)
    assert ref.route_disagreement(aux["routes"], routes) == 0.0
    assert counts[0] == n_tok * 2 * 4
    assert counts[3:].tolist() == [int((routes == e).sum())
                                   for e in range(4)]
    assert counts[1] == counts[3:].sum()


def test_three_adamw_steps_match_the_reference(mesh, params, tokens, want):
    """The hybrid step with optax AdamW (the expert layer's dense fallback
    on the CPU; flash and the banded flash interpreted)."""
    optimizer = optax.adamw(OPT["lr"], b1=OPT["b1"], b2=OPT["b2"],
                            eps=OPT["eps"], weight_decay=OPT["weight_decay"])
    step = make_hybrid_shard_map_step(
        _loss(aux=True, remat=True), optimizer, mesh, params,
        lm_specs(ARCH, params, "model"), data_axis="data",
        batch_spec=P("data"), has_aux=True, donate=False,
        aux_specs={"counts": P(), "routes": P("data")})
    p, st, losses = params, optimizer.init(params), []
    for i in range(3):
        p, st, loss, aux = step(p, st, (tokens,))
        losses.append(float(loss))
        # the step's own routes, at the weights the steps before it left
        assert ref.route_disagreement(aux["routes"],
                                      want["routes"][i]) == 0.0
        if i == 0:    # AdamW's first moment is (1 - b1) g
            mu = optax.tree_utils.tree_get(st, "mu")
            grad_norms = np.asarray(ref.leaf_norms(mu)) / (1 - OPT["b1"])
    np.testing.assert_allclose(losses, want["losses"], atol=2e-5)
    assert losses[2] < losses[0]
    assert ref.worst_leaf_gap(grad_norms, want["grad_norms"]) < 1e-4
    update = np.asarray(ref.leaf_norms(jax.tree_util.tree_map(
        jnp.subtract, p, params)))
    assert ref.worst_leaf_gap(update, want["update_norms"]) < 2e-3
    counts = aux["counts"]
    assert counts.dtype == jnp.int32 and counts.shape == (3 + 4,)


@pytest.mark.parametrize("path", ["kernels", "fallback"])
def test_recomputation_changes_nothing(mesh, params, tokens, path,
                                       monkeypatch):
    if path == "kernels":
        monkeypatch.setattr(moe_mod, "moe_dropless",
                            partial(moe_mod.moe_dropless, interpret=True))
    (l0, c0), g0 = _grad(mesh, params, tokens, remat=False)
    (l1, c1), g1 = _grad(mesh, params, tokens, remat=True)
    assert float(l0) == float(l1)
    for name in ("counts", "routes"):
        np.testing.assert_array_equal(np.asarray(c0[name]),
                                      np.asarray(c1[name]))
    for a, b in zip(jax.tree_util.tree_leaves(g0),
                    jax.tree_util.tree_leaves(g1)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-8)


@pytest.mark.parametrize("path", ["kernels", "fallback"])
def test_the_shares_add_up(path):
    """The four shares' parts of ONE expert layer — ``held`` = (0, 2) …
    (6, 2) of 8 experts — forward and the gradients with respect to the
    layer's input and the router, sum to the uncut reference layer's."""
    cfg = dict(CFG, num_experts_held=8)
    whole = ref.init_params(jax.random.PRNGKey(5), cfg)["blocks"][0]["moe"]
    x = jax.random.normal(jax.random.PRNGKey(6), (48, 32))
    ct = jax.random.normal(jax.random.PRNGKey(7), (48, 32))

    def reference(x, router):
        m = dict(whole, router=router)
        idx, gates = ref.route(x, m, cfg, "float32")
        return (ref.moe_routed(x, m, idx, gates, (0, 8), "float32")
                * ct).sum()

    def share(first):
        def part(x, router):
            m = {"router": router, **{name: whole[name][first:first + 2]
                                      for name in ("w_gate", "w_up",
                                                   "w_down")}}
            y, counts, _ = moe_mod.moe_dropless(
                x, m, arch_of(cfg, held=(first, 2)).moe,
                interpret=True if path == "kernels" else None)
            return (y * ct).sum(), counts
        return jax.value_and_grad(part, argnums=(0, 1), has_aux=True)(
            x, whole["router"])

    want_y, (want_dx, want_dr) = jax.value_and_grad(
        reference, argnums=(0, 1))(x, whole["router"])
    parts = [share(first) for first in (0, 2, 4, 6)]
    got_y = sum(float(y) for (y, _), _ in parts)
    got_dx = sum(g[0] for _, g in parts)
    got_dr = sum(g[1] for _, g in parts)
    assert abs(got_y - float(want_y)) < 1e-3 * abs(float(want_y))
    np.testing.assert_allclose(np.asarray(got_dx), np.asarray(want_dx),
                               rtol=1e-3, atol=1e-5)
    np.testing.assert_allclose(np.asarray(got_dr), np.asarray(want_dr),
                               rtol=1e-3, atol=1e-5)
    # every assignment is held by exactly one share
    held = sum(int(c[1]) for (_, c), _ in parts)
    assert held == int(parts[0][0][1][0]) == 48 * 2


@pytest.mark.parametrize("norm", [True, False])
def test_softmax_routing_renormalises_where_the_model_says(norm):
    cfg = dict(CFG, norm_topk_prob=norm)
    router = jax.random.normal(jax.random.PRNGKey(8), (32, 8))
    x = jax.random.normal(jax.random.PRNGKey(9), (40, 32))
    idx, gates = moe_mod.softmax_topk_route(
        x, router, arch_of(cfg).moe)
    want_idx, want_gates = ref.route(x, {"router": router}, cfg, "float32")
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(want_idx))
    np.testing.assert_allclose(np.asarray(gates), np.asarray(want_gates),
                               rtol=1e-5)
    sums = np.asarray(gates.sum(-1))
    if norm:
        np.testing.assert_allclose(sums, 1.0, rtol=1e-5)
    else:
        assert (sums <= 1.0).all() and sums.min() < 0.95
    probs = jax.nn.softmax(x @ router, -1)      # top-2 of the softmax
    np.testing.assert_array_equal(
        np.sort(np.asarray(idx), -1),
        np.sort(np.asarray(jnp.argsort(-probs, -1)[:, :2]), -1))
    # the gates carry the router's gradient, the choice none
    g = jax.grad(lambda r: (moe_mod.softmax_topk_route(
        x, r, arch_of(cfg).moe)[1] ** 2).sum())(router)
    assert np.isfinite(np.asarray(g)).all() and float(jnp.abs(g).max()) > 0


def test_an_unknown_router_is_refused():
    cfg = arch_of(CFG).moe
    bad = MoEConfig(**{**cfg.__dict__, "router": "argmax"})
    with pytest.raises(ValueError, match="router"):
        moe_mod.moe_dropless(jnp.zeros((4, 32)), {"router": jnp.zeros(
            (32, 8))}, bad)


def test_counts_are_summed_and_floats_averaged_over_the_data_axis(devices):
    """``make_hybrid_shard_map_step(has_aux=True)``: an integer leaf of the
    aux (a count) is summed over the replicas, any other averaged."""
    mesh2 = mn.make_nd_mesh(("data", "model"), (2, 1), devices[:2])
    w = {"w": jnp.ones((4,))}

    def loss_fn(p, batch):
        x = batch[0]
        return ((x @ p["w"]) ** 2).mean(), {
            "rows": jnp.int32(x.shape[0]) + x[:, 0].astype(jnp.int32).sum(),
            "mean": x.mean()}

    step = make_hybrid_shard_map_step(
        loss_fn, optax.sgd(0.0), mesh2, w, {"w": P()}, data_axis="data",
        batch_spec=P("data"), has_aux=True, donate=False)
    x = jnp.arange(24.0).reshape(6, 4)
    _, _, _, aux = step(w, optax.sgd(0.0).init(w), (x,))
    assert aux["rows"].dtype == jnp.int32
    assert int(aux["rows"]) == 6 + int(x[:, 0].sum())
    assert float(aux["mean"]) == pytest.approx(float(x.mean()))


def test_an_aux_leaf_a_sample_is_not_reduced(devices):
    """``aux_specs``: a leaf whose spec names the data axis (an expert
    layer's chosen experts a token) comes out of the step as it is, sharded
    over the replicas; the leaves beside it are reduced as ever."""
    mesh2 = mn.make_nd_mesh(("data", "model"), (2, 1), devices[:2])
    w = {"w": jnp.ones((4,))}

    def loss_fn(p, batch):
        x = batch[0]
        return ((x @ p["w"]) ** 2).mean(), {
            "rows": jnp.int32(x.shape[0]),
            "first": x[:, 0].astype(jnp.int32)}

    step = make_hybrid_shard_map_step(
        loss_fn, optax.sgd(0.0), mesh2, w, {"w": P()}, data_axis="data",
        batch_spec=P("data"), has_aux=True, donate=False,
        aux_specs={"rows": P(), "first": P("data")})
    x = jnp.arange(24.0).reshape(6, 4)
    _, _, _, aux = step(w, optax.sgd(0.0).init(w), (x,))
    assert int(aux["rows"]) == 6
    assert aux["first"].tolist() == x[:, 0].astype(jnp.int32).tolist()


def test_routing_counts_are_booked_from_the_host():
    from chainermn_tpu.observability import trace

    tr = trace.get_tracer()
    was = tr.enabled
    moe_mod.book_routing_counts(np.array([10, 4, 2, 3, 1]))    # off: nothing
    tr.enable()
    try:
        before = tr.counters()
        moe_mod.book_routing_counts(np.array([10, 4, 2, 3, 1]))
        moe_mod.book_routing_counts([30, 9, 2, 4, 5], steps=3)
        after = tr.counters()
    finally:
        tr.enabled = was
    delta = {k: after[k] - before.get(k, 0.0) for k in after
             if k.startswith("train/moe_")}
    assert delta == {"train/moe_steps": 4.0,
                     "train/moe_assignments_total": 40.0,
                     "train/moe_assignments_held": 13.0,
                     "train/moe_expert_tokens/0": 7.0,
                     "train/moe_expert_tokens/1": 6.0}


def test_the_training_block_runs_a_window_and_a_rotation(params, mesh):
    """``tp_block`` no longer refuses a window or a ``Rotary`` record (it
    still refuses an output gate): a sliding layer's result is the
    reference layer's, and differs from the same layer without its band."""
    from chainermn_tpu.parallel.transformer import tp_block

    x = jax.random.normal(jax.random.PRNGKey(3), (1, SEQ, 32))
    blk = params["blocks"][0]

    def run(arch, impl):
        return jax.jit(jax.shard_map(
            lambda x, b: tp_block(x, b, head_dim=HEAD_DIM, axis_name="model",
                                  attn_impl=impl, arch=arch, layer=0),
            mesh=mesh, in_specs=(P(), P()), out_specs=P()))(x, blk)

    want = ref.layer(x, blk, CFG, 0)[0]
    for impl in ("flash", "xla"):
        np.testing.assert_allclose(np.asarray(run(ARCH, impl)),
                                   np.asarray(want), rtol=2e-4, atol=2e-4)
    unbanded = LMArch(**{**ARCH.__dict__, "windows": None})
    assert float(jnp.abs(run(unbanded, "xla") - want).max()) > 1e-2
    gated = LMArch(**{**ARCH.__dict__, "attn_gate": True})
    with pytest.raises(NotImplementedError, match="attn_gate=True"):
        run(gated, "xla")


def test_aux_needs_an_expert_layer(mesh, tokens):
    from chainermn_tpu.parallel import init_tp_transformer_lm

    dense = init_tp_transformer_lm(jax.random.PRNGKey(0), VOCAB, 16, 4, 1,
                                   max_len=SEQ)
    with pytest.raises(ValueError, match="no expert layer"):
        jax.shard_map(
            partial(tp_transformer_lm_loss, head_dim=4, axis_name="model",
                    aux=True),
            mesh=mesh, in_specs=(P(), P("data")), out_specs=P(),
            check_vma=False)(dense, (tokens,))


def test_the_two_copies_of_the_reference_are_one_text():
    here = os.path.dirname(os.path.abspath(__file__))
    a = os.path.join(here, "mellum2_reference.py")
    b = os.path.join(os.path.dirname(here), "benchmark", "reference",
                     "mellum2.py")
    assert open(a).read() == open(b).read()
