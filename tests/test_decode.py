"""KV-cache decoding tests.

Beyond-reference (the reference generated only via seq2seq greedy
translate): the incremental decoder must produce EXACTLY the tokens a full
re-forward of the growing sequence would pick (the cache is an exactness
contract, not an approximation), for learned and RoPE positions, fused and
GQA attention, TP-sharded and not.
"""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

import chainermn_tpu as mn
from chainermn_tpu.parallel import (
    init_tp_transformer_lm,
    make_lm_generator,
    tp_transformer_lm_loss,
    transformer_lm_specs,
)

VOCAB, D, HEADS, LAYERS, SEQ = 32, 16, 4, 2, 24
HEAD_DIM = D // HEADS
B, S_P, NEW = 2, 6, 5


def _forward_logits(p, tokens):
    """Reference forward: per-position logits ``(B, S, V)`` from the public
    training-path pieces — the oracle the greedy tests score against."""
    from chainermn_tpu.parallel.tensor_parallel import (
        vocab_parallel_embedding)
    from chainermn_tpu.parallel.transformer import _layer_norm, tp_block

    x = vocab_parallel_embedding(tokens, p["embed"], axis_name="model")
    x = x * (p["embed"].shape[1] ** 0.5)
    positions = None
    if "pos_embed" in p:
        x = x + p["pos_embed"][: x.shape[1]][None]
    else:
        positions = jnp.arange(x.shape[1])
    for blk in p["blocks"]:
        x = tp_block(x, blk, head_dim=HEAD_DIM, axis_name="model",
                     positions=positions)
    x = _layer_norm(x, p["lnf_scale"], p["lnf_bias"])
    return jnp.einsum("bsd,vd->bsv", x, p["embed"],
                      preferred_element_type=jnp.float32)


def _full_forward_argmax_oracle(params, prompt, new_tokens, devices):
    """Greedy reference: re-run the FULL sequence each step on a 1-device
    model-axis mesh and take the last position's argmax."""
    mesh = mn.make_nd_mesh(("data", "model"), (1, 1), devices[:1])
    fn = shard_map(lambda p, t: _forward_logits(p, t)[:, -1],
                   mesh=mesh, in_specs=(P(), P()), out_specs=P())
    seq = prompt
    out = []
    for _ in range(new_tokens):
        logits = np.asarray(jax.jit(fn)(params, seq))
        nxt = logits.argmax(-1).astype(np.int32)
        out.append(nxt)
        seq = np.concatenate([seq, nxt[:, None]], axis=1)
    return np.stack(out, axis=1)


@pytest.mark.parametrize("pos_impl", ["learned", "rope"])
@pytest.mark.parametrize("n_kv_heads", [None, 2])
def test_cached_decode_matches_full_reforward(devices, pos_impl, n_kv_heads):
    params = init_tp_transformer_lm(
        jax.random.PRNGKey(0), VOCAB, D, HEADS, LAYERS, max_len=SEQ,
        pos_impl=pos_impl, n_kv_heads=n_kv_heads)
    rng = np.random.RandomState(0)
    prompt = rng.randint(0, VOCAB, (B, S_P)).astype(np.int32)

    mesh = mn.make_nd_mesh(("data", "model"), (1, 2), devices[:2])
    gen = make_lm_generator(mesh, "model", head_dim=HEAD_DIM,
                            max_new_tokens=NEW)
    got = np.asarray(gen(params, prompt))
    want = _full_forward_argmax_oracle(params, prompt, NEW, devices)
    np.testing.assert_array_equal(got, want)


def test_tp_sharding_does_not_change_tokens(devices):
    params = init_tp_transformer_lm(
        jax.random.PRNGKey(1), VOCAB, D, HEADS, LAYERS, max_len=SEQ)
    rng = np.random.RandomState(1)
    prompt = rng.randint(0, VOCAB, (B, S_P)).astype(np.int32)
    outs = {}
    for tp in (1, 2, 4):
        mesh = mn.make_nd_mesh(("data", "model"), (1, tp), devices[:tp])
        gen = make_lm_generator(mesh, "model", head_dim=HEAD_DIM,
                                max_new_tokens=NEW)
        outs[tp] = np.asarray(gen(params, prompt))
    np.testing.assert_array_equal(outs[1], outs[2])
    np.testing.assert_array_equal(outs[1], outs[4])


def test_sampling_without_rng_raises(devices):
    """Determinism-trap regression: temperature > 0 with rng=None used to
    fall back silently to PRNGKey(0), so every default-rng call sampled
    the IDENTICAL token sequence.  The contract is now explicit: sampling
    requires a key; greedy (temperature=0) still runs without one."""
    params = init_tp_transformer_lm(
        jax.random.PRNGKey(2), VOCAB, D, HEADS, LAYERS, max_len=64)
    prompt = np.zeros((1, 4), np.int32)
    mesh = mn.make_nd_mesh(("data", "model"), (1, 2), devices[:2])
    gen = make_lm_generator(mesh, "model", head_dim=HEAD_DIM,
                            max_new_tokens=4, temperature=1.0)
    with pytest.raises(ValueError, match="explicit rng"):
        gen(params, prompt)
    greedy = make_lm_generator(mesh, "model", head_dim=HEAD_DIM,
                               max_new_tokens=4)
    assert np.asarray(greedy(params, prompt)).shape == (1, 4)


def test_sampling_is_reproducible_and_varied(devices):
    params = init_tp_transformer_lm(
        jax.random.PRNGKey(2), VOCAB, D, HEADS, LAYERS, max_len=64)
    rng = np.random.RandomState(2)
    prompt = rng.randint(0, VOCAB, (B, S_P)).astype(np.int32)
    mesh = mn.make_nd_mesh(("data", "model"), (1, 2), devices[:2])
    gen = make_lm_generator(mesh, "model", head_dim=HEAD_DIM,
                            max_new_tokens=8, temperature=1.0)
    a = np.asarray(gen(params, prompt, jax.random.PRNGKey(7)))
    b = np.asarray(gen(params, prompt, jax.random.PRNGKey(7)))
    c = np.asarray(gen(params, prompt, jax.random.PRNGKey(8)))
    np.testing.assert_array_equal(a, b)  # same key → same tokens
    assert (a != c).any()                # different key → different draw
    assert ((a >= 0) & (a < VOCAB)).all()


def test_learned_positions_length_guard(devices):
    params = init_tp_transformer_lm(
        jax.random.PRNGKey(3), VOCAB, D, HEADS, LAYERS, max_len=8)
    prompt = np.zeros((1, 6), np.int32)
    mesh = mn.make_nd_mesh(("data", "model"), (1, 1), devices[:1])
    gen = make_lm_generator(mesh, "model", head_dim=HEAD_DIM,
                            max_new_tokens=5)  # 6 + 5 > 8
    with pytest.raises(ValueError, match="max_len"):
        gen(params, prompt)


def test_sampling_noise_is_fresh_per_step(devices):
    """Regression: the Gumbel key must be salted per step — frozen noise
    makes a high-temperature draw from a near-uniform model emit the SAME
    token forever (P[8 identical fair draws from V=32] ~ 3e-11)."""
    params = init_tp_transformer_lm(
        jax.random.PRNGKey(4), VOCAB, D, HEADS, LAYERS, max_len=64)
    prompt = np.zeros((1, 4), np.int32)
    mesh = mn.make_nd_mesh(("data", "model"), (1, 2), devices[:2])
    gen = make_lm_generator(mesh, "model", head_dim=HEAD_DIM,
                            max_new_tokens=8, temperature=5.0)
    out = np.asarray(gen(params, prompt, jax.random.PRNGKey(0)))[0]
    assert len(set(out.tolist())) > 1, out


def _in_model_mesh(fn, devices, n_args):
    mesh = mn.make_nd_mesh(("data", "model"), (1, 1), devices[:1])
    return shard_map(fn, mesh=mesh, in_specs=(P(),) * n_args, out_specs=P())


@pytest.mark.parametrize("path,kind", [
    ("train", "mamba"), ("train", "nope"), ("prefill", "nope"),
    ("tick", "nope")])
def test_a_kind_the_path_does_not_run_is_refused_by_name(devices, path,
                                                         kind):
    """``blocks.LAYER_KINDS`` is the one door: a kind with no training
    forward (a selective-scan layer) and a kind the table lacks raise an
    error that names the kind and the layer — neither falls through to
    another kind's code (latent attention in the loss, MHA in serving)."""
    from chainermn_tpu.parallel.blocks import LMArch, MambaConfig
    from chainermn_tpu.parallel.decode import lm_decode_tick, lm_prefill
    from chainermn_tpu.parallel.transformer import tp_block

    params = init_tp_transformer_lm(
        jax.random.PRNGKey(9), VOCAB, D, HEADS, LAYERS, max_len=SEQ)
    arch = LMArch(attn_kinds=("mha", kind), mamba=MambaConfig(2 * D))
    kw = dict(head_dim=HEAD_DIM, axis_name="model", arch=arch)
    tokens = jnp.zeros((B, S_P), jnp.int32)

    def run(p, t):
        if path == "train":
            return tp_block(jnp.zeros((B, S_P, D)), p["blocks"][1], layer=1,
                            **kw)
        if path == "prefill":
            return lm_prefill(p, t, SEQ, **kw)[0]
        caches = [(jnp.zeros((B, SEQ, D)),) * 2] * LAYERS
        return lm_decode_tick(p, t[:, 0], caches, jnp.full((B,), S_P), **kw)[0]

    with pytest.raises(NotImplementedError,
                       match=f"layer 1 is described with attention kind "
                             f"{kind!r}"):
        jax.eval_shape(_in_model_mesh(run, devices, 2), params, tokens)


@pytest.mark.parametrize("path", ["prefill", "tick"])
def test_the_mha_entry_keeps_what_it_declares(devices, kept_as_declared,
                                              path):
    params = init_tp_transformer_lm(
        jax.random.PRNGKey(9), VOCAB, D, HEADS, LAYERS, max_len=SEQ,
        n_kv_heads=2)
    mesh = mn.make_nd_mesh(("model",), (1,), devices[:1])
    kept_as_declared(params, None, HEAD_DIM, 1, path, mesh)
