#!/usr/bin/env python
# spmd-lint: disable-file=prng-constant-key — fixed seeds are the point:
# profile/probe runs must be bit-reproducible across commits to be comparable
"""Component breakdown of the greedy decode tick.

Where does the per-token time go at d1024/L8/h16/V32k/b8?  Replicates
``parallel/decode.py :: lm_generate``'s scan with switchable components
and times each variant at TWO cache lengths, so every component splits
into a FIXED cost and an S-MARGINAL cost (the part that scales with
cache length — the bandwidth-floor comparison the round-4 verdict asks
about).

Variants (cumulative knockouts):
  full        the real tick (embed + 8 blocks + vocab logits/argmax)
  no_logits   argmax replaced by a cheap h-derived token
  no_append   caches attended but never written (appends removed)
  no_attend   ctx = broadcast(q) (cache neither read nor written,
              but still carried)
  no_cache    caches not even carried (pure projections/MLP tick)

Timing: best-of-3 chains of ``reps`` generator calls with one host
readback at the end (amortized over the chain).
"""

import json
import os
import sys
import time
from functools import partial

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp
import numpy as np

import chainermn_tpu as mn
from chainermn_tpu.parallel.decode import _decoder_core, _prefill
from chainermn_tpu.parallel import (init_tp_transformer_lm, shard_pytree,
                                    transformer_lm_specs)
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

VOCAB, D, H, L, HD = 32768, 1024, 16, 8, 64
B = 8


def make_gen(mesh, total, new, variant):
    """A jitted greedy generator with the given knockout variant."""

    def inner(params, prompt):
        axis = "model"
        s_p = prompt.shape[1]
        embed, attn_block, block_with, rope = _decoder_core(params, HD, axis)
        blocks = params["blocks"]

        def logits_next(h_last, step_pos):
            if variant in ("no_logits", "no_append", "no_attend", "no_cache"):
                return (h_last.astype(jnp.float32).sum(-1)).astype(jnp.int32) % VOCAB
            table = params["embed"]
            start = jax.lax.axis_index(axis) * table.shape[0]
            logits = jnp.einsum("bd,vd->bv", h_last, table,
                                preferred_element_type=jnp.float32)
            local_best = logits.max(-1)
            local_idx = start + logits.argmax(-1)
            gbest = jax.lax.pmax(local_best, axis)
            winner = (local_best == gbest)
            return jax.lax.pmin(
                jnp.where(winner, local_idx, jnp.int32(2 ** 30)), axis)

        h, caches = _prefill(params, embed, attn_block, prompt, total, HD)
        first = logits_next(h[:, -1], jnp.int32(s_p))

        def attn_variant(x, blk, kc, vc, positions, write_at, q_valid):
            if variant == "no_cache" or variant == "no_attend":
                def attend(q, k, v):
                    n = x.shape[0]
                    ctx = (q + k.mean() + v.mean()).reshape(
                        n, 1, H, HD)
                    return ctx, (kc, vc)
                return block_with(x, blk, positions, attend)
            if variant == "no_append":
                def attend(q, k, v):
                    # the real attend (new (b, h, t, d) cache layout)
                    # minus the cache_append
                    n = x.shape[0]
                    s_q = q.shape[1]
                    valid = (q_valid + jnp.arange(s_q) + 1)[
                        None, None, None, :, None]
                    hkv = kc.shape[1]
                    g = q.shape[2] // hkv
                    q5 = q.reshape(n, s_q, hkv, g, HD)
                    s = jnp.einsum("bqhgd,bhkd->bhgqk", q5, kc,
                                   preferred_element_type=jnp.float32) \
                        / (HD ** 0.5)
                    mask = (jnp.arange(kc.shape[2])[
                        None, None, None, None, :] < valid)
                    s = jnp.where(mask, s, -1e30)
                    p = jax.nn.softmax(s, axis=-1)
                    ctx = jnp.einsum("bhgqk,bhkd->bqhgd",
                                     p.astype(vc.dtype), vc,
                                     preferred_element_type=jnp.float32
                                     ).astype(x.dtype)
                    return ctx, (kc, vc)
                return block_with(x, blk, positions, attend)
            return attn_block(x, blk, kc, vc, positions, write_at, q_valid)

        def tick(carry, i):
            token, caches = carry
            pos = s_p + i - 1
            x = embed(token[:, None], pos[None])
            new_caches = []
            for blk, (kc, vc) in zip(blocks, caches):
                x, kc, vc = attn_variant(x, blk, kc, vc, pos[None], pos, pos)
                new_caches.append((kc, vc))
            h = jnp.asarray(x)
            from chainermn_tpu.parallel.transformer import _layer_norm
            h = _layer_norm(h, params["lnf_scale"], params["lnf_bias"])
            nxt = logits_next(h[:, -1], s_p + i)
            if variant == "no_cache":
                new_caches = caches
            return (nxt, new_caches), token

        (last, _), toks = jax.lax.scan(
            tick, (first, caches), jnp.arange(1, new))
        return jnp.concatenate([toks.T, last[:, None]], axis=1).astype(
            jnp.int32)

    specs_cache = {}

    def apply(params, prompt):
        specs = transformer_lm_specs(params, "model")
        key = jax.tree_util.tree_structure(specs)
        if key not in specs_cache:
            specs_cache[key] = jax.jit(shard_map(
                inner, mesh=mesh, in_specs=(specs, P()), out_specs=P()))
        sharded = jax.tree_util.tree_map(
            lambda x, sp: jax.device_put(x, NamedSharding(mesh, sp)),
            params, specs)
        return specs_cache[key](sharded, prompt)

    return apply


def main():
    mesh = mn.make_nd_mesh(("model",), (len(jax.devices()),))
    out = {}
    for sp, new in ((512, 512), (2048, 512)):
        total = sp + new
        params = init_tp_transformer_lm(
            jax.random.PRNGKey(0), VOCAB, D, H, L, max_len=total,
            dtype=jnp.bfloat16)
        prompt = jnp.asarray(np.random.RandomState(0).randint(
            0, VOCAB, (B, sp)), jnp.int32)

        def timed(fn):
            np.asarray(fn(params, prompt))
            best = float("inf")
            for _ in range(3):
                t0 = time.perf_counter()
                for _ in range(4):
                    fn(params, prompt)
                np.asarray(fn(params, prompt))
                best = min(best, (time.perf_counter() - t0) / 5)
            return best

        pre = timed(make_gen(mesh, total, 1, "full"))
        row = {}
        for variant in ("full", "no_logits", "no_append", "no_attend",
                        "no_cache"):
            t = timed(make_gen(mesh, total, new, variant))
            row[variant] = round((t - pre) / new * 1e3, 3)
        out[f"total_{total}"] = row
        print(f"total={total}: {row}", file=sys.stderr, flush=True)
    # S-marginal per variant (us/position over the added 1536 positions)
    marg = {v: round((out["total_2560"][v] - out["total_1024"][v])
                     / 1536 * 1e3, 3)
            for v in out["total_1024"]}
    out["s_marginal_us_per_pos"] = marg
    out["floor_us_per_pos"] = 0.33
    print(json.dumps(out))


if __name__ == "__main__":
    main()
