#!/usr/bin/env python
"""Single-op device-time microbench via in-jit scan chains.

Per-dispatch host overhead can exceed most ops here, so each op is timed as ONE dispatch of a lax.scan that
chains the op N times (iteration i+1 consumes iteration i's output: no CSE,
no elision). Host readback of the final scalar is the barrier.
"""

import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from chainermn_tpu.ops.flash_attention import flash_attention

B, S, D, H = 8, 1024, 1024, 16
HD = D // H
PEAK = 197e12
N = 50  # scan length


def bench(make_chain, tag, flops_per_iter=None):
    """make_chain() -> (jitted fn of initial operands, operands)."""
    fn, args = make_chain()
    out = fn(*args)
    float(out)  # compile + warmup barrier
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        out = fn(*args)
        float(out)
        best = min(best, (time.perf_counter() - t0) / N)
    ms = best * 1e3
    entry = {"ms": round(ms, 3)}
    if flops_per_iter:
        entry["mfu"] = round(flops_per_iter / best / PEAK, 3)
    print(f"{tag}: {json.dumps(entry)}", flush=True)
    return ms


rs = np.random.RandomState(0)
mk = lambda *shape: jax.device_put(rs.randn(*shape).astype(jnp.bfloat16))


def chain(op, x0, *consts):
    """Scan op N times: carry = op(carry, *consts); return final scalar."""
    @jax.jit
    def run(x, *cs):
        def body(c, _):
            return op(c, *cs), None
        final, _ = jax.lax.scan(body, x, None, length=N)
        return jnp.max(final).astype(jnp.float32)
    return run, (x0, *consts)


def main():
    causal_flops = 2 * 2 * B * H * S * S * HD / 2

    q0, k0, v0 = mk(B, S, H, HD), mk(B, S, H, HD), mk(B, S, H, HD)

    def flash_op(q, k, v, **kw):
        return flash_attention(q, k, v, causal=True, **kw)

    bench(lambda: chain(flash_op, q0, k0, v0), "flash_fwd", causal_flops)
    for bq, bk in ((128, 256), (256, 256), (256, 512), (512, 512),
                   (512, 1024), (1024, 1024)):
        bench(lambda bq=bq, bk=bk: chain(
            lambda q, k, v: flash_op(q, k, v, block_q=bq, block_k=bk),
            q0, k0, v0), f"flash_fwd_b{bq}x{bk}", causal_flops)

    def xla_attn(q, k, v):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                       preferred_element_type=jnp.float32) / (HD ** 0.5)
        mask = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
        s = jnp.where(mask[None, None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)

    bench(lambda: chain(xla_attn, q0, k0, v0), "xla_attn_fwd", causal_flops)

    # fwd+bwd: chain dq back into q
    def flash_vjp(q, k, v):
        out, vjp = jax.vjp(lambda qq: flash_op(qq, k, v), q)
        (dq,) = vjp(out)
        return dq

    bench(lambda: chain(flash_vjp, q0, k0, v0), "flash_fwd_bwd(dq-only)",
          causal_flops * 2.5)

    def flash_vjp_all(q, k, v):
        out, vjp = jax.vjp(flash_op, q, k, v)
        dq, dk, dv = vjp(out)
        return dq

    bench(lambda: chain(flash_vjp_all, q0, k0, v0), "flash_fwd_bwd_all",
          causal_flops * 3.5)

    def xla_vjp_all(q, k, v):
        out, vjp = jax.vjp(xla_attn, q, k, v)
        dq, dk, dv = vjp(out)
        return dq

    bench(lambda: chain(xla_vjp_all, q0, k0, v0), "xla_attn_fwd_bwd_all",
          causal_flops * 3.5)

    # plain matmul (8192,1024)x(1024,1024), chained
    x0, w0 = mk(B * S, D), mk(D, D)
    bench(lambda: chain(lambda x, w: (x @ w) * 0.03, x0, w0),
          "matmul_8192x1024x1024", 2 * B * S * D * D)

    # MLP block
    wi0, bi0, wo0, bo0 = mk(D, 4 * D), mk(4 * D), mk(4 * D, D), mk(D)
    h0 = mk(B, S, D)

    def mlp(x, wi, bi, wo, bo):
        y = jax.nn.gelu(jnp.matmul(x, wi,
                        preferred_element_type=jnp.float32)
                        .astype(x.dtype) + bi)
        return jnp.matmul(y, wo,
                          preferred_element_type=jnp.float32).astype(x.dtype) * 0.03

    bench(lambda: chain(mlp, h0, wi0, bi0, wo0, bo0), "mlp_fwd",
          2 * B * S * D * 8 * D)

    # LayerNorm
    s0, b0 = mk(D), mk(D)

    def ln(x, s_, b_):
        x32 = x.astype(jnp.float32)
        mu = x32.mean(-1, keepdims=True)
        var = ((x32 - mu) ** 2).mean(-1, keepdims=True)
        return ((x32 - mu) * jax.lax.rsqrt(var + 1e-5) * s_ + b_).astype(x.dtype)

    bench(lambda: chain(ln, h0, s0, b0), "layernorm_fwd")

    # transpose roundtrip (B,S,H,hd)->(BH,S,hd)->back
    def tr(x):
        y = x.transpose(0, 2, 1, 3).reshape(B * H, S, HD)
        return y.reshape(B, H, S, HD).transpose(0, 2, 1, 3) * 0.999

    bench(lambda: chain(tr, q0), "transpose_roundtrip")

    # vocab CE fwd (logits materialize)
    tab0 = mk(32768, D)
    tgt = jax.device_put(
        rs.randint(0, 32768, (B, S)).astype(np.int32))

    def ce(x, tab):
        logits = jnp.einsum("bsd,vd->bsv", x, tab,
                            preferred_element_type=jnp.float32)
        m = logits.max(-1)
        se = jnp.exp(logits - m[..., None]).sum(-1)
        picked = jnp.take_along_axis(logits, tgt[..., None], -1)[..., 0]
        nll = jnp.mean(m + jnp.log(se) - picked)
        return x * (1.0 + 0.0 * nll)  # keep chain shape, depend on nll

    bench(lambda: chain(ce, h0, tab0), "vocab_ce_fwd",
          2 * B * S * D * 32768)


if __name__ == "__main__":
    main()
