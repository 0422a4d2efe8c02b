#!/usr/bin/env python
"""Long-context flash backward block hunt.

S=8k/16k attention MFU sat at 0.22-0.245 vs 0.50+ for the same kernels at
S=1k.  This sweep times forward-only and forward+backward separately per
(block_q, block_k) so the slow half is identified rather than guessed, on
the real chip with the scan-chain method (one readback per rep chain;
nothing is subtracted from a measured wall time).

Usage: python scripts/tune_flash_bwd.py [S]
"""

import json
import sys
import time

sys.path.insert(0, ".")

import jax
import jax.numpy as jnp
import numpy as np

from chainermn_tpu.ops.flash_attention import flash_attention

PEAK = 197e12


def timed_ms(fn, x, reps):
    @jax.jit
    def chain(qq):
        def body(c, _):
            return fn(c).astype(c.dtype), None
        fin, _ = jax.lax.scan(body, qq, None, length=reps)
        return jnp.max(fin).astype(jnp.float32)

    float(chain(x))
    best = float("inf")
    for _ in range(2):
        t0 = time.perf_counter()
        float(chain(x))
        best = min(best, (time.perf_counter() - t0) / reps)
    return best * 1e3


def main():
    S = int(sys.argv[1]) if len(sys.argv) > 1 else 8192
    B = 2 if S <= 8192 else 1
    H, D = 16, 64
    rs = np.random.RandomState(0)
    q = jax.device_put(rs.randn(B, S, H, D).astype(jnp.bfloat16))
    flops_fwd = 2 * 2 * B * H * S * S * D / 2
    flops_fb = flops_fwd * 3.5
    reps = 20 if S <= 8192 else 12

    for bq, bk in ((512, 1024), (512, 512), (1024, 512), (1024, 1024),
                   (256, 1024), (2048, 512), (512, 2048), (2048, 1024),
                   (1024, 2048)):
        def fwd(c, bq=bq, bk=bk):
            return flash_attention(c, c, c, causal=True,
                                   block_q=bq, block_k=bk)

        def fb(c, bq=bq, bk=bk):
            # Sweep the BACKWARD blocks too: since the late-round-4
            # decoupling, the backward no longer reads the forward's
            # blocks, so a forward-only sweep would time the fixed
            # bwd default at every point.
            o, vjp = jax.vjp(lambda a: flash_attention(
                a, a, a, causal=True, block_q=bq, block_k=bk,
                bwd_block_q=bq, bwd_block_k=bk), c)
            (dq,) = vjp(o)
            return dq

        row = {"S": S, "bq": bq, "bk": bk}
        try:
            ms_f = timed_ms(fwd, q, reps)
            row["fwd_ms"] = round(ms_f, 2)
            row["fwd_mfu"] = round(flops_fwd / (ms_f / 1e3) / PEAK, 3)
        except Exception as e:
            row["fwd_err"] = repr(e)[:120]
        try:
            ms_fb = timed_ms(fb, q, reps)
            row["fb_ms"] = round(ms_fb, 2)
            row["fb_mfu"] = round(flops_fb / (ms_fb / 1e3) / PEAK, 3)
            if "fwd_ms" in row:
                bwd = ms_fb - row["fwd_ms"]
                row["bwd_ms"] = round(bwd, 2)
                row["bwd_mfu"] = round(
                    (flops_fb - flops_fwd) / (bwd / 1e3) / PEAK, 3)
        except Exception as e:
            row["fb_err"] = repr(e)[:120]
        print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
