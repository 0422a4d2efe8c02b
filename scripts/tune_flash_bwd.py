#!/usr/bin/env python
"""Flash attention sweep on the chip: the sub-block edge inside a grid
cell, and the grid's blocks.

Times forward-only and forward+backward separately per point, so the slow
half is identified rather than guessed, on the real chip with the
scan-chain method (one readback per rep chain; nothing is subtracted from a
measured wall time).  The default shape is the train cell's
(``gpt2-medium-train-s1024``: B 8, H 16, S 1024, D 64, causal, bf16); the
sub-block edge (``ops/flash_attention.py::_SUB_BLOCK``, a module constant)
is swept by setting it before each trace.

Usage:
  python scripts/tune_flash_bwd.py                       # the cell, edges 128/256/512
  python scripts/tune_flash_bwd.py --seq 8192 --batch 2 --head-dim 128 \\
      --blocks 512x1024,1024x1024,512x2048 --sub 256     # the grid's blocks
  python scripts/tune_flash_bwd.py --heads 128 --batch 1 --head-dim 192 \\
      --seq 3072 --fwd-only                              # a prefill's shape
"""

import argparse
import json
import sys
import time

sys.path.insert(0, ".")

import jax
import jax.numpy as jnp
import numpy as np

import chainermn_tpu  # noqa: F401  (the package's `ops` re-exports the function
#                       under the module's name: take the module itself)

fa = sys.modules["chainermn_tpu.ops.flash_attention"]

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


def _pairs(text):
    return [tuple(int(n) for n in p.split("x")) for p in text.split(",")]


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--heads", type=int, default=16)
    ap.add_argument("--seq", type=int, default=1024)
    ap.add_argument("--head-dim", type=int, default=64)
    ap.add_argument("--causal", type=int, default=1)
    ap.add_argument("--sub", default="128,256,512",
                    help="sub-block edges to sweep (comma separated)")
    ap.add_argument("--blocks", default=None,
                    help="grid blocks to sweep, QxK comma separated "
                         "(default: the kernels' own defaults)")
    ap.add_argument("--fwd-only", action="store_true")
    ap.add_argument("--reps", type=int, default=20)
    a = ap.parse_args()

    B, S, H, D, causal = a.batch, a.seq, a.heads, a.head_dim, bool(a.causal)
    rs = np.random.RandomState(0)
    q = jax.device_put(rs.randn(B, S, H, D).astype(jnp.bfloat16))
    flops_fwd = 2 * 2 * B * H * S * S * D / (2 if causal else 1)
    flops_fb = flops_fwd * 3.5

    for sub in (int(e) for e in a.sub.split(",")):
        for bq, bk in (_pairs(a.blocks) if a.blocks else [(None, None)]):
            fa._SUB_BLOCK = sub

            def fwd(c, bq=bq, bk=bk):
                return fa.flash_attention(c, c, c, causal=causal,
                                          block_q=bq, block_k=bk)

            def fb(c, bq=bq, bk=bk):
                # the backward's blocks are its own (it does not read the
                # forward's), so a swept pair is given to both
                o, vjp = jax.vjp(lambda x: fa.flash_attention(
                    x, x, x, causal=causal, block_q=bq, block_k=bk,
                    bwd_block_q=bq, bwd_block_k=bk), c)
                (dq,) = vjp(o)
                return dq

            row = {"B": B, "H": H, "S": S, "D": D, "causal": causal,
                   "sub": sub, "bq": bq, "bk": bk}
            try:
                ms_f = timed_ms(fwd, q, a.reps)
                row["fwd_ms"] = round(ms_f, 4)
                row["fwd_mfu"] = round(flops_fwd / (ms_f / 1e3) / PEAK, 3)
            except Exception as e:
                row["fwd_err"] = repr(e)[:200]
            if not a.fwd_only:
                try:
                    ms_fb = timed_ms(fb, q, a.reps)
                    row["fb_ms"] = round(ms_fb, 4)
                    row["fb_mfu"] = round(flops_fb / (ms_fb / 1e3) / PEAK, 3)
                    if "fwd_ms" in row:
                        bwd = ms_fb - row["fwd_ms"]
                        row["bwd_ms"] = round(bwd, 4)
                        row["bwd_mfu"] = round(
                            (flops_fb - flops_fwd) / (bwd / 1e3) / PEAK, 3)
                except Exception as e:
                    row["fb_err"] = repr(e)[:200]
            print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
