#!/usr/bin/env python
"""Probe (ISSUE 37; first run before the served tick was given the writer):
what does one layer's row write cost as the vmapped
``dynamic_update_slice`` the tick ran until PR 35 — a ``while`` loop over
every slot of every buffer — against ``ops/kv_cache.py::write_rows``' one
in-place kernel over the busy slots?

On the committed tick shapes of ``gpt2-medium-serve-steady`` (32 slots x
1024 rows x 1024 columns, K and V), of ``laguna-xs2-ep16-serve-mixed`` (24
slots: a 512-row ring and 4096 rows, 1024 columns, K and V) and of
``deepseek-v3-ep16-serve-steady`` (64 slots x 4096 rows x 640 columns, one
latent buffer), bf16, with 3 / 10 / all slots busy.  ``REPS`` writes run in
ONE program, each at the row after the one before on donated buffers, so
the device runs them back to back and no launch is in the time.  The
kernel's block is timed at the dtype's whole sublane tile (16 rows of bf16)
and at 8 rows.  Busy slots' rows must come out equal under both writers,
and the kernel must leave the other slots bit for bit as they were.  If a
call costs more than ~20 us at 3 busy slots the design is wrong.  Chip
only; prints one JSON object last (PERF.md, Findings PR 37, has the first
run's).

    chiprun -- python scripts/probe_cache_write.py
"""

import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from chainermn_tpu.ops import kv_cache as kvc  # noqa: E402

#: name -> (slots, rows, columns, buffers a layer)
SHAPES = {
    "gpt2_rows": (32, 1024, 1024, 2),
    "laguna_ring": (24, 512, 1024, 2),
    "laguna_rows": (24, 4096, 1024, 2),
    "deepseek_latent": (64, 4096, 640, 1),
}
REPS = 100


def vmapped(bufs, rows, pos, busy):
    """The parent's write: every slot, whether busy or not."""
    del busy
    return tuple(jax.vmap(lambda c, r, p: jax.lax.dynamic_update_slice(
        c, r, (p, 0)))(c, r, pos) for c, r in zip(bufs, rows))


def kernel(bufs, rows, pos, busy):
    return kvc.write_rows(bufs, rows, pos, busy)


def looped(write, total):
    """``REPS`` writes in one program on donated buffers, write ``i`` at
    ``(pos + i) % total`` with the new rows plus ``i``."""
    def run(bufs, rows, pos, busy):
        def body(i, bufs):
            new = tuple(r + i.astype(r.dtype) for r in rows)
            return write(bufs, new, (pos + i) % total, busy)
        return jax.lax.fori_loop(0, REPS, body, bufs)
    return jax.jit(run, donate_argnums=(0,))


def timed(fn, bufs, *args):
    """Median microseconds a write, and the buffers after the last lap."""
    bufs = jax.block_until_ready(fn(bufs, *args))
    laps = []
    for _ in range(5):
        t = time.perf_counter()
        bufs = jax.block_until_ready(fn(bufs, *args))
        laps.append((time.perf_counter() - t) / REPS * 1e6)
    return statistics.median(laps), bufs


def probe(name):
    n, total, cols, k = SHAPES[name]
    rs = np.random.RandomState(37)
    out = {"shape": [n, total, cols], "buffers": k}
    start = [rs.randn(n, total, cols).astype(np.float32) for _ in range(k)]
    first = [np.asarray(jnp.asarray(c, jnp.bfloat16), np.float32)
             for c in start]
    fresh = lambda: tuple(jnp.asarray(c, jnp.bfloat16) for c in start)
    for n_busy in (3, 10, n):
        busy = np.isin(np.arange(n), rs.permutation(n)[:n_busy])
        pos = rs.randint(0, total, n).astype(np.int32)
        rows = tuple(jnp.asarray(rs.randn(n, 1, cols), jnp.bfloat16)
                     for _ in range(k))
        args = (rows, jnp.asarray(pos), jnp.asarray(busy))
        res, us = {}, {}
        us["vmapped"], res["vmapped"] = timed(looped(vmapped, total),
                                              fresh(), *args)
        us["kernel"], res["kernel"] = timed(looped(kernel, total), fresh(),
                                            *args)
        was = kvc._sublanes
        kvc._sublanes = lambda dtype: 8
        kvc._write_rows_kernel.clear_cache()    # traced once a shape
        try:
            us["kernel_8_rows"], res["kernel_8_rows"] = timed(
                looped(kernel, total), fresh(), *args)
        finally:
            kvc._sublanes = was
            kvc._write_rows_kernel.clear_cache()
        for key in ("kernel", "kernel_8_rows"):
            for was_c, got, ref in zip(first, res[key], res["vmapped"]):
                got, ref = (np.asarray(a, np.float32) for a in (got, ref))
                assert np.array_equal(got[busy], ref[busy]), (name, key)
                assert np.array_equal(got[~busy], was_c[~busy]), (name, key)
        out[f"busy_{n_busy}"] = {key: round(v, 2) for key, v in us.items()}
    return out


def main():
    if jax.default_backend() != "tpu":
        print(json.dumps({"error": "no TPU: the probe times device code"}))
        return 2
    result = {"probe": "cache_write", "reps": REPS,
              "device": jax.devices()[0].device_kind}
    for name in SHAPES:
        result[name] = probe(name)
        print(json.dumps({name: result[name]}), flush=True)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
