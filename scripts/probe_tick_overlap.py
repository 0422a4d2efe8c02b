#!/usr/bin/env python
"""Probe (ISSUE 32; first run before anything was built, on the program
that took its tokens from the host alone): does a read of tick N's result
return when N ends, or only when a tick N+1 queued behind it ends?

Runs the ``serving_tick`` program of ``gpt2-medium-serve-steady`` at the
cell's size, directly (``pool.update`` + the engine's program): two chained
launches, the second fed the first's device result as its tokens; the first's
result is read with and without ``copy_to_host_async()`` issued before the
second launch.  Then loops of 100 ticks: serial (stage, launch, read), serial
with the tokens left on the device, and one tick in flight with and without
the asynchronous copy.  Chip only; prints one JSON object last (PERF.md,
Findings PR 32, has the first run's).

    chiprun -- python scripts/probe_tick_overlap.py
"""

import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

from benchmark.harness import session  # noqa: E402


def main() -> int:
    _, cell, devices = session.open_cell("gpt2-medium-serve-steady")
    ctx = session.context(cell, devices, 32000001, 1.0)
    server = ctx.family.build_server(ctx)
    import jax.numpy as jnp

    eng = server.eng
    de, pool = eng.engine, eng.pool
    rng = np.random.default_rng(0)
    for _ in range(6):       # the cell's ≈5 busy slots
        eng.submit(rng.integers(0, server.vocab, 200, dtype=np.int32), 600)
    for _ in range(12):
        eng.step()
    ctx.say(f"busy {pool.busy_count} pos {pool.pos.max()}")
    pos0 = pool.pos.copy()
    busy = pool.busy_mask()
    n = pool.n_slots
    keys = np.zeros((n, 2), np.uint32)
    temps = np.zeros(n, np.float32)
    clock = time.perf_counter

    on_device = np.full(n, -1, np.int32)
    result0 = de._last_result

    def launch(tokens):
        """Stage as the engine does (a device ``tokens`` is the tick
        before's result, taken where it is), dispatch, advance."""
        prev, override = ((result0, tokens) if isinstance(tokens, np.ndarray)
                          else (tokens, on_device))
        ops = (prev, jnp.asarray(np.array(override, np.int32, copy=True)),
               jnp.asarray(np.array(pool.pos, np.int32, copy=True)),
               jnp.asarray(keys.copy()), jnp.asarray(temps.copy()),
               jnp.asarray(busy.copy()))
        nxt = pool.update(lambda caches: de._tick_prog(de._params, caches,
                                                       *ops))
        pool.advance(busy)
        return nxt

    host0 = np.zeros(n, np.int32)
    for _ in range(5):                       # warm every path
        a = launch(host0)
        b = launch(a)
        a.copy_to_host_async()
        np.asarray(a), np.asarray(b)
    pool.pos = pos0.copy()

    out = {}
    # a tick alone: launch to read
    alone = []
    for _ in range(30):
        t = clock()
        np.asarray(launch(host0))
        alone.append((clock() - t) * 1e3)
    out["alone_launch_to_read_ms"] = statistics.median(alone)
    pool.pos = pos0.copy()

    # two chained launches on an idle device
    for name, use_async in (("chained_plain", False), ("chained_async", True)):
        ra, rb, l2 = [], [], []
        for _ in range(30):
            t = clock()
            a = launch(host0)
            if use_async:
                a.copy_to_host_async()
            b = launch(a)
            l2.append((clock() - t) * 1e3)
            np.asarray(a)
            ra.append((clock() - t) * 1e3)
            np.asarray(b)
            rb.append((clock() - t) * 1e3)
        out[name] = {"both_launched_ms": statistics.median(l2),
                     "read_first_ms": statistics.median(ra),
                     "read_second_ms": statistics.median(rb)}
        pool.pos = pos0.copy()

    # loops of 100 ticks
    def loop(mode):
        k = 100
        blocked = []
        prev = launch(host0)
        np.asarray(prev)
        t0 = clock()
        if mode == "serial":
            for _ in range(k):
                tok = np.asarray(prev)
                prev = launch(tok)
                t = clock()
                np.asarray(prev)
                blocked.append((clock() - t) * 1e3)
        elif mode == "serial_device_tokens":
            for _ in range(k):
                prev = launch(prev)
                t = clock()
                np.asarray(prev)
                blocked.append((clock() - t) * 1e3)
        else:
            prev = launch(prev)
            for _ in range(k):
                cur = launch(prev)
                if mode == "in_flight_async":
                    cur.copy_to_host_async()
                t = clock()
                np.asarray(prev)
                blocked.append((clock() - t) * 1e3)
                prev = cur
            np.asarray(prev)
        cadence = (clock() - t0) * 1e3 / k
        pool.pos = pos0.copy()
        return {"cadence_ms": cadence,
                "blocked_in_read_p50_ms": statistics.median(blocked)}

    for mode in ("serial", "serial_device_tokens", "in_flight_plain",
                 "in_flight_async", "serial", "in_flight_async"):
        key = mode if mode not in out else mode + "_again"
        out[key] = loop(mode)
    out["device"] = {"platform": devices[0].platform,
                     "kind": devices[0].device_kind}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
