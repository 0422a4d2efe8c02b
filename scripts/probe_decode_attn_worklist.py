#!/usr/bin/env python
"""Probe (ISSUE 34; first run before the served tick was given the work
list): what does one flash-decode call cost a LIVE block and a SKIPPED grid
step, and does a compacted work list read the same live blocks faster?

On the committed tick shapes of ``gpt2-medium-serve-steady`` (32 slots x
1024 rows x 1024 columns, K and V, ``decode_attn_mha``) and
``deepseek-v3-ep16-serve-steady`` (64 slots x 4096 rows x 640 columns, 128
heads, ``decode_attn_mla``), with the pool as the ledger's PR 33 lines
describe it (a few slots busy, the others holding a cached prefix or
nothing), one call is timed under four walks over the SAME kernel bodies:

(a) ``ragged``   the ``(B, S / block)`` grid of PR 26-33: every slot's
                 blocks up to the position it holds, each row's dead blocks
                 skipped in place (rebuilt here from the bodies; the program
                 has no such walk any more);
(a') ``ragged_busy`` the same grid with the slots that serve nobody held at
                 position 0: the least PR 33's walk could read;
(b) ``padded``   the busy slots' live blocks contiguous, on a flat grid of
                 the static ``B * S / block`` steps, the rest padding;
(c) ``traced``   the same list on a grid whose bound is the list's length
                 (what ``ops/decode_attention.py`` runs).

Each with every slot busy at full length too (the walks then read the same
blocks), which gives the microseconds a live block; the steps that carry no
block give the microseconds a skipped step.  Busy rows must come out equal,
bit for bit, under every walk.  Chip only; prints one JSON object last
(PERF.md, Findings PR 34, has the first run's).

    chiprun -- python scripts/probe_decode_attn_worklist.py
"""

import argparse
import functools
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
from jax.experimental import pallas as pl  # noqa: E402
from jax.experimental.pallas import tpu as pltpu  # noqa: E402

from chainermn_tpu.ops import decode_attention as da  # noqa: E402

BLOCK = da.DEFAULT_BLOCK_S

#: name -> (slots, rows, busy slots, their positions' range, cached slots,
#: their positions' range): the ledger's PR 33 lines (slot_occupancy 12.6 %
#: of 32 / 21 % of 64; tick_cache_read_share 58 / 35 %)
SHAPES = {
    "gpt2": dict(b=32, s=1024, n_busy=4, busy_pos=(200, 900), n_cached=20,
                 cached_pos=(100, 800)),
    "deepseek": dict(b=64, s=4096, n_busy=13, busy_pos=(600, 1700),
                     n_cached=40, cached_pos=(300, 2600)),
}


class _Walk:
    """One of the walks, around ``ops/decode_attention.py``'s kernel bodies
    while a call is traced: ``ragged`` swaps the bodies' ``_step`` for the
    ``(B, S / block)`` grid's own (pair = the program ids, init at ``j ==
    0``, finish at the row's last grid step, body under ``j * block <=
    pos[i]``), ``padded`` the grid for the list's static length."""

    def __init__(self, walk, s):
        self.walk, self.s = walk, s

    def __enter__(self):
        self.was = da._step, da._work_grid
        n_blocks, s = self.s // BLOCK, self.s
        if self.walk == "ragged":
            def step(slot_ref, block_ref, n_ref, pos_ref, s_, block_s):
                i, j = pl.program_id(0), pl.program_id(1)
                live = jnp.minimum(pos_ref[i], s - 1) // BLOCK
                return ((i == 0) & (j == 0), i, jnp.minimum(j, live),
                        pos_ref[i], j <= live, j == 0, j == n_blocks - 1)
            da._step = step
        elif self.walk == "padded":
            da._work_grid = lambda work: (work.slot.shape[0],)

    def __exit__(self, *exc):
        da._step, da._work_grid = self.was


def _ragged_call(body, b, s, pos, in_specs, out_spec, scratch, out_shape,
                 name, *operands):
    """PR 33's call: position by scalar prefetch, grid ``(B, n_blocks)``;
    ``in_specs`` / ``out_spec`` take ``kv`` (the clamped cache index map),
    ``row`` (one block a slot) or ``whole``."""
    maps = {
        "kv": lambda i, j, p: (i, jnp.minimum(
            j, jnp.minimum(p[i], s - 1) // BLOCK), 0),
        "row": lambda i, j, p: (i, 0, 0),
        "whole": lambda i, j, p: (0, 0)}
    spec = lambda shape_map: pl.BlockSpec(shape_map[0], maps[shape_map[1]])
    return pl.pallas_call(
        lambda pos_ref, *refs: body(None, None, None, pos_ref, *refs),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b, s // BLOCK),
            in_specs=[spec(x) for x in in_specs], out_specs=spec(out_spec),
            scratch_shapes=[pltpu.VMEM(x, jnp.float32) for x in scratch]),
        out_shape=out_shape, name=name)(pos, *operands)


def mha_call(walk, q, kc, vc, pos, busy):
    b, s, d = kc.shape
    h, hd = 16, 64
    with _Walk(walk, s):
        if walk != "ragged":
            return da.decode_attend.__wrapped__(
                q, kc, vc, pos, busy, n_heads=h, head_dim=hd)
        seg = da._seg(d, h)
        return _ragged_call(
            functools.partial(da._kernel, s=s, block_s=BLOCK,
                              scale=1.0 / hd ** 0.5), b, s, pos,
            [((b, d), "whole"), ((1, BLOCK, d), "kv"), ((1, BLOCK, d), "kv"),
             ((d, h), "whole"), ((h, d), "whole")], ((b, d), "whole"),
            [(1, h), (1, h), (1, d)], jax.ShapeDtypeStruct((b, d), q.dtype),
            "probe_ragged_mha", q, kc, vc, seg, seg.T)


def mla_call(walk, q, cache, _, pos, busy):
    b, s, width = cache.shape
    h, rank = q.shape[1], 512
    with _Walk(walk, s):
        if walk != "ragged":
            return da.decode_attend_mla.__wrapped__(
                q, cache, pos, busy, rank=rank, scale=0.1)
        return _ragged_call(
            functools.partial(da._mla_kernel, s=s, block_s=BLOCK, scale=0.1,
                              rank=rank), b, s, pos,
            [((1, h, width), "row"), ((1, BLOCK, width), "kv")],
            ((1, h, rank), "row"), [(h, 128), (h, 128), (h, rank)],
            jax.ShapeDtypeStruct((b, h, rank), q.dtype),
            "probe_ragged_mla", q, cache)


REPS = 100


def looped(call, walk):
    """``REPS`` calls in ONE program, each fed the one before (a zero of
    its result added to the queries), so the device runs them back to back
    and no launch is in the time; returns the last call's result."""
    def run(q, *rest):
        def body(_, carry):
            zero, _ = carry
            out = call(walk, q + zero, *rest)
            return (out.reshape(-1)[0] * 0).astype(q.dtype), out
        out0 = call(walk, q, *rest)
        return jax.lax.fori_loop(0, REPS - 1, body, (
            (out0.reshape(-1)[0] * 0).astype(q.dtype), out0))[1]
    return jax.jit(run)


def timed(fn, *args):
    """Median microseconds a call."""
    jax.block_until_ready(fn(*args))
    laps = []
    for _ in range(5):
        t = time.perf_counter()
        out = jax.block_until_ready(fn(*args))
        laps.append((time.perf_counter() - t) / REPS * 1e6)
    return statistics.median(laps), out


def probe(name, call, operands):
    shape = SHAPES[name]
    b, s = shape["b"], shape["s"]
    rs = np.random.RandomState(34)
    order = rs.permutation(b)
    busy = np.zeros(b, bool)
    busy[order[:shape["n_busy"]]] = True
    pos = np.zeros(b, np.int32)                  # a free slot holds 0
    pos[busy] = rs.randint(*shape["busy_pos"], size=shape["n_busy"])
    cached = order[shape["n_busy"]:shape["n_busy"] + shape["n_cached"]]
    pos[cached] = rs.randint(*shape["cached_pos"], size=shape["n_cached"])
    held, _ = da.live_blocks(pos, s, BLOCK)
    needed, total = da.live_blocks(pos, s, BLOCK, busy)
    pos_busy = np.where(busy, pos, 0).astype(np.int32)
    full_pos = np.full(b, s - 1, np.int32)
    everyone = np.ones(b, bool)

    fns = {w: looped(call, w) for w in ("ragged", "padded", "traced")}
    us, outs = {}, {}
    for key, walk, p, m in (
            ("ragged", "ragged", pos, everyone),
            ("ragged_busy", "ragged", pos_busy, everyone),
            ("padded", "padded", pos, busy),
            ("traced", "traced", pos, busy),
            ("ragged_full", "ragged", full_pos, everyone),
            ("padded_full", "padded", full_pos, everyone),
            ("traced_full", "traced", full_pos, everyone)):
        us[key], outs[key] = timed(fns[walk], *operands, jnp.asarray(p),
                                   jnp.asarray(m))
    rows = np.flatnonzero(busy)
    same = {k: bool(np.array_equal(np.asarray(outs["ragged"])[rows],
                                   np.asarray(outs[k])[rows]))
            for k in ("ragged_busy", "padded", "traced")}
    same["full"] = bool(
        np.array_equal(np.asarray(outs["ragged_full"]),
                       np.asarray(outs["padded_full"]))
        and np.array_equal(np.asarray(outs["ragged_full"]),
                           np.asarray(outs["traced_full"])))
    idle_zero = bool((np.asarray(outs["traced"], np.float32)[~busy]
                      == 0).all())
    live_us = us["traced_full"] / total
    return {
        "blocks": {"total": total, "held_by_every_slot": held,
                   "busy_slots_live": needed,
                   "busy_slots": int(busy.sum())},
        "call_us": us,
        "us_per_live_block": {
            "every_block_live_ragged": us["ragged_full"] / total,
            "every_block_live_list": live_us,
            "ragged_as_the_pool_stands": us["ragged"] / held,
            "list_of_the_busy_slots": us["traced"] / needed},
        "us_per_skipped_step": {
            "ragged": (us["ragged"] - held * us["ragged_full"] / total)
            / (total - held),
            "padded": (us["padded"] - us["traced"]) / (total - needed)},
        "list_over_ragged": us["traced"] / us["ragged"],
        "busy_share_of_held_blocks": needed / held,
        "busy_rows_bit_equal_to_ragged": same,
        "idle_rows_read_zero": idle_zero,
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=34,
                    help="of the queries and caches (the pools' positions "
                         "are fixed: the ledger's)")
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("no TPU: this probe times the chip", file=sys.stderr)
        return 2
    keys = jax.random.split(jax.random.PRNGKey(args.seed), 5)
    bf = jnp.bfloat16
    out = {}
    g = SHAPES["gpt2"]
    out["gpt2_mha"] = probe("gpt2", mha_call, (
        jax.random.normal(keys[0], (g["b"], 1024), bf),
        jax.random.normal(keys[1], (g["b"], g["s"], 1024), bf),
        jax.random.normal(keys[2], (g["b"], g["s"], 1024), bf)))
    print(json.dumps(out["gpt2_mha"]), flush=True)
    d = SHAPES["deepseek"]
    out["deepseek_mla"] = probe("deepseek", mla_call, (
        jax.random.normal(keys[3], (d["b"], 128, 640), bf),
        jax.random.normal(keys[4], (d["b"], d["s"], 640), bf), None))
    out["device"] = {"platform": dev.platform, "kind": dev.device_kind}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
