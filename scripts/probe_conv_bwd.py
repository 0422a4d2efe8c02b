"""Per-shape cost probe for ResNet-50's conv backward passes.

docs/PERF.md (NF-ResNet section) measured the ResNet-50 backward half at
~27.7 GB/step vs an ~11 GB analytic floor and attributed the excess to
XLA:TPU's backward-conv lowerings, quantifying a ~41 -> ~25 ms upside for
custom kernels but deferring them.  This probe breaks that aggregate down:
for every distinct conv shape in the ResNet-50 bottleneck stack it times
forward, dgrad (vjp wrt x) and wgrad (vjp wrt w) separately on the real
chip and reads XLA's bytes-accessed for each, against the per-op traffic
floor.  The output ranks shapes by (excess bytes x occurrence count) so
kernel work lands where the bytes are.

Usage:  python scripts/probe_conv_bwd.py [--batch 128] [--json out.json]
"""

from __future__ import annotations

import sys
sys.path.insert(0, ".")

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

# (name, H, W, Cin, Cout, k, stride, count_in_resnet50)
# Spatial sizes are the conv INPUT.  Counts from the torchvision bottleneck
# layout: layers (3, 4, 6, 3), stride-2 on the first 3x3 of layers 2-4.
SHAPES = [
    ("l1_1x1_in", 56, 56, 64, 64, 1, 1, 2),      # blocks 2-3 entry
    ("l1_1x1_in0", 56, 56, 64, 64, 1, 1, 1),     # block 1 entry (from stem)
    ("l1_3x3", 56, 56, 64, 64, 3, 1, 3),
    ("l1_1x1_out", 56, 56, 64, 256, 1, 1, 3),
    ("l1_proj", 56, 56, 64, 256, 1, 1, 1),
    ("l2_1x1_in", 56, 56, 256, 128, 1, 1, 1),
    ("l2_3x3_s2", 56, 56, 128, 128, 3, 2, 1),
    ("l2_1x1_in_b", 28, 28, 512, 128, 1, 1, 3),
    ("l2_3x3", 28, 28, 128, 128, 3, 1, 3),
    ("l2_1x1_out", 28, 28, 128, 512, 1, 1, 4),
    ("l2_proj_s2", 56, 56, 256, 512, 1, 2, 1),
    ("l3_1x1_in", 28, 28, 512, 256, 1, 1, 1),
    ("l3_3x3_s2", 28, 28, 256, 256, 3, 2, 1),
    ("l3_1x1_in_b", 14, 14, 1024, 256, 1, 1, 5),
    ("l3_3x3", 14, 14, 256, 256, 3, 1, 5),
    ("l3_1x1_out", 14, 14, 256, 1024, 1, 1, 6),
    ("l3_proj_s2", 28, 28, 512, 1024, 1, 2, 1),
    ("l4_1x1_in", 14, 14, 1024, 512, 1, 1, 1),
    ("l4_3x3_s2", 14, 14, 512, 512, 3, 2, 1),
    ("l4_1x1_in_b", 7, 7, 2048, 512, 1, 1, 2),
    ("l4_3x3", 7, 7, 512, 512, 3, 1, 2),
    ("l4_1x1_out", 7, 7, 512, 2048, 1, 1, 3),
    ("l4_proj_s2", 14, 14, 1024, 2048, 1, 2, 1),
]


def conv(x, w, stride):
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _time_once(jchain, args):
    for attempt in (1, 2, 3):
        try:
            jax.block_until_ready(jchain(*args))  # compile / warm
            break
        except Exception as e:
            if attempt == 3:
                raise
            print(f"  (compile retry {attempt}: {e!r:.80s})", flush=True)
            time.sleep(2)
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        jax.block_until_ready(jchain(*args))
        best = min(best, time.perf_counter() - t0)
    return best


_OVERHEAD_S = None


def _fixed_overhead():
    """Per-execution fixed cost (dispatch + readback), MEASURED once with
    a trivial program and subtracted from every chain time — a single
    chain would otherwise under-resolve sub-ms ops."""
    global _OVERHEAD_S
    if _OVERHEAD_S is None:
        x = jnp.float32(1.0)
        _OVERHEAD_S = _time_once(jax.jit(lambda v: v + 1.0), (x,))
        print(f"(measured fixed overhead: {_OVERHEAD_S*1e3:.1f} ms/execution)",
              flush=True)
    return _OVERHEAD_S


def _run_chain(make_chain, args, n=150):
    t = _time_once(jax.jit(make_chain(n)), args)
    return max(t - _fixed_overhead(), 0.0) / n * 1e3


def timed_carry(fn, x0, iters=20):
    """Chain where the op's output IS the next input — zero harness bytes.

    Only valid when output and input shapes/dtypes match (3x3 stride-1
    ci==co convs, and their dgrads).  A 1e-30 down-scale per step keeps
    values finite over the chain without adding traffic (it fuses)."""

    def make_chain(n):
        def chain(x):
            def body(c, _):
                out = fn(c)
                # 0.02 ~ 1/sqrt(9*64): keeps the chain's magnitude flat; the
                # scalar multiply fuses into the producing op (no extra bytes)
                return (out * 0.02).astype(c.dtype), None
            fin, _ = jax.lax.scan(body, x, None, length=n)
            return jnp.max(jnp.abs(fin)).astype(jnp.float32)
        return chain

    return _run_chain(make_chain, (x0,))


def _bytes(fn, *args):
    try:
        ca = jax.jit(fn).lower(*args).compile().cost_analysis()
        if isinstance(ca, list):
            ca = ca[0]
        return float(ca.get("bytes accessed", float("nan")))
    except Exception:
        return float("nan")


def timed(fn, *args, iters=20):
    """Per-call wall time via a scan chain inside ONE jit.

    Naive dispatch loops under-measure (dispatch is pipelined), so iterations are serialized with a scalar-carry
    data dependency: arg0 is nudged by the carry, the carry is refreshed
    from the output.  The nudge adds one read+write of arg0 and one read
    of the output per iteration — identical for every impl measured, so
    impl-vs-impl deltas are clean even though absolute floor ratios carry
    the harness bytes."""

    def make_chain(n):
        def chain(s, *a):
            def body(c, _):
                out = fn(a[0] * (1.0 + c * 1e-30).astype(a[0].dtype), *a[1:])
                leaf = out[0] if isinstance(out, (tuple, list)) else out
                return jnp.max(jnp.abs(leaf)).astype(jnp.float32) * 1e-30, None
            fin, _ = jax.lax.scan(body, s, None, length=n)
            return fin
        return chain

    ms = _run_chain(make_chain, (jnp.float32(0.0),) + tuple(args))
    try:
        comp = jax.jit(fn).lower(*args).compile()
        ca = comp.cost_analysis()
        if isinstance(ca, list):
            ca = ca[0]
        byts = float(ca.get("bytes accessed", float("nan")))
    except Exception:
        byts = float("nan")
    return ms, byts


def probe(batch, dtype=jnp.bfloat16, args_impl="xla", name_filter=""):
    rows = []
    for name, h, w_, cin, cout, k, s, cnt in SHAPES:
        if name_filter and name_filter not in name:
            continue
        if args_impl == "pallas" and (s != 1 or k not in (1, 3)):
            continue  # kernels cover stride-1 k in {1,3} only
        # three independent keys: drawing x/wt/dy from ONE key correlates
        # the tensors (identical underlying bits per shape prefix) and
        # skews the probe's arithmetic intensity — found by spmd-lint
        key = jax.random.PRNGKey(0)  # spmd-lint: disable=prng-constant-key — probes must be reproducible run-to-run
        kx, kw, kdy = jax.random.split(key, 3)
        x = jax.random.normal(kx, (batch, h, w_, cin), dtype)
        wt = jax.random.normal(kw, (k, k, cin, cout), dtype)
        ho, wo = h // s, w_ // s
        dy = jax.random.normal(kdy, (batch, ho, wo, cout), dtype)

        # The scan-chain harness nudges arg0, so arg0 must be one the
        # output depends on: x for fwd/wgrad, dy for dgrad.
        f = lambda x, wt: conv(x, wt, s)
        if args_impl == "pallas" and k == 3:
            from chainermn_tpu.ops.conv_backward import (
                conv3x3_dgrad, conv3x3_wgrad)
            dgrad = lambda dy: conv3x3_dgrad(dy, wt, x.shape, s)
            wgrad = lambda x: conv3x3_wgrad(x, dy, s)
        else:
            dgrad = lambda dy: jax.vjp(lambda x: f(x, wt), x)[1](dy)[0]
            wgrad = lambda x: jax.vjp(lambda wt: f(x, wt), wt)[1](dy)[0]

        carry_ok = k == 3 and s == 1 and cin == cout
        if carry_ok:
            fwd_ms, fwd_b = timed_carry(lambda v: f(v, wt), x), _bytes(f, x, wt)
            dg_ms, dg_b = timed_carry(dgrad, dy), _bytes(dgrad, dy)
        else:
            fwd_ms, fwd_b = timed(f, x, wt)
            dg_ms, dg_b = timed(dgrad, dy)
        wg_ms, wg_b = timed(wgrad, x)

        bpe = np.dtype(np.float16).itemsize  # bf16 = 2 bytes
        xb = batch * h * w_ * cin * bpe
        yb = batch * ho * wo * cout * bpe
        wb = k * k * cin * cout * bpe
        floors = {"fwd": xb + wb + yb, "dgrad": yb + wb + xb,
                  "wgrad": xb + yb + wb}
        flops = 2 * batch * ho * wo * k * k * cin * cout
        rows.append({
            "name": name, "count": cnt, "stride": s, "k": k,
            "shape": f"{h}x{w_}x{cin}->{cout}",
            "fwd_ms": round(fwd_ms, 3), "dgrad_ms": round(dg_ms, 3),
            "wgrad_ms": round(wg_ms, 3),
            "fwd_gb": round(fwd_b / 1e9, 3),
            "dgrad_gb": round(dg_b / 1e9, 3),
            "wgrad_gb": round(wg_b / 1e9, 3),
            "floor_gb": round(floors["fwd"] / 1e9, 3),
            "dgrad_x": round(dg_b / floors["dgrad"], 2),
            "wgrad_x": round(wg_b / floors["wgrad"], 2),
            "gflops": round(flops / 1e9, 1),
        })
        print(f"{name:14s} {rows[-1]['shape']:>18s} k{k} s{s} x{cnt}: "
              f"fwd {fwd_ms:6.2f}ms/{fwd_b/1e9:5.2f}GB  "
              f"dgrad {dg_ms:6.2f}ms/{dg_b/1e9:5.2f}GB ({rows[-1]['dgrad_x']}x floor)  "
              f"wgrad {wg_ms:6.2f}ms/{wg_b/1e9:5.2f}GB ({rows[-1]['wgrad_x']}x floor)",
              flush=True)
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--json", default=None)
    ap.add_argument("--impl", default="xla", choices=["xla", "pallas"])
    ap.add_argument("--filter", default="", help="substring filter on shape names")
    args = ap.parse_args()

    print(f"devices: {jax.devices()}  impl: {args.impl}", flush=True)
    rows = probe(args.batch, args_impl=args.impl, name_filter=args.filter)

    def tot(key_ms, key_gb):
        return (sum(r[key_ms] * r["count"] for r in rows),
                sum(r[key_gb] * r["count"] for r in rows))

    for part in ("fwd", "dgrad", "wgrad"):
        ms, gb = tot(f"{part}_ms", f"{part}_gb")
        print(f"TOTAL {part:6s}: {ms:7.2f} ms  {gb:6.2f} GB", flush=True)

    worst = sorted(rows, key=lambda r: -(r["wgrad_ms"] + r["dgrad_ms"]) * r["count"])
    print("\nworst backward shapes (ms x count):")
    for r in worst[:8]:
        print(f"  {r['name']:14s} {(r['wgrad_ms']+r['dgrad_ms'])*r['count']:7.2f} ms "
              f"(dgrad {r['dgrad_x']}x, wgrad {r['wgrad_x']}x floor)")

    if args.json:
        with open(args.json, "w") as fh:
            json.dump(rows, fh, indent=1)


if __name__ == "__main__":
    main()
