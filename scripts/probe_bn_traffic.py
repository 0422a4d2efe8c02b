#!/usr/bin/env python
"""How much of ResNet-50's HBM traffic does training BatchNorm cost?

docs/PERF.md's roofline pinned the b=128 step at 44 GB accessed — HBM-bound
on v5e — and named BN's extra activation passes as the biggest slice.  This
probe measures that claim directly by AOT-compiling the SAME train step with
three norm layers and reading XLA's bytes-accessed + flops, then timing each
on the real chip:

  bn       — reference-parity BatchNorm (current-batch stats): the baseline.
  stalebn  — one-step-stale stats (models/resnet.py :: StaleBatchNorm): the
             normalize becomes a constant-affine epilogue XLA can fuse into
             the producing conv; only the stats reduction still reads the
             activation.  (Perf-probe only: diverges in training —
             docs/evidence_stalebn_divergence.json.)
  affine   — per-channel scale+shift, no stats at all: the fusion FLOOR —
             the traffic a perfect conv+BN+ReLU fusion could not go below.
  nf       — nf_resnet50 (scaled weight standardization + SkipInit): the
             SHIPPED BN-free path; must sit on the affine floor.

Measured round 4 (v5e, b=128, 224²): bn 49.5 ms / 44.2 GB / 0.161
useful-MFU; stalebn 41.7 / 35.8 / 0.192; affine 40.9 / 35.9 / 0.195;
nf 41.2 / 35.2 / 0.194.

Usage: python scripts/probe_bn_traffic.py
"""

import json
import sys

sys.path.insert(0, ".")

import bench  # noqa: E402
import jax  # noqa: E402

B, IMG, STEPS = 128, 224, 40


def main():
    dev = jax.devices()[0]
    peak = bench.peak_flops_for(dev.device_kind)
    bw = bench.hbm_bw_for(dev.device_kind)
    base_ms = None
    for norm in ("bn", "stalebn", "affine", "nf"):
        if norm == "nf":
            step, v, o, batch, n_chips, gb = bench.build_step(
                "nf_resnet50", IMG, B)
        else:
            step, v, o, batch, n_chips, gb = bench.build_step(
                "resnet50", IMG, B, norm=norm)
        step_c, flops, nbytes = bench.compile_with_flops(step, v, o, batch)
        dt, _ = bench.measure(step_c, v, o, batch, steps=STEPS)
        ms = dt / STEPS * 1e3
        base_ms = base_ms or ms
        out = {
            "norm": norm,
            "step_ms": round(ms, 2),
            "img_per_s_per_chip": round(STEPS * gb / dt / n_chips, 1),
            "vs_bn_pct": round(100.0 * base_ms / ms, 1),
            "gbytes_per_step": round(nbytes / 1e9, 2) if nbytes else None,
            "tflops_per_step": round(flops / 1e12, 3) if flops else None,
            "t_hbm_ms": round(nbytes / bw * 1e3, 1) if nbytes and bw else None,
            "t_mxu_ms": round(flops / peak * 1e3, 1) if flops and peak else None,
            "mfu_useful": round(3 * 4.1e9 * B / (ms / 1e3) / peak, 3)
            if peak else None,
        }
        print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
