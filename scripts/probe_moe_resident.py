#!/usr/bin/env python
"""Probe (ISSUE 42): what does the held experts' product cost a served tick
between the routing and the layer's result — ``parallel/moe.py::
_held_experts_product`` — as the STAGED path has it (the rows gathered into
an ``(M, D)`` buffer, three ``moe_gmm`` calls, a gather a choice) against the
RESIDENT one (``moe_gmm_rows`` + ``moe_gmm_sum``: rows taken and summed
inside the products), at the three expert cells' tick sizes?

A program runs ``LAYERS`` expert layers, each with its own held experts'
weights (entry parameters: they lie in HBM, as the tick's do) and its own
routes, the residual stream carried from layer to layer as a temporary (as
the tick's is).  The routes are a tick's: ``busy`` of the slots carry a
token, which chooses 8 of 256 experts at random, of which 16 are held; the
other slots are routed to none.  The clock is the DEVICE's: the programs'
durations on the profiler's ``XLA Modules`` line of a traced lap, and the
operations' on its ops line, by name.  Both forms' results are compared on
the chip.

Chip only; prints one JSON object last.

    chiprun -- python scripts/probe_moe_resident.py [seed]
"""

import json
import os
import shutil
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmark.harness.trace_reduce import (  # noqa: E402
    find_xplane, short_name)
from chainermn_tpu.parallel import moe  # noqa: E402

#: cell -> (slots, busy slots, D, F, layers in the probe's program)
SHAPES = {"laguna": (24, 6, 2048, 512, 13), "kimi": (64, 11, 2304, 1024, 13),
          "deepseek": (64, 10, 7168, 2048, 4)}
EXPERTS, HELD, TOP_K = 256, 16, 8
REPS = 10


def layers(form: str, x, weights, idx, gates):
    """``LAYERS`` held-experts products behind one another."""
    resident = moe._rows_resident
    moe._rows_resident = resident if form == "resident" else (
        lambda t, d, a: False)
    try:
        for w, i, g in zip(weights, idx, gates):
            y, _ = moe._held_experts_product(x, w, i, g, 0, HELD, True,
                                             False)
            x = (x.astype(jnp.float32) + y).astype(x.dtype)
    finally:
        moe._rows_resident = resident
    return x


def timed(fn, *args):
    out = jax.block_until_ready(fn(*args))
    trace = os.path.join(ROOT, "benchmark", ".scratch", "probe_moe_resident")
    shutil.rmtree(trace, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace, profiler_options=options)
    for _ in range(REPS):
        out = fn(*args)
    jax.block_until_ready(out)
    jax.profiler.stop_trace()
    data = jax.profiler.ProfileData.from_file(find_xplane(trace))
    shutil.rmtree(trace, ignore_errors=True)
    runs, ops = [], {}
    for plane in data.planes:
        if plane.name != "/device:TPU:0":
            continue
        for line in plane.lines:
            if line.name == "XLA Modules":
                runs += [e.duration_ns for e in line.events]
            elif line.name == "XLA Ops":
                for e in line.events:
                    key = short_name(e.name).split(".")[0]
                    got = ops.setdefault(key, [0, 0])
                    got[0] += e.duration_ns
                    got[1] += 1
    assert len(runs) >= REPS, len(runs)
    return statistics.median(sorted(runs)[-REPS:]) / 1e3, ops


def probe(name, seed):
    t, busy, d, f, n_layers = SHAPES[name]
    rs = np.random.RandomState(seed)
    bf16 = jnp.bfloat16
    x = jnp.asarray(rs.randn(t, d), bf16)
    scale = lambda k: 1.0 / np.sqrt(k)
    key = jax.random.PRNGKey(seed)
    weights = []
    for i in range(n_layers):
        kg, ku, kd = jax.random.split(jax.random.fold_in(key, i), 3)
        weights.append({
            "w_gate": jax.random.normal(kg, (HELD, d, f), bf16) * scale(d),
            "w_up": jax.random.normal(ku, (HELD, d, f), bf16) * scale(d),
            "w_down": jax.random.normal(kd, (HELD, f, d), bf16) * scale(f)})
    live = np.isin(np.arange(t), rs.permutation(t)[:busy])
    idx = [np.where(live[:, None], np.stack(
        [rs.permutation(EXPERTS)[:TOP_K] for _ in range(t)]), EXPERTS)
        .astype(np.int32) for _ in range(n_layers)]
    gates = [jnp.asarray(rs.rand(t, TOP_K) * 0.3, jnp.float32)
             for _ in range(n_layers)]
    idx = [jnp.asarray(i) for i in idx]
    held = float(np.mean([(np.asarray(i) < HELD).sum() for i in idx]))
    out = {"shape": [t, d, f], "busy": busy, "layers": n_layers,
           "held_assignments_a_layer": held}
    results = {}
    for form in ("staged", "resident"):
        fn = jax.jit(lambda x, w, i, g, form=form: layers(form, x, w, i, g))
        results[form] = np.asarray(fn(x, weights, idx, gates), np.float32)
        us, ops = timed(fn, x, weights, idx, gates)
        out[form] = {
            "device_us_a_layer": round(us / n_layers, 2),
            "ops_us_a_layer": {
                k: [round(ns / 1e3 / REPS / n_layers, 2),
                    round(n / REPS / n_layers, 1)]
                for k, (ns, n) in sorted(ops.items(),
                                         key=lambda kv: -kv[1][0])[:14]}}
    diff = np.abs(results["staged"] - results["resident"])
    out["max_abs_diff"] = float(diff.max())
    out["max_abs"] = float(np.abs(results["staged"]).max())
    out["rows_differing_share"] = float((diff.max(-1) > 0).mean())
    return out


def main():
    if jax.default_backend() != "tpu":
        print(json.dumps({"error": "no TPU: the probe times device code"}))
        return 2
    seed = int(sys.argv[1]) if len(sys.argv) > 1 else 42
    result = {"probe": "moe_resident", "reps": REPS, "seed": seed,
              "device": jax.devices()[0].device_kind}
    for name in SHAPES:
        result[name] = probe(name, seed)
        print(json.dumps({name: result[name]}), flush=True)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
