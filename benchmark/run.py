#!/usr/bin/env python
"""The benchmark's command: one process, one cell, one run.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Knows no cell, configuration, family, driver or metric by name: the cell's
files are found from BENCHMARK.json (``benchmark/harness/loader.py`` says
how).  Prints free lines, each stamped with the device, and the contract's
one JSON object last.  Without a TPU, or with fewer chips than the cell
asks for, it exits non-zero and prints no result: there is no CPU path
(``main(_allow_cpu=..., _sizes=...)`` is the tests' rehearsal hook, not a
flag and not an environment variable)."""

import time

_T0 = time.perf_counter()      # process start, as near as Python can see it

import argparse                # noqa: E402
import json                    # noqa: E402
import os                      # noqa: E402
import sys                     # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.harness import device, loader, session           # noqa: E402
from benchmark.harness import spans as _spans, trace_reduce      # noqa: E402


def main(argv=None, *, _allow_cpu=False, _sizes=None, _t0=None) -> int:
    t0 = _T0 if _t0 is None else _t0
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    try:
        man, cell, devices = session.open_cell(args.workload, _sizes,
                                               _allow_cpu)
    except device.NoChip as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    compiles = _spans.CompileCounter()
    ctx = session.context(cell, devices, args.seed, args.seconds,
                          trace=bool(args.trace), t0=t0)
    ctx.say(f"cell {cell['name']} seed {args.seed} seconds {args.seconds} "
            f"trace {args.trace}; compile cache {cell['compile_cache_dir']}")

    marks = {}

    def open_window():
        marks["open"] = time.perf_counter()
        marks["reference_before"] = ctx.reference_s
        compiles.open = True
        return marks["open"]

    def close_window():
        compiles.open = False
        marks["close"] = time.perf_counter()
        return marks["close"] - marks["open"]

    ctx.open_window, ctx.close_window = open_window, close_window
    res = ctx.driver.run(ctx)

    checks = res["checks"] + [{
        "name": "compiles_in_window", "value": float(compiles.n),
        "limit": 0.0, "ok": compiles.n == 0}]
    for c in checks:
        ctx.say(f"check {c['name']}: {c['value']!r} (limit {c['limit']!r}) "
                f"{'ok' if c['ok'] else 'NOT OK'}")
    correct = all(c["ok"] for c in checks)

    setup_s = marks["open"] - t0 - marks["reference_before"]
    values = dict(res["end_to_end"], setup_s=setup_s)
    stamp = device.stamp(devices)
    stamp["memory_peak_bytes"] = device.memory_peak_bytes(devices)
    out = {"correct": correct, "attempted": res["attempted"],
           "failed": res["failed"]}

    if not args.trace:
        due = loader.metrics_of(man, "end_to_end", cell["name"])
        out["metrics"] = {m["name"]: {"value": values[m["name"]],
                                      "unit": m["unit"]}
                          for m in due if m["name"] in values}
    else:
        reduced = trace_reduce.reduce_file(
            ctx.tracer.directory, _spans.GAP_SPANS, chips=cell["chips"])
        run = dict(res["run"], chips=cell["chips"], values=values,
                   memory_peak_bytes=stamp["memory_peak_bytes"],
                   peaks=device.PEAKS.get(stamp["kind"], {}))
        due = loader.metrics_of(man, "per_layer", cell["name"],
                                reported=set(values))
        out["metrics"] = {}
        for m in due:
            value = loader.module("layer_metrics", m["name"]).read(
                reduced, ctx.spans.records, run)
            if value is not None:
                out["metrics"][m["name"]] = {"value": value,
                                             "unit": m["unit"]}
        stamp["busy_s"] = reduced["busy_s"]
        stamp["window_s"] = reduced["window_s"]
        out["breakdown"] = {"device_ops": reduced["device_ops"],
                            "idle_gaps": reduced["idle_gaps"]}
    out["device"] = stamp
    ctx.say(f"setup_s {setup_s:.2f} (reference, not counted: "
            f"{ctx.reference_s:.2f} s); compile cache hits "
            f"{compiles.hits} misses {compiles.misses}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
