#!/usr/bin/env python
"""Finds the knee of a fixed-rate serving cell, once, on the chip.

    python benchmark/sweep_rate.py --workload <cell> --seed <n> \\
        --rates 4,8,12,... --seconds 15 --out benchmark/cells/<cell>.sweep.json

One process, one compile: the cell's server is built and warmed once, then
each rate gets one window of the cell's own traffic (same lengths, same
generator) and a drain.  The knee is the highest rate at which the backlog
(requests due and not finished) at the end of the window is no larger than
at its middle; the cell runs at four fifths of it.  The generator's
lateness is printed at each rate, so a starved generator is not read as a
fast server.  The found rate is written into ``benchmark/cells/<cell>.json``
by hand, with this script's output beside it."""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.harness import device, session             # noqa: E402
from benchmark.harness import traffic as _traffic          # noqa: E402


def main(argv=None, *, _allow_cpu=False, _sizes=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=1)
    parser.add_argument("--rates", required=True)
    parser.add_argument("--seconds", type=float, default=15.0)
    parser.add_argument("--out", required=True)
    args = parser.parse_args(argv)

    try:
        _, cell, devices = session.open_cell(args.workload, _sizes, _allow_cpu)
    except device.NoChip as e:
        print(f"sweep_rate: {e}", file=sys.stderr)
        return 2
    ctx = session.context(cell, devices, args.seed, args.seconds)
    family, driver = ctx.family, ctx.driver
    server = family.build_server(ctx)
    server.warm(cell["traffic"]["warm_prompts"])

    rows = []
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        reqs = _traffic.open_loop(cell["traffic"], args.seed + i,
                                  args.seconds, server.vocab, rate_per_s=rate)
        out = driver.serve_window(server, reqs, args.seconds, ctx,
                                  cell["traffic"]["drain_factor"])
        s = driver.summarize(out, cell["traffic"]["min_tail_samples"])
        row = {"rate_per_s": rate, "backlog_mid": out["backlog_mid"],
               "backlog_end": out["backlog_end"],
               "drained_after_s": out["wall_s"],
               "busy_slots_mean": (sum(out["busy_samples"])
                                   / max(len(out["busy_samples"]), 1)), **s}
        row["sustained"] = (row["failed"] == 0 and out["backlog_end"]
                            is not None and out["backlog_mid"] is not None
                            and out["backlog_end"] <= out["backlog_mid"])
        rows.append(row)
        ctx.say("sweep " + json.dumps(row))
    sustained = [r["rate_per_s"] for r in rows if r["sustained"]]
    result = {"workload": args.workload, "seed": args.seed,
              "seconds": args.seconds, "device": device.stamp(devices),
              "rows": rows, "knee_rate_per_s": max(sustained, default=None)}
    with open(args.out, "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps({"knee_rate_per_s": result["knee_rate_per_s"]}))
    server.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
