#!/usr/bin/env python
"""Finds the knee of a fixed-rate serving cell, once, on the chip.

    python benchmark/sweep_rate.py --workload <cell> --seed <n> \\
        --rates 4,8,12,... --seconds 15 --out benchmark/cells/<cell>.sweep.json

One process, one compile: the cell's server is built and warmed once, then
each rate gets one window of the cell's own traffic (same lengths, same
generator) and a drain.  A rate is sustained where no request failed and the
backlog (requests due and not finished) at the end of the window has not
grown past its middle's by more than the schedule's own fluctuation
(:func:`sustained`); the knee is the highest such rate and the cell runs at
four fifths of it.  The generator's lateness is printed at each rate, so a
starved generator is not read as a fast server.  The found rate is written
into ``benchmark/cells/<cell>.json`` by hand, with this script's output
beside it."""

import argparse
import json
import math
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.harness import device, session             # noqa: E402
from benchmark.harness import traffic as _traffic          # noqa: E402


def sustained(failed: int, backlog_mid, backlog_end) -> bool:
    """No request failed, and the backlog at the window's end is within two
    standard deviations of a Poisson count above its middle's.  Two instants
    of a steady queue with ``n`` requests in flight differ by about
    ``sqrt(n)``: the plain ``end <= mid`` read that fluctuation as growth
    (28 -> 37 and 50 -> 61 at rates that drained within seconds, PERF.md,
    Findings PR 40), where a queue that grows reads 251 -> 357."""
    if failed or backlog_mid is None or backlog_end is None:
        return False
    return backlog_end <= backlog_mid + 2.0 * math.sqrt(backlog_mid + 1)


def deepest_queue(recs) -> int:
    """The most requests that waited in the engine's queue at once, from the
    handles' own ``submitted`` and ``prefill_start`` stamps (a request that
    was never admitted waits to the end)."""
    events = []
    for r in recs:
        stamps = r["handle"].timestamps if r["handle"] is not None else {}
        if "submitted" in stamps:
            events.append((stamps["submitted"], 1))
            if "prefill_start" in stamps:
                events.append((stamps["prefill_start"], -1))
    depth = deepest = 0
    for _, step in sorted(events):
        depth += step
        deepest = max(deepest, depth)
    return deepest


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
               "queue_depth_max": deepest_queue(out["recs"]),
               "busy_slots_mean": (sum(out["busy_samples"])
                                   / max(len(out["busy_samples"]), 1)), **s}
        row["sustained"] = sustained(row["failed"], out["backlog_mid"],
                                     out["backlog_end"])
        rows.append(row)
        ctx.say("sweep " + json.dumps(row))
    held = [r["rate_per_s"] for r in rows if r["sustained"]]
    result = {"workload": args.workload, "seed": args.seed,
              "seconds": args.seconds, "device": device.stamp(devices),
              "rows": rows, "knee_rate_per_s": max(held, default=None)}
    with open(args.out, "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps({"knee_rate_per_s": result["knee_rate_per_s"]}))
    server.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
