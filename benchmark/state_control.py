#!/usr/bin/env python
"""Runs a cell's broken-STATE controls on the chip, at the cell's own size.

    python benchmark/state_control.py --workload <cell> --seed 7 [--seconds 20]
        [--faults a,b]

Beside ``control.py`` (the reference in a lower precision).  A model whose
layers keep a recurrent state can be served wrongly in ways a lower
precision does not resemble: a tick that forgets to decay the state, a
prefill that hands over the state of the PADDED prompt.  The family names
such faults (``families/<family>.py::STATE_FAULTS``: each breaks the
program in place and returns its undo); for each, this serves a short
window at the cell's own load with the broken program and makes the
comparison that decides ``correct``.  Every fault has to FAIL a limit of
``reference/<family>.py::LIMITS``; this prints each number beside its limit
and exits 1 if a broken program passed.  The benchmark's own runs never
run it; ``benchmark/tests`` keeps it at a size a test run can hold."""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.harness import device, session, traffic         # noqa: E402


def serve_broken(ctx, fault):
    """The comparison's rows for a window served with ``fault`` applied."""
    fam, tr = ctx.family, ctx.traffic
    mend = fam.STATE_FAULTS[fault]()
    try:
        server = fam.build_server(ctx)
        reqs = traffic.open_loop(tr, ctx.seed, ctx.seconds, server.vocab)
        server.warm(tr["warm_prompts"])
        out = ctx.driver.serve_window(server, reqs, ctx.seconds, ctx,
                                      tr["drain_factor"])
        sample = fam.served_sample(ctx, out["recs"], reqs,
                                   tr["check_requests"])
        server.close()
        del server
    finally:
        mend()
    return fam.serve_compare(ctx, sample)


def main(argv=None, *, _allow_cpu=False, _sizes=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, default=20.0)
    parser.add_argument("--faults", default="")
    args = parser.parse_args(argv)
    try:
        _, cell, devices = session.open_cell(args.workload, _sizes, _allow_cpu)
    except device.NoChip as e:
        print(f"state_control: {e}", file=sys.stderr)
        return 2
    ctx = session.context(cell, devices, args.seed, args.seconds)
    faults = [f for f in args.faults.split(",") if f] or sorted(
        ctx.family.STATE_FAULTS)
    passed = 0
    for fault in faults:
        rows = serve_broken(ctx, fault)
        failed = [r["name"] for r in rows if not r["ok"]]
        passed += not failed
        ctx.say("state_control " + json.dumps({
            "seed": args.seed, "fault": fault, "rows": rows,
            "failed_on": failed}))
    print(json.dumps({"faults": len(faults), "faults_that_passed": passed}))
    return 1 if passed else 0


if __name__ == "__main__":
    sys.exit(main())
