#!/usr/bin/env python
"""Runs a cell's control on the chip, at the cell's own size.

    python benchmark/control.py --workload <cell> --seeds 1,2,3 [--seconds 15]

The control is the plain reference put in the program's place and computed
in the nearest precision below the one the configuration states
(``families/<family>.py::CONTROL_PRECISION``); ``drivers/<driver>.py::
control`` says what is compared.  Every seed's control has to FAIL a limit
of ``reference/<family>.py::LIMITS``; this prints each number beside its
limit and exits 1 if a control passed.  The benchmark's own runs never run
it; ``benchmark/tests`` keeps it at a size a test run can hold."""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.harness import device, session              # noqa: E402


def main(argv=None, *, _allow_cpu=False, _sizes=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--seconds", type=float, default=15.0)
    args = parser.parse_args(argv)
    try:
        _, cell, devices = session.open_cell(args.workload, _sizes, _allow_cpu)
    except device.NoChip as e:
        print(f"control: {e}", file=sys.stderr)
        return 2
    passed = 0
    for seed in (int(s) for s in args.seeds.split(",")):
        ctx = session.context(cell, devices, seed, args.seconds)
        rows = ctx.driver.control(ctx)
        control = [r for r in rows if not r["name"].startswith("program.")]
        failed = [r["name"] for r in control if not r["ok"]]
        passed += not failed
        ctx.say("control " + json.dumps({
            "seed": seed, "rows": rows, "control_failed_on": failed}))
    print(json.dumps({"controls": len(args.seeds.split(",")),
                      "controls_that_passed": passed}))
    return 1 if passed else 0


if __name__ == "__main__":
    sys.exit(main())
