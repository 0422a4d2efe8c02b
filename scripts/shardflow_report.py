#!/usr/bin/env python
"""Shard-flow report gate — CI face of ``chainermn_tpu.analysis.shardflow``.

Per registered entry point: the static collective cost model (ledger-
convention payload bytes + physical ring wire/message estimates), the
peak-live-memory-per-replica estimate, the replication report across the
entry's data axis, and the static↔dynamic reconciliation verdict against
the PR 1 runtime comm ledger.

Same exit-code contract as
``scripts/lint_spmd.py``: 0 = clean (modulo the checked-in
``.shardflow-baseline.json``), 1 = findings, 2 = inputs unusable.

Usage::

    python scripts/shardflow_report.py                      # all entry points
    python scripts/shardflow_report.py --entry train.step   # one entry point
    python scripts/shardflow_report.py --json               # machine output
    python scripts/shardflow_report.py --fix-baseline       # accept findings

Unlike ``lint_spmd.py --no-jaxpr`` this runner always needs jax: the
reconciliation EXECUTES each entry point under the accounting layer —
that is the whole point (the cost model can never silently rot).
"""

import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None):
    sys.path.insert(0, _REPO)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from chainermn_tpu.analysis.shardflow import main as shardflow_main
    return shardflow_main(list(sys.argv[1:] if argv is None else argv))


if __name__ == "__main__":
    sys.exit(main())
