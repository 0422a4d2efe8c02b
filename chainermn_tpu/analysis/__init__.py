"""chainermn_tpu.analysis — SPMD-aware static analyzer for JAX code.

The MPI heritage of this codebase makes collective *ordering and symmetry*
a correctness invariant: a collective executed under rank-dependent control
flow deadlocks the gang (SURVEY.md §3.2), a reused PRNG key silently draws
identical samples (the PR 3 rng trap), and a zero-copy ``asarray`` of a
host buffer that is later mutated in place races async dispatch (the PR 3
serving pos-vector bug).  This package catches that family mechanically.

Two complementary engines:

* **AST engine** (``ast_engine``) — pure stdlib ``ast``; no JAX import
  required, so it runs on any box that can read Python.  Rules:
  collective-deadlock, prng-constant-key, prng-key-reuse, host-alias-race,
  traced-control-flow, inplace-jit-mutation.
* **jaxpr engine** (``jaxpr_engine``) — traces *registered entry points*
  (``entrypoints.py``, tiny shapes, CPU backend) and checks the extracted
  collective sequence for axis names absent from the enclosing mesh spec
  (unbound-axis) and for recompilation hazards (recompile-hazard, with an
  explicit allowlist for the per-prompt-length prefill programs).

A third pass builds on the jaxpr engine: the **shard-flow analyzer**
(``shardflow``) propagates sharding through every registered entry point
to produce a replication report (what is fully materialized per replica
— the ZeRO-1 target), a static collective cost model (wire bytes +
message counts), and a peak-live-memory-per-replica estimate — and
RECONCILES the static predictions against the runtime comm ledger by
executing each entry point under the PR 1 accounting layer (exact byte
equality; the cost model can never silently rot).  Runner:
``scripts/shardflow_report.py`` / ``python -m
chainermn_tpu.analysis.shardflow``; baseline:
``.shardflow-baseline.json``.

The collective surface is *derived*, not hardcoded: ``registry.py`` parses
``ops/collective.py`` and ``communicators/base.py`` so new collectives are
linted the day they land.

Runners: ``python -m chainermn_tpu.analysis <paths>`` and
``scripts/lint_spmd.py`` (exit 0 clean / 1 findings / 2 unusable).
Accepted findings live in the
checked-in baseline (``.spmd-lint-baseline.json``); one-off exceptions use
``# spmd-lint: disable=<rule>`` inline.  See docs/ANALYSIS.md.

This module must stay importable WITHOUT jax: only stdlib + relative
imports at top level (``jaxpr_engine`` imports jax lazily).
"""

from .findings import (  # noqa: F401
    Baseline,
    Finding,
    SEVERITIES,
    load_baseline,
)
from .registry import CollectiveRegistry, default_registry  # noqa: F401
from .ast_engine import (  # noqa: F401
    AST_RULES,
    analyze_file,
    analyze_paths,
    analyze_source,
)
from .shardflow import (  # noqa: F401  (stdlib-only at import time)
    SHARDFLOW_RULES,
    ShardflowReport,
)
from .concurrency import CONCURRENCY_RULES  # noqa: F401  (stdlib-only)
from .protocol import ALL_MODELS as PROTOCOL_MODELS  # noqa: F401
from .schedule import (  # noqa: F401  (stdlib+numpy only)
    CALIBRATION_SCHEMA,
    GENERATORS as SCHEDULE_GENERATORS,
    Schedule,
    Topology,
)
from .schedule_check import (  # noqa: F401
    FLEET_PAIRS,
    SCHEDULE_EXEC_SCHEMA,
    SEEDED_FAULTS,
    ScheduleExecProfile,
    verify_schedule,
)
from .calibrate import (  # noqa: F401  (stdlib+numpy only)
    drift_report,
    fit_calibration,
    load_calibration,
    schedule_critical_path,
)

__all__ = [
    "AST_RULES",
    "Baseline",
    "CALIBRATION_SCHEMA",
    "CONCURRENCY_RULES",
    "CollectiveRegistry",
    "FLEET_PAIRS",
    "Finding",
    "PROTOCOL_MODELS",
    "SCHEDULE_EXEC_SCHEMA",
    "SCHEDULE_GENERATORS",
    "SEEDED_FAULTS",
    "SEVERITIES",
    "SHARDFLOW_RULES",
    "Schedule",
    "ScheduleExecProfile",
    "ShardflowReport",
    "Topology",
    "analyze_file",
    "analyze_paths",
    "analyze_source",
    "default_registry",
    "drift_report",
    "fit_calibration",
    "load_baseline",
    "load_calibration",
    "schedule_critical_path",
    "verify_schedule",
]
