"""Collective-communication accounting.

Every collective in the in-jit face (``chainermn_tpu.ops.collective``) and
the eager face (``communicators/``) reports through here: op name, axis,
payload bytes, wire dtype, and — when the call runs eagerly, outside a
trace — host-side latency.  The EQuARX-style question ("how many bytes
crossed the wire per step, through which collective?") becomes readable
from the training log and the exported Chrome trace instead of requiring
an external profiler.

Two call regimes, one ledger
----------------------------
* **Eager** (communicator methods, setup paths): each call records bytes
  AND host latency; a ``comm/<op>`` span brackets it on the timeline.
* **In-jit** (ops wrappers under ``jit``/``shard_map``): the wrapper runs
  at TRACE time, so a record lands once per compilation, not per
  execution.  The :meth:`CommAccountant.step` capture fixes the
  per-step view: collectives recorded while tracing a step program are
  remembered as that program's *profile*, and every later execution of
  the same program re-books the profile — the compiled program really
  does replay those collectives each step.  Latency inside jit is XLA's
  business (overlapped with compute); only bytes/calls are booked.

All recording is a no-op while tracing is disabled (one attribute read).

CAVEAT — enable BEFORE the first compile: in-jit records land at trace
time, so a program compiled while tracing was disabled carries no
bookings and no stored profile — its collectives stay invisible to the
ledger for as long as the jit cache serves it (re-jitting, e.g. after a
shape change, repairs this).  Enable tracing before building/warming the
step to get in-jit accounting; eager calls are always booked live.
"""

from __future__ import annotations

import threading
import time
from contextlib import contextmanager
from typing import Any, Dict, Optional

import numpy as np

from . import trace
# stdlib-only at module level (its lane/store imports are lazy), so this
# costs nothing and keeps the guarded hot path at ONE global read/call
from ..health import collective_guard as _collective_guard


def _as_dtype(dt) -> np.dtype:
    """np.dtype over names numpy alone doesn't know ('bfloat16')."""
    try:
        return np.dtype(dt)
    except TypeError:
        import jax.numpy as jnp
        return np.dtype(getattr(jnp, str(dt)))


def payload_info(tree) -> tuple:
    """``(nbytes, dtype_str, n_elements, in_jit)`` over a pytree's leaves.

    Works on concrete arrays and on tracers (via ``aval``) so the same
    accounting serves the eager and in-jit faces.  This function IS the
    ledger's byte convention — one logical payload per call, shape ×
    itemsize, independent of axis size — and the static cost model
    computes its per-equation bytes through it
    (``analysis.shardflow._aval_nbytes`` feeds avals in), so the two
    sides of the reconciliation can never diverge on the formula.
    """
    import jax

    nbytes = 0
    n_elems = 0
    dtype = None
    in_jit = False
    for leaf in jax.tree_util.tree_leaves(tree):
        if isinstance(leaf, jax.core.Tracer):
            in_jit = True
        aval = getattr(leaf, "aval", None)
        shape = getattr(aval, "shape", None)
        if shape is None:
            shape = getattr(leaf, "shape", ())
        dt = getattr(aval, "dtype", None)
        if dt is None:
            dt = getattr(leaf, "dtype", None)
        dt = np.dtype(dt) if dt is not None else np.dtype(np.float32)
        n = int(np.prod(shape)) if shape else 1
        n_elems += n
        nbytes += n * dt.itemsize
        dtype = dtype or str(dt)
    return nbytes, dtype or "float32", n_elems, in_jit


class CommAccountant:
    """Ledger of collective calls: cumulative totals, per-program trace
    profiles, and a per-step report."""

    def __init__(self):
        self._lock = threading.Lock()
        self.totals: Dict[str, Dict[str, float]] = {}
        self._programs: Dict[Any, Dict[str, Dict[str, float]]] = {}
        self._step_accum: Optional[Dict[str, Dict[str, float]]] = None
        # in-jit-only rows of the current step — ONLY these become the
        # program profile (an eager collective recorded in the same
        # bracket is live every step and must not be replayed on top of
        # itself)
        self._step_jit: Optional[Dict[str, Dict[str, float]]] = None
        self._step_traced = False
        self.last_step_report: Optional[Dict[str, Any]] = None

    def reset(self) -> None:
        with self._lock:
            self.totals = {}
            self._programs = {}
            self._step_accum = None
            self._step_jit = None
            self._step_traced = False
            self.last_step_report = None

    # ---- recording ----
    def record(self, op: str, axis, nbytes: int, dtype: str,
               in_jit: bool, latency_s: Optional[float] = None,
               noted: bool = False) -> None:
        """``noted=True`` marks a DECLARED collective (booked via
        :func:`note` — the host's knowledge of traffic no wrapper sees,
        e.g. the autodiff-inserted gradient psum).  Noted bytes
        accumulate in a separate ``noted_bytes`` field on the row, so a
        key shared between wrapped calls and notes (rows aggregate per
        ``op@axis``) still splits exactly — the shard-flow
        reconciliation holds wrapped bytes to the traced equations and
        noted bytes to the entry point's declaration."""
        axis_key = "+".join(axis) if isinstance(axis, (tuple, list)) else str(axis)
        key = f"{op}@{axis_key}"
        with self._lock:
            row = self.totals.setdefault(
                key, {"calls": 0, "bytes": 0, "host_time_s": 0.0})
            row["calls"] += 1
            row["bytes"] += int(nbytes)
            if latency_s is not None:
                row["host_time_s"] += float(latency_s)
            if noted:
                row["noted_bytes"] = row.get("noted_bytes", 0) + int(nbytes)
            # a key can aggregate calls of several dtypes (fp32 loss +
            # int32 counters through the same psum@axis) — keep the set
            dts = row.setdefault("dtypes", [])
            if dtype not in dts:
                dts.append(dtype)
            if self._step_accum is not None:
                srow = self._step_accum.setdefault(
                    key, {"calls": 0, "bytes": 0, "host_time_s": 0.0})
                srow["calls"] += 1
                srow["bytes"] += int(nbytes)
                if latency_s is not None:
                    srow["host_time_s"] += float(latency_s)
                if noted:
                    srow["noted_bytes"] = (srow.get("noted_bytes", 0)
                                           + int(nbytes))
                if in_jit:
                    self._step_traced = True
                    jrow = self._step_jit.setdefault(
                        key, {"calls": 0, "bytes": 0, "host_time_s": 0.0})
                    jrow["calls"] += 1
                    jrow["bytes"] += int(nbytes)
                    if noted:
                        jrow["noted_bytes"] = (jrow.get("noted_bytes", 0)
                                               + int(nbytes))
        tr = trace.get_tracer()
        tr.add_counter(f"comm/{op}/bytes", nbytes)
        tr.add_counter(f"comm/{op}/calls", 1)
        # flight-recorder tee: one ring event per accounting delta, so a
        # postmortem shows the last collectives the process completed
        from . import flight as _flight
        _flight.note("comm", op=op, axis=axis_key, bytes=int(nbytes),
                     dtype=dtype, in_jit=bool(in_jit))

    # ---- per-step capture ----
    @contextmanager
    def step(self, program_key: Any = "step"):
        """Bracket one training step.  Collectives recorded inside are
        the step's comm work; in-jit ops traced during a (re)compile are
        stored as the program's profile and re-booked on cache-hit
        executions.  ``last_step_report`` holds the finished report
        afterwards.

        CONTRACT: one ``program_key`` ↔ one jitted program (the
        ``StandardUpdater`` bracket wraps exactly its ``step_fn`` call).
        A retrace REPLACES the stored profile — correct for shape-change
        recompiles of the same program.  If a bracket spans several
        independently-compiled jits, give each its own bracket/key;
        under one key, whichever traced last would win and cache-hit
        replays would misattribute the others."""
        if not trace.get_tracer().enabled:
            # no report for an untraced step — and clear any earlier one
            # so consumers (StepBreakdownReport) don't republish frozen
            # values forever after tracing is disabled mid-run (locked:
            # the traced finalize writes it under _lock on another
            # thread's step bracket)
            with self._lock:
                self.last_step_report = None
            yield None
            return
        with self._lock:
            self._step_accum = {}
            self._step_jit = {}
            self._step_traced = False
        try:
            yield self
        finally:
            replayed = {}
            with self._lock:
                accum = self._step_accum or {}
                jit_rows = self._step_jit or {}
                self._step_accum = None
                self._step_jit = None
                if self._step_traced:
                    # a compile happened: remember the program's
                    # structural (in-jit ONLY) collectives for cache-hit
                    # steps — eager rows recorded in the same bracket are
                    # live every step and must not be replayed too
                    self._programs[program_key] = {
                        k: dict(v) for k, v in jit_rows.items()}
                else:
                    # cache hit: the compiled program still ran its
                    # collectives — book the remembered profile (without
                    # host latency, which XLA overlaps internally) into
                    # BOTH the step report and the cumulative ledger, so
                    # totals reflect executed collectives, not compiles.
                    replayed = self._programs.get(program_key, {})
                    for k, v in replayed.items():
                        for dest in (accum, self.totals):
                            row = dest.setdefault(
                                k, {"calls": 0, "bytes": 0,
                                    "host_time_s": 0.0})
                            row["calls"] += v["calls"]
                            row["bytes"] += v["bytes"]
                            if v.get("noted_bytes"):
                                row["noted_bytes"] = (
                                    row.get("noted_bytes", 0)
                                    + v["noted_bytes"])
                self.last_step_report = self._summarize(accum)
            # mirror the replayed bookings into the trace counter tracks
            # (outside our lock — the tracer takes its own), so the
            # exported comm/<op> counters advance every step, not just on
            # the compile step
            tr = trace.get_tracer()
            for k, v in replayed.items():
                op = k.split("@", 1)[0]
                tr.add_counter(f"comm/{op}/bytes", v["bytes"])
                tr.add_counter(f"comm/{op}/calls", v["calls"])

    @staticmethod
    def _summarize(accum: Dict[str, Dict[str, float]]) -> Dict[str, Any]:
        def snap(v):
            # deep enough that the report is a true snapshot — the
            # 'dtypes' list keeps growing in the live row
            out = dict(v)
            if "dtypes" in out:
                out["dtypes"] = list(out["dtypes"])
            return out

        return {
            "per_op": {k: snap(v) for k, v in accum.items()},
            "bytes": int(sum(v["bytes"] for v in accum.values())),
            "calls": int(sum(v["calls"] for v in accum.values())),
            "host_time_s": float(sum(v.get("host_time_s", 0.0)
                                     for v in accum.values())),
        }

    def report(self) -> Dict[str, Any]:
        """Cumulative per-op totals since enable/reset."""
        with self._lock:
            return self._summarize(self.totals)


_ACCOUNTANT = CommAccountant()


def get_accountant() -> CommAccountant:
    return _ACCOUNTANT


def note(op: str, axis, tree) -> None:
    """Book a collective the host cannot wrap — e.g. the psum that
    autodiff inserts for replicated-param cotangents on the default
    train-step path.  The caller knows the op happens and what it moves
    (the pytree's size); this records that knowledge so the flagship
    path's gradient traffic appears in the ledger instead of reading as
    a 4-byte loss pmean.  In-jit-ness is inferred from the leaves, so a
    note recorded at trace time replays per step like any wrapped
    collective."""
    if not trace.get_tracer().enabled:
        return
    nbytes, dtype, _, in_jit = payload_info(tree)
    _ACCOUNTANT.record(op, axis, nbytes, dtype, in_jit=in_jit, noted=True)


def collective(op: str, axis, x, thunk, wire_dtype=None):
    """Run ``thunk()`` (the actual ``jax.lax`` collective) under
    accounting.  The in-jit face's single entry point: bytes/dtype come
    from ``x``'s leaves; host latency is recorded only for eager calls;
    ``wire_dtype`` overrides the byte count for compressed-wire ops
    (quantized ring: int8 payload regardless of ``x.dtype``).

    When a :class:`~chainermn_tpu.health.CollectiveGuard` is installed
    (``health.set_collective_guard`` — the training gang's collective
    watchdog, ISSUE 13), every EAGER call is bracketed by
    ``guard.enter/exit``: a call that outlives the guard window is
    aborted loudly with the missing rank(s) NAMED from the lease table
    instead of hanging anonymously.  Trace-time (in-jit) calls complete
    at trace and are not guarded; guarding works with tracing disabled.
    """
    tr = trace.get_tracer()
    guard = _collective_guard()
    if not tr.enabled:
        if guard is None:
            return thunk()
        tok = guard.enter(op)
        try:
            return thunk()
        finally:
            guard.exit(tok)
    nbytes, dtype, n_elems, in_jit = payload_info(x)
    if wire_dtype is not None:
        wd = _as_dtype(wire_dtype)
        dtype = str(wd)
        nbytes = n_elems * wd.itemsize
    if in_jit:
        out = thunk()
        _ACCOUNTANT.record(op, axis, nbytes, dtype, in_jit=True)
        return out
    tok = guard.enter(op) if guard is not None else None
    t0 = time.perf_counter()
    try:
        with tr.span(f"comm/{op}", cat="comm", axis=str(axis), bytes=nbytes):
            out = thunk()
    finally:
        if tok is not None:
            guard.exit(tok)
    _ACCOUNTANT.record(op, axis, nbytes, dtype, in_jit=False,
                       latency_s=time.perf_counter() - t0)
    return out


_EAGER_DEPTH = threading.local()


def accounted_method(op: str):
    """Decorator for eager communicator collectives (``comm.allreduce``
    and friends): bytes from the rank-major stack, host-side dispatch
    latency, a ``comm/<op>`` span on the timeline.  Applied
    automatically to every backend by ``CommunicatorBase
    .__init_subclass__`` — naive, xla, and any future subclass.

    Re-entrancy guarded: only the OUTERMOST accounted call records, so a
    subclass override delegating to ``super().allreduce(...)`` (both
    levels wrapped by ``__init_subclass__``) books one logical
    collective once, and helpers implemented in terms of other wrapped
    collectives (``multi_node_mean_grad`` → ``allreduce``) book under
    the caller's name rather than double.

    The installed :class:`~chainermn_tpu.health.CollectiveGuard` (if
    any) brackets the OUTERMOST call too — the communicator hot path's
    bounded-timeout watchdog (ISSUE 13), active even with tracing off.
    """
    import functools

    def deco(fn):
        @functools.wraps(fn)
        def wrapper(self, x, *args, **kwargs):
            tr = trace.get_tracer()
            nested = getattr(_EAGER_DEPTH, "d", 0)
            guard = None if nested else _collective_guard()
            tok = guard.enter(op) if guard is not None else None
            try:
                if not tr.enabled or nested:
                    if guard is None:
                        return fn(self, x, *args, **kwargs)
                    # outermost-with-guard, tracing off: still mark the
                    # depth so a delegating helper (multi_node_mean_grad
                    # -> allreduce) cannot double-enter the guard
                    _EAGER_DEPTH.d = 1
                    try:
                        return fn(self, x, *args, **kwargs)
                    finally:
                        _EAGER_DEPTH.d = 0
                nbytes, dtype, _, _ = payload_info(x)
                axis = getattr(self, "axis_name", "world")
                _EAGER_DEPTH.d = 1
                t0 = time.perf_counter()
                try:
                    with tr.span(f"comm/{op}", cat="comm", axis=str(axis),
                                 bytes=nbytes):
                        out = fn(self, x, *args, **kwargs)
                finally:
                    _EAGER_DEPTH.d = 0
                _ACCOUNTANT.record(op, axis, nbytes, dtype, in_jit=False,
                                   latency_s=time.perf_counter() - t0)
                return out
            finally:
                if tok is not None:
                    guard.exit(tok)
        wrapper._obs_wrapped = True
        return wrapper
    return deco


#: Back-compat alias (the helper predates its public face).
_payload_info = payload_info


# --------------------------------------------------------------------------
# schedule-execution counters + the /statusz calibration provider
# (ISSUE 20: the collective truth plane's always-on aggregate face)
# --------------------------------------------------------------------------

_SCHED_LOCK = threading.Lock()
_SCHED_EXEC: Dict[str, float] = {}
_CAL_PROVIDER_REGISTERED = False


def _register_calibration_provider() -> None:
    global _CAL_PROVIDER_REGISTERED
    if _CAL_PROVIDER_REGISTERED:
        return
    from . import flight as _flight
    _flight.register_provider("calibration", calibration_snapshot)
    _CAL_PROVIDER_REGISTERED = True


def record_schedule_exec(records) -> None:
    """Book one profiled schedule execution's records into the
    ``schedule_exec/*`` counters (/metricsz face) and tracer counters
    (Chrome trace face).  Called by ``reshard._emit_schedule_exec``;
    first booking registers the /statusz ``calibration`` provider."""
    if not records:
        return
    with _SCHED_LOCK:
        for r in records:
            link = r.get("link", "?")
            _SCHED_EXEC[f"schedule_exec/{link}/ops"] = \
                _SCHED_EXEC.get(f"schedule_exec/{link}/ops", 0.0) + 1
            _SCHED_EXEC[f"schedule_exec/{link}/bytes"] = \
                _SCHED_EXEC.get(f"schedule_exec/{link}/bytes", 0.0) \
                + float(r.get("bytes", 0))
            _SCHED_EXEC[f"schedule_exec/{link}/wall_us"] = \
                _SCHED_EXEC.get(f"schedule_exec/{link}/wall_us", 0.0) \
                + float(r.get("wall_us", 0.0))
        _SCHED_EXEC["schedule_exec/records"] = \
            _SCHED_EXEC.get("schedule_exec/records", 0.0) + len(records)
        _SCHED_EXEC["schedule_exec/executions"] = \
            _SCHED_EXEC.get("schedule_exec/executions", 0.0) + 1
    tr = trace.get_tracer()
    if tr.enabled:
        tr.add_counter("schedule_exec/records", float(len(records)))
    _register_calibration_provider()


def schedule_exec_gauges() -> Dict[str, float]:
    """Snapshot of the ``schedule_exec/*`` counters (merged into
    /metricsz the same way the flight drop counts are)."""
    with _SCHED_LOCK:
        return dict(_SCHED_EXEC)


def calibration_snapshot() -> Dict[str, Any]:
    """The /statusz ``calibration`` provider: the live counters.  No
    calibration artifact is installed in a process (``calibration`` stays
    ``None``); a fit is applied per call, ``price_schedule(calibration=)``."""
    return {"counters": schedule_exec_gauges(), "calibration": None}


def reset_schedule_exec() -> None:
    """Test hook: clear the counters."""
    with _SCHED_LOCK:
        _SCHED_EXEC.clear()
