"""What the PROGRAM wrote into the traced slice: its own host spans
(``serving/step`` and its phases, which ``chainermn_tpu``'s tracer enters
as ``jax.profiler.TraceAnnotation``s) and its named jitted programs on the
device's ``XLA Modules`` line.

``layer_metrics/<name>.py::read(trace, spans, run)`` is handed only the
REDUCED trace, so this helper re-reads the slice's ``.xplane.pb`` itself,
once per process: the newest ``*.xplane.pb`` under ``benchmark/.trace/``
that was written since this process started and whose ``traced_slice``
is the one the reduced trace was made from.  Anything else — no trace,
another run's trace, a trace without the slice — gives ``None`` and the
readers leave their metric out.  A later ``benchmark`` issue should pass
the trace's path in ``run`` (``run.py`` knows it) and this search goes.

Nothing of ``trace_reduce`` is copied: its ``read_events`` gives the
device's operations and the host's python-thread spans, its interval
arithmetic the rest; the ``XLA Modules`` line is the one thing read here
besides.
"""

import glob
import os
import re

from benchmark.harness import device, loader
from benchmark.harness.trace_reduce import (
    DEVICE_PLANE, SLICE, clip, read_events, subtract, total, union)

#: the line of a device plane with one event per executed program,
#: named ``jit_<function>(<fingerprint>)``
MODULES_LINE = "XLA Modules"
MODULE_NAME = re.compile(r"^jit_(.+?)\(\d+\)$")
#: the root span of one engine iteration and, by name, the bucket each of
#: its top-level children belongs to (docs/OBSERVABILITY.md's table)
STEP = "serving/step"
BUCKETS = {
    "admit": ("serving/admit", "serving/expire"),
    "prefill_host": ("serving/prefill", "serving/prefix_copy",
                     "serving/spill_restore"),
    "tick_host": ("serving/tick",),
    "emit": ("serving/emit",),
    "bookkeeping": ("serving/bookkeeping",),
}
TRACE_ROOT = os.path.join(loader.BENCH, ".trace")

_CACHE = {}


def _process_started() -> float:
    try:
        import psutil
        return psutil.Process().create_time()
    except Exception:          # no psutil: the slice's identity still guards
        return 0.0


def newest_xplane(root: str = None, since: float = None):
    """``(path, cell name)`` of the newest ``.xplane.pb`` under ``root``
    (``benchmark/.trace/<cell>/plugins/profile/<time>/``) modified at or
    after ``since`` (default: when this process started), or ``None``."""
    root = TRACE_ROOT if root is None else root
    since = _process_started() if since is None else since
    found = [(os.path.getmtime(p), p) for p in glob.glob(os.path.join(
        root, "*", "plugins", "profile", "*", "*.xplane.pb"))]
    found = [(t, p) for t, p in found if t >= since - 1.0]
    if not found:
        return None
    path = max(found)[1]
    return path, os.path.relpath(path, root).split(os.sep)[0]


def read_modules(path: str) -> dict:
    """``{chip: [(program, start_ns, end_ns)]}`` from the ``XLA Modules``
    line, ``program`` without ``jit_`` and the fingerprint."""
    import jax

    out = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        for line in plane.lines:
            if line.name != MODULES_LINE:
                continue
            for e in line.events:
                name = MODULE_NAME.match(e.name)
                out.setdefault(int(m.group(1)), []).append((
                    name.group(1) if name else e.name, e.start_ns,
                    e.start_ns + e.duration_ns))
    return out


def view(events: dict, modules: dict) -> dict:
    """What the readers need of one trace: the slice's bounds, chip 0's
    idle gaps inside it, the program's host spans and chip 0's program
    executions that start inside it.  ``None`` without a slice."""
    slices = [h for h in events["host"] if h[0] == SLICE]
    if not slices or not events["devices"]:
        return None
    lo, hi = slices[0][1], slices[-1][2]
    chip0 = min(events["devices"])
    busy = clip(union((s, e) for _, s, e in events["devices"][chip0]),
                lo, hi)
    return {
        "window_s": (hi - lo) / 1e9,
        "gaps": subtract([(lo, hi)], busy),
        "spans": [h for h in events["host"] if h[0].startswith("serving/")
                  and h[2] > lo and h[1] < hi],
        "modules": [m for m in modules.get(chip0, ()) if lo <= m[1] < hi],
    }


def load(trace: dict):
    """The view of the trace that ``trace`` (the reduced trace a reader was
    handed) was made from, or ``None``.  Cached for the process."""
    found = newest_xplane()
    if found is None:
        return None
    path, cell = found
    key = (path, os.path.getmtime(path))
    if key not in _CACHE:
        v = view(read_events(path), read_modules(path))
        if v is not None:
            v["cell"] = cell
        _CACHE[key] = v
    v = _CACHE[key]
    if v is None or abs(v["window_s"] - trace["window_s"]) > 1e-9:
        return None
    return v


def cell_of(trace: dict):
    """The cell the traced run ran (its config and traffic as data), found
    from the trace's directory; ``None`` without this process's trace."""
    v = load(trace)
    if v is None:
        return None
    try:
        return loader.cell(loader.manifest(), v["cell"])
    except KeyError:
        return None


def _overlap(gaps, intervals) -> float:
    """Seconds of the union ``gaps`` that the union ``intervals`` covers
    (both sorted and disjoint; linear, as ``subtract`` is)."""
    return (total(gaps) - total(subtract(gaps, intervals))) / 1e9


def idle_split(v: dict) -> dict:
    """Chip 0's idle seconds inside the slice by what the host was doing:
    every gap is cut along the program's spans — the part under a
    top-level child of ``serving/step`` goes to that child's bucket, the
    part of ``serving/step`` under no child to ``bookkeeping``, the part
    under no ``serving/step`` to ``outside_step`` (the caller's loop) —
    so the buckets sum to the idle time exactly.  ``None`` where the
    program wrote no ``serving/step`` (a program before these spans)."""
    steps = union((s, e) for n, s, e in v["spans"] if n == STEP)
    if not steps:
        return None
    out = {b: _overlap(v["gaps"], union(
        (s, e) for n, s, e in v["spans"] if n in names))
        for b, names in BUCKETS.items()}
    in_step = _overlap(v["gaps"], steps)
    out["bookkeeping"] += in_step - sum(out.values())
    out["outside_step"] = total(v["gaps"]) / 1e9 - in_step
    return out


def idle_children(v: dict, parent: str) -> dict:
    """Idle share (%) of the slice under each ``<parent>/<child>`` span
    (``/stage``, ``/dispatch``, ``/readback``)."""
    return {name: 100.0 * _overlap(v["gaps"], union(
        (s, e) for n, s, e in v["spans"] if n == name)) / v["window_s"]
        for name in sorted({n for n, _, _ in v["spans"]
                            if n.startswith(parent + "/")})}


def idle_share(trace: dict, run: dict, bucket: str, parent: str = None):
    """One bucket of :func:`idle_split` as a share (%) of the slice.  With
    ``parent`` (the bucket's own span), the bucket's split by that span's
    children is printed as a free line, stamped with the device like
    every other (``run.py`` prints the result line last)."""
    v = load(trace)
    split = idle_split(v) if v is not None else None
    if split is None or not v["window_s"]:
        return None
    if parent is not None:
        import jax

        device.say(jax.devices()[:run.get("chips", 1)],
                   f"serve_idle_share.{bucket}: " + ", ".join(
                       f"{name} {share:.3f} %" for name, share
                       in idle_children(v, parent).items()))
    return 100.0 * split[bucket] / v["window_s"]


def program_ms(trace: dict, prefix: str):
    """Median device duration (ms) of one execution of the programs whose
    name starts with ``prefix``, over the slice; ``None`` if none ran."""
    import statistics

    v = load(trace)
    if v is None:
        return None
    ms = [(e - s) / 1e6 for n, s, e in v["modules"] if n.startswith(prefix)]
    return statistics.median(ms) if ms else None
