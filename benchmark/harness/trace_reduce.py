"""From the profiler's ``.xplane.pb`` to what the ledger reads.

Busy and idle time of each chip, the table of device operations, the idle
gaps named by what the host was doing, and collective time with the part of
it that no other operation hides.  Reads the trace with nothing but JAX
(``jax.profiler.ProfileData``).  Roofline shares are not computed here: no
Pallas kernel of the program carries a stable name yet (PERF.md, Open
questions).

    python benchmark/harness/trace_reduce.py <dir-or-xplane.pb> [--dump]
"""

import glob
import json
import os
import re
import sys

#: the line of a device plane that holds one event per executed operation
OPS_LINE = "XLA Ops"
#: the line that holds what runs beside them (asynchronous copies, collectives)
ASYNC_LINE = "Async XLA Ops"
#: the host span that ``harness/spans.py::Tracer`` lays over the traced slice
SLICE = "traced_slice"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
COLLECTIVE = re.compile(
    r"all-reduce|reduce-scatter|all-gather|all-to-all|collective-permute",
    re.I)


def find_xplane(path: str) -> str:
    if os.path.isfile(path):
        return path
    found = sorted(glob.glob(os.path.join(
        path, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return found[-1]


KERNEL_TAG = " [tpu_custom_call]"


def short_name(text: str) -> str:
    """An operation's own name.  On a TPU an event of the ops line is
    named by its whole HLO text, ``%fusion.7 = (f32[...]) fusion(...)``;
    keep ``fusion.7``, and tag a Pallas kernel (a Mosaic custom call),
    since the program's kernels carry no name of their own yet."""
    name = text.split(" = ", 1)[0].lstrip("%")
    if 'custom_call_target="tpu_custom_call"' in text:
        name += KERNEL_TAG
    return name


def read_events(path: str) -> dict:
    """``{"devices": {chip: [(name, start_ns, end_ns)]}, "async": {chip:
    [...]}, "host": [...]}``: the operations of each chip, what ran beside
    them, and the host's python-thread spans (where
    ``jax.profiler.TraceAnnotation`` lands)."""
    import jax

    data = jax.profiler.ProfileData.from_file(find_xplane(path))
    devices, beside, host = {}, {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                into = {OPS_LINE: devices, ASYNC_LINE: beside}.get(line.name)
                if into is not None:
                    into.setdefault(int(m.group(1)), []).extend(
                        (short_name(e.name), e.start_ns,
                         e.start_ns + e.duration_ns) for e in line.events)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                if line.name.startswith("python"):
                    host.extend((e.name, e.start_ns,
                                 e.start_ns + e.duration_ns)
                                for e in line.events)
    return {"devices": devices, "async": beside, "host": host}


def dump(path: str, top: int = 25) -> dict:
    """Planes, lines and the commonest event names: look at a trace by hand
    before writing code against it."""
    import jax

    data = jax.profiler.ProfileData.from_file(find_xplane(path))
    out = {}
    for plane in data.planes:
        lines = {}
        for line in plane.lines:
            total, n, first = {}, 0, None
            for e in line.events:
                n += 1
                first = e.start_ns if first is None else min(first, e.start_ns)
                total[e.name] = total.get(e.name, 0.0) + e.duration_ns
            names = sorted(total.items(), key=lambda kv: -kv[1])[:top]
            lines[line.name] = {"events": n, "first_start_ns": first,
                                "top_ns": names}
        out[plane.name] = lines
    return out


# ---- interval arithmetic (half-open, nanoseconds) -------------------------

def union(intervals):
    """Sorted disjoint union of ``(start, end)`` pairs."""
    out = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def total(intervals) -> float:
    return float(sum(e - s for s, e in intervals))


def subtract(a, b):
    """The part of union ``a`` that union ``b`` does not cover."""
    out, b, j = [], list(b), 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def self_times(events) -> dict:
    """Seconds per operation name, a parent (a ``while``, a ``call``)
    counted without the time of the operations nested in it."""
    out, stack = {}, []          # stack of [name, end, child_ns, start]

    def close(upto):
        while stack and stack[-1][1] <= upto:
            name, end, child, start = stack.pop()
            out[name] = out.get(name, 0.0) + (end - start - child) / 1e9
            if stack:
                stack[-1][2] += end - start

    for name, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        close(s)
        if stack:
            e = min(e, stack[-1][1])
        stack.append([name, e, 0.0, s])
    close(float("inf"))
    return out


def leaves(events):
    """The events that hold no other event inside them."""
    out, ordered = [], sorted(events, key=lambda ev: (ev[1], -ev[2]))
    for i, ev in enumerate(ordered):
        nxt = ordered[i + 1] if i + 1 < len(ordered) else None
        if nxt is None or nxt[1] >= ev[2] or nxt[2] > ev[2]:
            out.append(ev)
    return out


def reduce(events: dict, span_names=(), chips=None) -> dict:
    """The reduced trace.  ``span_names``: the benchmark-side host spans by
    which idle gaps are named.  The window is the ``SLICE`` host span where
    the trace holds one, else the extent of the device's events."""
    devices = events["devices"]
    if chips is not None:
        devices = {d: devices[d] for d in sorted(devices)[:chips]}
    host = events["host"]
    spans = [h for h in host if h[0] == SLICE]
    every = [ev for ops in devices.values() for ev in ops]
    if spans:
        lo, hi = spans[0][1], spans[-1][2]
    elif every:
        lo, hi = min(e[1] for e in every), max(e[2] for e in every)
    else:
        lo = hi = 0.0
    busy = {d: clip(union((s, e) for _, s, e in ops), lo, hi)
            for d, ops in devices.items()}
    out = {
        "window_s": (hi - lo) / 1e9,
        "busy_s_per_chip": {d: total(b) / 1e9 for d, b in busy.items()},
        "busy_s": (sum(total(b) for b in busy.values()) / 1e9 / len(busy)
                   if busy else 0.0),
        "op_seconds": {}, "device_ops": [], "idle_gaps": [],
        "collective_s": 0.0, "collective_exposed_s": 0.0, "n_chips": len(busy),
    }
    if not devices:
        return out
    chip0 = min(devices)
    ops0 = [(n, max(s, lo), min(e, hi)) for n, s, e in devices[chip0]
            if min(e, hi) > max(s, lo)]
    out["op_seconds"] = self_times(ops0)
    out["device_ops"] = [[n, t] for n, t in sorted(
        out["op_seconds"].items(), key=lambda kv: -kv[1])[:10]]

    # idle gaps of chip 0, each named by the host span that covers most of it
    wanted = set(span_names)
    named = sorted((h for h in host if h[0] in wanted), key=lambda h: h[1])
    gaps, j = {}, 0
    for s, e in subtract([(lo, hi)], busy[chip0]):    # sorted, like ``named``
        while j < len(named) and named[j][2] <= s:
            j += 1
        best, cover, k = "none", 0.0, j
        while k < len(named) and named[k][1] < e:
            c = min(e, named[k][2]) - max(s, named[k][1])
            if c > cover:
                best, cover = named[k][0], c
            k += 1
        gaps[best] = gaps.get(best, 0.0) + (e - s) / 1e9
    out["idle_gaps"] = [[n, t] for n, t in sorted(
        gaps.items(), key=lambda kv: -kv[1])[:10]]

    # a collective is exposed while no other operation runs beside it; a
    # parent (a ``while`` around the step) is not an operation of its own
    flat = leaves(ops0)
    beside = clip([(s, e) for n, s, e in events.get("async", {}).get(chip0, ())
                   if COLLECTIVE.search(n)], lo, hi)
    coll = union([(s, e) for n, s, e in flat if COLLECTIVE.search(n)] + beside)
    other = union((s, e) for n, s, e in flat if not COLLECTIVE.search(n))
    out["collective_s"] = total(coll) / 1e9
    out["collective_exposed_s"] = total(subtract(coll, other)) / 1e9
    return out


def reduce_file(path: str, span_names=(), chips=None) -> dict:
    return reduce(read_events(path), span_names, chips=chips)


if __name__ == "__main__":
    if "--dump" in sys.argv:
        print(json.dumps(dump(sys.argv[1]), indent=1))
    else:
        print(json.dumps(reduce_file(sys.argv[1]), indent=1))
