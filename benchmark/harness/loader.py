"""Finds a cell's files from the names in BENCHMARK.json.

  workload ``name``      -> its entry: config, traffic, chips
  config ``name``        -> the ``file`` its entry names (``family`` inside)
  traffic ``name``       -> benchmark/traffic/<name>.json  (``driver`` inside)
  cell ``name``          -> benchmark/cells/<name>.json, optional: its
                            ``traffic`` object overrides the mix's keys
                            (the found rate of a fixed-rate cell)
  family / driver        -> benchmark/families/<family>.py, drivers/<driver>.py
  per-layer metric       -> benchmark/layer_metrics/<name>.py :: read()
"""

import importlib.util
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")


def _json(path):
    with open(path) as f:
        return json.load(f)


def manifest():
    return _json(os.path.join(ROOT, "BENCHMARK.json"))


def module(kind: str, name: str):
    """``benchmark/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = os.path.join(BENCH, kind, name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind[:-1]} file {path}")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def merge(base: dict, over) -> dict:
    """``base`` with ``over``'s keys laid over it, nested objects merged."""
    out = dict(base)
    for k, v in (over or {}).items():
        out[k] = merge(out[k], v) if isinstance(v, dict) and isinstance(
            out.get(k), dict) else v
    return out


def cell(man, name: str, sizes=None) -> dict:
    """Everything one cell runs with, resolved to data.  ``sizes`` (the
    tests' rehearsal hook) lays tiny ``config`` / ``traffic`` keys over it."""
    entries = [w for w in man["workloads"] if w["name"] == name]
    if not entries:
        known = ", ".join(w["name"] for w in man["workloads"])
        raise KeyError(f"no workload {name!r} in BENCHMARK.json ({known})")
    entry = entries[0]
    cfg_entry = next(c for c in man["configs"] if c["name"] == entry["config"])
    config = _json(os.path.join(ROOT, cfg_entry["file"]))
    traffic = _json(os.path.join(BENCH, "traffic", entry["traffic"] + ".json"))
    cell_path = os.path.join(BENCH, "cells", name + ".json")
    if os.path.isfile(cell_path):
        traffic = merge(traffic, _json(cell_path).get("traffic"))
    if sizes:
        config = merge(config, sizes.get("config"))
        traffic = merge(traffic, sizes.get("traffic"))
    return {"name": name, "chips": entry["chips"], "config": config,
            "traffic": traffic}


def metrics_of(man, group: str, cell_name: str, reported=None):
    """The ``group`` ('end_to_end' | 'per_layer') metrics due in a cell.

    A metric with a ``workloads`` key is due in the cells it lists.  A
    per-layer metric without one is due wherever the end-to-end metric it
    ``moves`` is reported (``reported``: those names)."""
    due = []
    for m in man[group]:
        if "workloads" in m:
            if cell_name in m["workloads"]:
                due.append(m)
        elif group == "end_to_end" or reported is None \
                or m["moves"] in reported:
            due.append(m)
    return due
