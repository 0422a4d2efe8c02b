"""What every entry point (``run.py``, ``sweep_rate.py``, ``control.py``)
does before its own work: find the cell's data and chips, place the compile
cache, and build the context that families and drivers are handed."""

import os
import time
import types

from benchmark.harness import device, loader, spans


def open_cell(workload: str, sizes=None, allow_cpu: bool = False):
    """``(manifest, cell, devices)``; :class:`device.NoChip` without the
    chips the cell asks for.  Places JAX's persistent compile cache (the
    program's own rule: ``JAX_COMPILATION_CACHE_DIR`` if set, else the fixed
    in-checkout ``.jax_cache``) before anything compiles."""
    man = loader.manifest()
    cell = loader.cell(man, workload, sizes)
    devices = device.find(cell["chips"], allow_cpu=allow_cpu)

    import jax
    from chainermn_tpu.topology import enable_compile_cache

    cell["compile_cache_dir"] = enable_compile_cache()
    # every program, however quick to compile, comes from the cache after a
    # cell's first run: set-up stays the same from run to run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return man, cell, devices


def context(cell, devices, seed: int, seconds: float, *, trace: bool = False,
            t0: float = None):
    """The namespace a family and a driver work with."""
    t0 = time.perf_counter() if t0 is None else t0
    ctx = types.SimpleNamespace(
        name=cell["name"], config=cell["config"], traffic=cell["traffic"],
        devices=devices, chips=cell["chips"], seed=seed, seconds=seconds,
        on_tpu=devices[0].platform == "tpu", spans=spans.Spans(),
        reference_s=0.0,
        tracer=spans.Tracer(os.path.join(
            loader.BENCH, ".trace", cell["name"]), trace),
        say=lambda text: device.say(
            devices, f"+{time.perf_counter() - t0:7.2f}s {text}"))
    ctx.family = loader.module("families", cell["config"]["family"])
    ctx.driver = loader.module("drivers", cell["traffic"]["driver"])
    return ctx
