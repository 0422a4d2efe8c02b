#!/usr/bin/env python
"""Probe (ISSUE 41; run before the served tick was given the kernel): what
does one delta-rule layer's convolution window cost a tick as
``parallel/kda.py::_short_conv`` has it — concatenate, four float32 slices,
a vmapped ``dynamic_slice`` that compiles to a ``while`` loop over every
slot — against three ways of moving only what the busy slots need?

* ``one_slot_kernel``: the issue's sketch, ``cache_write_rows``' shape — one
  busy slot's ``(1, W-1, C)`` block a grid step over the tick's busy list.
  Its operand is row-major over ``(N, W-1, C)``, and the pool's window is
  NOT: the chip's default layout of a ``(64, 3, 12288)`` bf16 array is
  ``{2,0,1:T(8,128)(2,1)}``, the short axis outermost, so the compiler lays
  the whole pool's window out anew before the call and back after it.
* ``conv_step`` (``ops/conv_step.py``): the window walked as the ``(W-1, N,
  C)`` array it is, a block of 16 slots a step over the blocks that hold a
  busy slot, in place.
* ``xla_select``: ``conv_step_xla``, the same step as plain selects.

At Kimi's shape (64 slots, 12 busy, ``C`` 12288) and at Jamba's (128 slots,
64 busy, ``C`` 5120), bf16 windows, width 4.  A program steps ``LAYERS``
windows once each — separate donated arguments in the pool's own layout, as
the tick's 20 layers are — and ``REPS`` such programs run back to back: a
call's time holds what the compiler puts around it.  Two clocks: the host's
over the laps, which at 144 buffers a program is the LAUNCH's (≈ 65 µs a
call whatever the form: the first run read 65–72 for every form but the
parent's), and the device's own from a traced lap, which is the number.  Busy
slots' results must come out equal under every form, and idle slots'
windows bit for bit as they were.

Second, ``ops/kda_step.py``: the state kernel with its vectors stacked into
``(N, 3, H, d_k)`` / ``(N, 2, H, d_v)`` full-pool temporaries (the form until
PR 41, kept below for the comparison) against the operands read where they
lie, at Kimi's shape (32 heads of 128 x 128, 20 states).

Chip only; prints one JSON object last (PERF.md, Findings PR 41, has the
first run's).

    chiprun -- python scripts/probe_conv_step.py
"""

import json
import os
import shutil
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.experimental import pallas as pl  # noqa: E402
from jax.experimental.pallas import tpu as pltpu  # noqa: E402

from benchmark.harness.trace_reduce import find_xplane  # noqa: E402
from chainermn_tpu.ops import conv_step as cs  # noqa: E402
from chainermn_tpu.ops import kda_step as ks  # noqa: E402
from chainermn_tpu.ops.kv_cache import busy_slots  # noqa: E402
from chainermn_tpu.parallel.kda import _short_conv  # noqa: E402

#: name -> (slots, busy, channels)
SHAPES = {"kimi": (64, 12, 12288), "jamba": (128, 64, 5120)}
#: the state kernel's: slots, busy, heads, head width, states
KDA_SHAPE = (64, 12, 32, 128, 20)
WIDTH = 4
LAYERS = 48
REPS = 10


# ---------------------------------------------------------------- the forms

def short_conv(window, new, weight, busy):
    y, out = _short_conv(window, new, weight, busy.astype(jnp.int32))
    return y[:, 0], out.astype(window.dtype)


def _one_slot(slot_ref, n_ref, new_ref, w_ref, win_ref, y_ref, wout_ref):
    del slot_ref
    f32 = jnp.float32
    width = w_ref.shape[0]
    listed = pl.program_id(0) < n_ref[0]
    new = new_ref[0]
    rows = [win_ref[0, i:i + 1, :] for i in range(width - 1)]
    xs = rows + [new]
    y = xs[0].astype(f32) * w_ref[0:1, :].astype(f32)
    for i in range(1, width):
        y = y + xs[i].astype(f32) * w_ref[i:i + 1, :].astype(f32)
    y_ref[0] = jnp.where(listed, y, 0.0)
    moved = rows[1:] + [new]
    for i in range(width - 1):
        wout_ref[0, i:i + 1, :] = jnp.where(listed, moved[i], rows[i])


def one_slot_kernel(window, new, weight, busy):
    n, keep, c = window.shape
    slots = busy_slots(busy, n)
    at = lambda t, s, nb: (s[t], 0, 0)
    y, out = pl.pallas_call(
        _one_slot,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(jnp.maximum(slots.n[0], 1),),
            in_specs=[pl.BlockSpec((1, 1, c), at),
                      pl.BlockSpec((WIDTH, c), lambda t, s, nb: (0, 0)),
                      pl.BlockSpec((1, keep, c), at)],
            out_specs=[pl.BlockSpec((1, 1, c), at),
                       pl.BlockSpec((1, keep, c), at)]),
        out_shape=[jax.ShapeDtypeStruct((n, 1, c), jnp.float32),
                   jax.ShapeDtypeStruct(window.shape, window.dtype)],
        input_output_aliases={4: 1},
        name="conv_one_slot",
    )(slots.slot, slots.n, new, weight, window)
    return jnp.where(busy[:, None], y[:, 0], 0.0), out


def conv_step(window, new, weight, busy):
    y, out = cs.conv_step(window, new, weight, busy)
    return y[:, 0], out


def xla_select(window, new, weight, busy):
    y, out = cs.conv_step_xla(window, new, weight, busy)
    return y[:, 0], out


CONV_FORMS = {"short_conv": short_conv, "one_slot_kernel": one_slot_kernel,
              "conv_step": conv_step, "xla_select": xla_select}


def _stacked_kernel(slot_ref, n_busy_ref, qkg_ref, vb_ref, s_ref, o_ref,
                    so_ref):
    del slot_ref
    n_heads = s_ref.shape[1]

    @pl.when(pl.program_id(1) < n_busy_ref[0])
    def _step():
        q_t = qkg_ref[0, 0].T
        k_t = qkg_ref[0, 1].T
        a_t = jnp.exp(qkg_ref[0, 2]).T
        for j in range(n_heads):
            k_col = k_t[:, j:j + 1]
            s_dec = s_ref[0, j] * a_t[:, j:j + 1]
            u = (s_dec * k_col).sum(0, keepdims=True)
            r = vb_ref[0, 1, j:j + 1] * (vb_ref[0, 0, j:j + 1] - u)
            s_new = s_dec + k_col * r
            so_ref[0, j] = s_new
            o_ref[0, j:j + 1] = (s_new * q_t[:, j:j + 1]).sum(
                0, keepdims=True)

    @pl.when(n_busy_ref[0] == 0)
    def _nothing_busy():
        so_ref[...] = s_ref[...]
        o_ref[...] = jnp.zeros_like(o_ref)


def kda_stacked(q, k, v, g, beta, state, busy, slots):
    """``kda_step`` as it stood until PR 41."""
    n, h, dk = q.shape
    dv = v.shape[-1]
    hb = 8
    f32 = jnp.float32
    qkg = jnp.stack([q, k, g], axis=1).astype(f32)
    vb = jnp.stack([v.astype(f32), jnp.broadcast_to(
        beta.astype(f32)[..., None], (n, h, dv))], axis=1)
    o, new_state = pl.pallas_call(
        _stacked_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(h // hb, n),
            in_specs=[
                pl.BlockSpec((1, 3, hb, dk),
                             lambda b, i, s, nb: (s[i], 0, b, 0)),
                pl.BlockSpec((1, 2, hb, dv),
                             lambda b, i, s, nb: (s[i], 0, b, 0)),
                pl.BlockSpec((1, hb, dk, dv),
                             lambda b, i, s, nb: (s[i], b, 0, 0))],
            out_specs=[
                pl.BlockSpec((1, hb, dv), lambda b, i, s, nb: (s[i], b, 0)),
                pl.BlockSpec((1, hb, dk, dv),
                             lambda b, i, s, nb: (s[i], b, 0, 0))]),
        out_shape=[jax.ShapeDtypeStruct((n, h, dv), f32),
                   jax.ShapeDtypeStruct(state.shape, f32)],
        input_output_aliases={4: 1},
        name="kda_step",
    )(slots.slot, slots.n, qkg, vb, state)
    return jnp.where(busy[:, None, None], o, 0.0), new_state


def kda_separate(q, k, v, g, beta, state, busy, slots):
    return ks.kda_step(q, k, v, g, beta, state, busy, slots)


# --------------------------------------------------------------- the timing

def timed(fn, bufs, *args):
    """Microseconds a call (a program steps every buffer once): the median
    over five laps of ``REPS`` programs on the host's clock, and the
    DEVICE's own — the programs' durations on the profiler's ``XLA
    Modules`` line of a traced lap, which holds no launch and no host."""
    n = len(bufs)
    out = jax.block_until_ready(fn(bufs, *args))
    laps = []
    for _ in range(5):
        t = time.perf_counter()
        for _ in range(REPS):
            out = fn(out[1], *args)
        jax.block_until_ready(out)
        laps.append((time.perf_counter() - t) / (REPS * n) * 1e6)
    trace = os.path.join(ROOT, "benchmark", ".scratch", "probe_conv_step")
    shutil.rmtree(trace, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace, profiler_options=options)
    for _ in range(REPS):
        out = fn(out[1], *args)
    jax.block_until_ready(out)
    jax.profiler.stop_trace()
    data = jax.profiler.ProfileData.from_file(find_xplane(trace))
    shutil.rmtree(trace, ignore_errors=True)
    runs = [e.duration_ns for plane in data.planes
            if plane.name == "/device:TPU:0" for line in plane.lines
            if line.name == "XLA Modules" for e in line.events]
    assert len(runs) >= REPS, len(runs)
    return {"host_us": round(statistics.median(laps), 2),
            "device_us": round(statistics.median(sorted(runs)[-REPS:])
                               / n / 1e3, 2)}


def probe_conv(name):
    n, n_busy, c = SHAPES[name]
    rs = np.random.RandomState(41)
    bf16 = jnp.bfloat16
    busy = np.isin(np.arange(n), rs.permutation(n)[:n_busy])
    new = jnp.asarray(rs.randn(n, 1, c), bf16)
    weight = jnp.asarray(rs.randn(WIDTH, c), bf16)
    start = [jnp.asarray(rs.randn(n, WIDTH - 1, c), bf16)
             for _ in range(LAYERS)]
    bits = lambda a: np.asarray(a).view(np.uint16)
    was = bits(start[0])
    out = {"shape": [n, WIDTH - 1, c], "busy": n_busy}
    want = None
    for key, form in CONV_FORMS.items():
        fn = jax.jit(lambda bufs, new, weight, busy, form=form: tuple(
            zip(*(form(w, new, weight, busy) for w in bufs))),
            donate_argnums=(0,))
        # one step from the start: results against the first form's
        y, wins = fn(tuple(jnp.array(w) for w in start[:1]), new, weight,
                     jnp.asarray(busy))
        y, win = np.asarray(y[0]), bits(wins[0])
        if want is None:
            want = (y, win)
        assert np.array_equal(win[busy], want[1][busy]), (name, key)
        assert np.array_equal(win[~busy], was[~busy]), (name, key)
        out[f"{key}_y_max_diff"] = float(np.abs(
            y[busy] - want[0][busy]).max())
        layout = fn.lower(tuple(start), new, weight, jnp.asarray(busy)
                          ).compile().as_text()
        out[f"{key}_pool_copies"] = sum(
            1 for ln in layout.split("\n")
            if " copy(" in ln and f"[{n},{WIDTH - 1},{c}]" in ln
            and ln.lstrip().startswith("%copy"))
        out[key] = timed(fn, tuple(jnp.array(w) for w in start), new,
                         weight, jnp.asarray(busy))
    return out


def probe_kda():
    n, n_busy, h, d, layers = KDA_SHAPE
    rs = np.random.RandomState(7)
    f32 = jnp.float32
    busy = np.isin(np.arange(n), rs.permutation(n)[:n_busy])
    vec = lambda *s: jnp.asarray(rs.randn(*s), f32)
    q, k, v = vec(n, h, d), vec(n, h, d), vec(n, h, d)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    g = -jnp.abs(vec(n, h, d)) * 0.1
    beta = jax.nn.sigmoid(vec(n, h))
    start = [vec(n, h, d, d) for _ in range(layers)]
    out = {"shape": [n, h, d, d], "busy": n_busy, "layers": layers}
    want = None
    for key, form in (("stacked", kda_stacked), ("separate", kda_separate)):
        def run(states, q, k, v, g, beta, busy, form=form):
            slots = busy_slots(busy, n)
            # every vector a temporary of its own layer, as the tick's are
            # (an entry parameter lies in HBM; a temporary of this size the
            # compiler may keep in fast memory: the first run, with k, v, g
            # shared parameters, read the separate form 21 µs SLOWER)
            return tuple(zip(*(form(q + i, k * (1 + i), v + i, g - i, beta, s,
                                    busy, slots)
                               for i, s in enumerate(states))))
        fn = jax.jit(run, donate_argnums=(0,))
        args = (q, k, v, g, beta, jnp.asarray(busy))
        o, states = fn((jnp.array(start[0]),), *args)
        o, state = np.asarray(o[0]), np.asarray(states[0])
        if want is None:
            want = (o, state)
        out[f"{key}_o_max_diff"] = float(np.abs(o - want[0]).max())
        out[f"{key}_state_max_diff"] = float(np.abs(state - want[1]).max())
        assert np.array_equal(state[~busy], np.asarray(start[0])[~busy]), key
        out[key] = timed(fn, tuple(jnp.array(s) for s in start), *args)
    return out


def main():
    if jax.default_backend() != "tpu":
        print(json.dumps({"error": "no TPU: the probe times device code"}))
        return 2
    result = {"probe": "conv_step", "layers": LAYERS, "reps": REPS,
              "device": jax.devices()[0].device_kind}
    for name in SHAPES:
        result[name] = probe_conv(name)
        print(json.dumps({name: result[name]}), flush=True)
    result["kda_step"] = probe_kda()
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
