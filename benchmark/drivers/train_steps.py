"""Training driver: a family's compiled step, driven for ``--seconds``.

Order of a run: the plain reference's first steps (before the program's
state exists, and outside ``setup_s``); the program's one trainer object,
driven through the same first steps by the window's own call and feed; the
comparison; then the window on that same object.  Steps are dispatched
without a per-step readback; the loss is read back every
``readback_every``-th step, which is also where the window may end."""

import math
import time


def run(ctx):
    fam, tr = ctx.family, ctx.traffic
    n_check, every = tr["check_steps"], tr["readback_every"]

    t0 = time.perf_counter()
    want = fam.train_reference(ctx, n_check)
    ctx.reference_s += time.perf_counter() - t0
    ctx.say(f"reference: {n_check} steps in {ctx.reference_s:.2f} s, losses "
            f"{want['losses']}, seconds per step {want['step_s']}")

    trainer = fam.build_trainer(ctx)
    ctx.say(f"step compiled: {trainer.info}")
    got = trainer.first_steps(n_check)
    checks = fam.train_compare(want, got)
    ctx.say(f"program: losses {got['losses']}")
    # warm the window's own shape of loop: `every` steps, one readback
    for _ in range(every - 1):
        trainer.step()
    float(trainer.step())

    spans, tracer = ctx.spans, ctx.tracer
    losses, n_steps = [], 0
    slice_steps = 0

    def read(loss):
        with spans.span("readback"):
            losses.append(float(loss))

    def segment(pending):
        """``every`` steps dispatched, THEN the previous segment's loss read
        back: a segment of work is always queued behind the one the host
        waits for, so a host that is late by less than a segment costs the
        device nothing (a run that dispatched only after each readback lost
        13 % once to the host's hiccups; PERF.md, Findings PR 24)."""
        for _ in range(every - 1):
            trainer.step()
        loss = trainer.step()
        if pending is not None:
            read(pending)
        return loss

    start = ctx.open_window()
    pending = None
    while True:
        if tracer.pending and time.perf_counter() - start > ctx.seconds / 3:
            # the traced slice holds exactly its own steps: drain before it
            # starts and before it stops
            if pending is not None:
                read(pending)
            tracer.start()
            pending = None
            for _ in range(tr["trace_steps"] // every):
                pending = segment(pending)
                n_steps += every
            read(pending)
            tracer.stop()
            slice_steps = tr["trace_steps"] // every * every
            pending = None
        pending = segment(pending)
        n_steps += every
        if time.perf_counter() - start >= ctx.seconds:
            break
    read(pending)
    window_s = ctx.close_window()

    checks.append({"name": "losses_finite", "value": float(
        sum(0 if math.isfinite(l) else 1 for l in losses)), "limit": 0.0,
        "ok": all(math.isfinite(l) for l in losses)})
    per_step = trainer.samples_per_step
    rate = n_steps * per_step / window_s
    ctx.say(f"window: {n_steps} steps of {per_step} samples in "
            f"{window_s:.3f} s; loss {losses[0]:.4f} -> {losses[-1]:.4f}")
    return {
        "checks": checks,
        "attempted": n_steps * per_step,
        "failed": 0,
        "end_to_end": {"train_samples_per_s": rate},
        "run": {
            "samples_per_step": per_step,
            "flops_per_sample": trainer.flops_per_sample,
            "steps_in_slice": slice_steps,
            "steps": n_steps,
            "window_s": window_s,
            "window_start": start,
        },
    }


def control(ctx):
    """The control of ``correct``: the reference put in the program's place,
    computed in the nearest precision below the one the configuration
    states.  It has to fail a limit.  No window is needed."""
    fam, n = ctx.family, ctx.traffic["check_steps"]
    want = fam.train_reference(ctx, n)
    got = fam.train_reference(ctx, n, precision=ctx.family.CONTROL_PRECISION)
    ctx.say(f"control losses {got['losses']} against {want['losses']}")
    return fam.train_compare(want, got)
