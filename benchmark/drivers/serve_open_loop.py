"""Serving driver: an open loop at a fixed rate against a family's server.

Single-threaded client loop: submit everything that is due, one engine
iteration, stamp the tokens it emitted (the engine streams each token to
the request's callback; the stamp is the client's clock there).  Latencies
count from when a request was DUE, not from when the loop got round to
submitting it, and the generator's lateness is reported beside them.  The
window's metrics cover every request due in it; requests still running when
it closes are drained (at most ``drain_factor`` windows) and any that does
not finish is a failure."""

import time

import numpy as np

from benchmark.harness import stats, traffic as _traffic


def serve_window(server, reqs, seconds, ctx, drain_factor=2.0,
                 trace_seconds=0.0):
    """Offer ``reqs`` to ``server`` for ``seconds`` and drain.  Returns the
    per-request records and the loop's own samples."""
    spans, tracer = ctx.spans, ctx.tracer
    recs = [{"due_s": r["due_s"], "token_t": [], "handle": None,
             "submit_s": None} for r in reqs]
    clock = time.perf_counter
    nxt, n = 0, len(reqs)
    busy_samples, backlog_mid, backlog_end = [], None, None
    traced_from, slice_ends = None, []
    start = clock()
    while True:
        now = clock() - start
        if now < seconds:
            if nxt < n and recs[nxt]["due_s"] <= now:
                with spans.span("submit"):
                    while nxt < n and recs[nxt]["due_s"] <= now:
                        rec, req = recs[nxt], reqs[nxt]
                        rec["submit_s"] = clock() - start
                        rec["handle"] = server.submit(
                            req["prompt"], req["max_new"],
                            lambda tok, rid, _r=rec: _r["token_t"].append(
                                clock() - start))
                        nxt += 1
        else:
            if backlog_end is None:
                backlog_end = server.backlog() + (n - nxt)
            nxt = n                    # the loop never got round to the rest
            if server.idle() or now >= seconds * (1.0 + drain_factor):
                break
        if tracer.pending and now > seconds - trace_seconds - 0.5:
            # the slice is the window's last seconds: stopping the profiler
            # holds the loop for some seconds (14 s once), which would spoil
            # every request due after it
            traced_from = now
            slice_ends.append(server.metrics())    # read outside the slice:
            tracer.start()                         # no tick runs between
        if tracer.active and clock() - tracer.started >= trace_seconds:
            tracer.stop()
            slice_ends.append(server.metrics())
        if server.idle():
            due = recs[nxt]["due_s"] if nxt < n else seconds
            time.sleep(min(max(due - now, 0.0), 0.001))
            continue
        with spans.span("engine_step"):
            server.step()
        if now < (seconds if traced_from is None else traced_from):
            busy_samples.append(server.busy_slots())
            if backlog_mid is None and now >= seconds / 2:
                backlog_mid = server.backlog()
    if tracer.active:
        tracer.stop()
        slice_ends.append(server.metrics())
    return {"recs": recs, "busy_samples": busy_samples,
            "slice_metrics": _growth(*slice_ends) if slice_ends else None,
            "backlog_mid": backlog_mid, "backlog_end": backlog_end,
            "seconds": seconds, "traced_from": traced_from,
            "wall_s": clock() - start}


def _growth(first: dict, last: dict) -> dict:
    """The engine's counters over the traced slice: each number of
    ``metrics()`` at the slice's end less what it read at its start (a
    gauge reads 0 here: take it from the run's ``engine_metrics``)."""
    return {k: v - first[k] for k, v in last.items()
            if k in first and isinstance(v, (int, float))}


def summarize(out, min_tail=10):
    """The window's numbers from the per-request records (in a traced run,
    from the requests due before the traced slice)."""
    seconds, recs = out["seconds"], out["recs"]
    if out.get("traced_from") is not None:
        recs = [r for r in recs if r["due_s"] < out["traced_from"]]
    miss_ms = seconds * 1e3
    ttft, gaps, late, failed, tokens_in = [], [], [], 0, 0
    for r in recs:
        done = (r["handle"] is not None and r["handle"].status == "done"
                and len(r["token_t"]) >= 1)
        if not done:
            failed += 1
            ttft.append(miss_ms)
            continue
        late.append((r["submit_s"] - r["due_s"]) * 1e3)
        ttft.append((r["token_t"][0] - r["due_s"]) * 1e3)
        t = np.asarray(r["token_t"])
        gaps.extend(np.diff(t) * 1e3)
        tokens_in += int((t <= seconds).sum())

    def tail(values, q):
        try:
            return stats.percentile(values, q, min_tail)
        except stats.TooFewSamples:
            return None

    # a gap that holds a prefill (the engine admits between two ticks) is
    # several cadences long: where few gaps do, the p95 is the cadence's own
    # tail; where a tenth or more do, it lies inside tick + prefill
    gap_p50 = stats.median(gaps) if gaps else None
    long_gaps = (100.0 * float((np.asarray(gaps) > 2 * gap_p50).mean())
                 if gaps else None)
    return {
        "attempted": len(recs), "failed": failed,
        "gaps_over_2x_p50_share": long_gaps,
        "serve_tokens_per_s": tokens_in / seconds,
        "ttft_p50_ms": stats.median(ttft), "ttft_p95_ms": tail(ttft, 95),
        "gap_p50_ms": gap_p50,
        "gap_p95_ms": tail(gaps, 95),
        "gen_late_p95_ms": tail(late, 95),
        "gen_late_max_ms": max(late) if late else None,
        "n_ttft": len(ttft), "n_gaps": len(gaps),
    }


def run(ctx):
    fam, tr = ctx.family, ctx.traffic
    server = fam.build_server(ctx)
    ctx.say(f"server built: {server.info}")
    reqs = _traffic.open_loop(tr, ctx.seed, ctx.seconds, server.vocab)
    server.warm(tr["warm_prompts"])
    ctx.say("server warm")
    ctx.open_window()
    out = serve_window(server, reqs, ctx.seconds, ctx, tr["drain_factor"],
                       tr["trace_seconds"])
    ctx.close_window()
    s = summarize(out, tr["min_tail_samples"])
    ctx.say(f"window: {s['attempted']} requests due in {ctx.seconds} s, "
            f"{s['failed']} failed, drained after {out['wall_s']:.2f} s; "
            f"TTFT p50 {s['ttft_p50_ms']:.1f} ms p95 {s['ttft_p95_ms']} "
            f"(n={s['n_ttft']}); gap p50 {s['gap_p50_ms']} p95 "
            f"{s['gap_p95_ms']} (n={s['n_gaps']}, over 2 x p50: "
            f"{s['gaps_over_2x_p50_share']} %); generator late p95 "
            f"{s['gen_late_p95_ms']} max {s['gen_late_max_ms']} ms")
    checks = [{"name": "tails_have_samples", "limit": 0.0,
               "value": float(s["ttft_p95_ms"] is None
                              or s["gap_p95_ms"] is None),
               "ok": s["ttft_p95_ms"] is not None
               and s["gap_p95_ms"] is not None}]
    engine_metrics = server.metrics()
    handles = fam.served_sample(ctx, out["recs"], reqs, tr["check_requests"])
    server.close()
    del server
    t0 = time.perf_counter()
    checks += fam.serve_compare(ctx, handles)
    ctx.reference_s += time.perf_counter() - t0
    return {
        # as the window's numbers: in a traced run the requests due before
        # the slice (the profiler's stop holds the loop for seconds, and what
        # was due meanwhile is never offered: no failure of the system's)
        "checks": checks, "attempted": s["attempted"], "failed": s["failed"],
        "end_to_end": {k: s[k] for k in (
            "serve_tokens_per_s", "ttft_p95_ms", "gap_p95_ms")
            if s[k] is not None},
        "run": {"summary": s, "recs": [
            {k: r[k] for k in ("due_s", "submit_s", "token_t")}
            | {"timestamps": (r["handle"].timestamps
                              if r["handle"] is not None else {})}
            for r in out["recs"] if out["traced_from"] is None
            or r["due_s"] < out["traced_from"]],
            "busy_samples": out["busy_samples"], "n_slots": tr["engine"]["n_slots"],
            "engine_metrics": engine_metrics,
            "slice_metrics": out["slice_metrics"], "window_s": ctx.seconds,
            "min_tail": tr["min_tail_samples"]},
    }


def control(ctx):
    """The control of ``correct``: a short window at the cell's own load,
    then, over the same prompts and served tokens as the program's check,
    the gap of the token that the lower precision puts first at each
    position.  It has to fail the limit.  Returns the program's rows and
    the control's."""
    fam, tr = ctx.family, ctx.traffic
    server = fam.build_server(ctx)
    reqs = _traffic.open_loop(tr, ctx.seed, ctx.seconds, server.vocab)
    server.warm(tr["warm_prompts"])
    out = serve_window(server, reqs, ctx.seconds, ctx, tr["drain_factor"])
    sample = fam.served_sample(ctx, out["recs"], reqs, tr["check_requests"])
    server.close()
    del server
    rows = fam.serve_compare(ctx, sample)
    for r in rows:
        r["name"] = "program." + r["name"]
    return rows + fam.serve_compare(ctx, sample,
                                    precision=fam.CONTROL_PRECISION)
