"""Model FLOP/s utilization of training: the FLOPs the algorithm needs for
forward and backward of one sample (the family's function, recomputation
not counted) times the traced slice's own rate (its steps over its wall
time, idle included), over the chips' bf16 peak.  Not a kernel's roofline
share.  The slice's rate, not the window's: around the slice the profiler
slows the host for seconds, and a traced window read 38 % and 47 % where
untraced ones give 50.9 % (PERF.md, Findings PR 24)."""


def read(trace, spans, run):
    if not run.get("steps_in_slice") or not trace["window_s"] \
            or "bf16_flops" not in run["peaks"]:
        return None
    rate = run["steps_in_slice"] * run["samples_per_step"] / trace["window_s"]
    return 100.0 * run["flops_per_sample"] * rate / (
        run["chips"] * run["peaks"]["bf16_flops"])
