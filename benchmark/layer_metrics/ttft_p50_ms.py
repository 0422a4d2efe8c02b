"""Time from when a request was DUE to its first token as the client loop
sees it (p50_ms over the window's requests; a failed or refused request
counts as a miss at the window's length).  Not bounded end to end: with the
180 requests a window holds, two runs of one seed read it 3-23 % apart
(PERF.md, section 2)."""


def read(trace, spans, run):
    return run.get("summary", {}).get("ttft_p50_ms")
