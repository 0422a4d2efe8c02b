"""The program's spans on the profiler's clock (ISSUE 25).

While a profiler session records, ``Tracer.span`` enters a
``jax.profiler.TraceAnnotation``, so the session sees the serving engine's
phases in its own trace — on the clock the device's events are on — with
nobody calling ``obs.enable()``; without a session, and with the tracer
disabled, the same run records nothing and a span is the shared no-op.
"""

import glob

import numpy as np
import pytest

from benchmark.harness.spans import GAP_SPANS

VOCAB, D, HEADS, LAYERS = 32, 16, 4, 2
HEAD_DIM = D // HEADS

#: span -> the span it must sit inside (docs/OBSERVABILITY.md's table)
PARENT = {
    "serving/step": None,
    "serving/expire": "serving/step",
    "serving/admit": "serving/step",
    "serving/prefill": "serving/step",
    "serving/prefill/stage": "serving/prefill",
    "serving/prefill/dispatch": "serving/prefill",
    "serving/prefill/readback": "serving/prefill",
    "serving/prefix_copy": "serving/step",
    "serving/spill_restore": "serving/step",
    "serving/tick": "serving/step",
    "serving/tick/stage": "serving/tick",
    "serving/tick/dispatch": "serving/tick",
    "serving/tick/readback": "serving/tick",
    "serving/emit": "serving/step",
    "serving/bookkeeping": "serving/step",
}
#: the args the table states for a span
ARGS = {
    "serving/step": {"tick"},
    "serving/prefill": {"trace_id", "s_real", "s_pad"},
    "serving/prefix_copy": {"trace_id"},
    "serving/spill_restore": {"trace_id"},
    "serving/tick": {"active"},
    "serving/emit": {"tokens"},
}


def _engine(devices):
    import jax

    import chainermn_tpu as mn
    from chainermn_tpu.parallel import init_tp_transformer_lm
    from chainermn_tpu.serving import ServingEngine

    params = init_tp_transformer_lm(
        jax.random.PRNGKey(0), VOCAB, D, HEADS, LAYERS, max_len=64)
    mesh = mn.make_nd_mesh(("model",), (1,), devices[:1])
    return ServingEngine(params, head_dim=HEAD_DIM, n_slots=2, max_total=32,
                         mesh=mesh, queue_capacity=8, prefill_bucket=8)


def _serve(eng, lead=0):
    """Three requests, the third a repeat of the first after it finished:
    a prefix hit, so ``serving/prefix_copy`` runs too.  ``lead`` is the
    prompts' first token: a run with another ``lead`` shares no prefix
    with this one, so its first two requests are prefilled."""
    rng = np.random.RandomState(1)
    first = rng.randint(0, VOCAB, 6).astype(np.int32)
    second = rng.randint(0, VOCAB, 11).astype(np.int32)
    first[0], second[0] = lead, lead + 1
    eng.submit(first, 4, trace_id="req-a")
    eng.submit(second, 3, trace_id="req-b")
    eng.run()
    eng.submit(np.concatenate([first, first[:2]]), 3, trace_id="req-c")
    eng.run()


@pytest.fixture(scope="module")
def traced(devices, tmp_path_factory):
    """``[(name, start_ns, end_ns, args)]`` of the python line of one
    profiler session around a tiny engine run; the tracer stays off."""
    import jax

    from chainermn_tpu import observability as obs

    obs.reset()
    assert not obs.enabled()
    eng = _engine(devices)
    _serve(eng)                       # compile outside the session
    out = str(tmp_path_factory.mktemp("profile"))
    jax.profiler.start_trace(out)
    try:
        _serve(eng, lead=10)
    finally:
        jax.profiler.stop_trace()
    assert obs.get_tracer().events() == []
    path, = glob.glob(out + "/plugins/profile/*/*.xplane.pb")
    spans = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            if line.name.startswith("python"):
                spans += [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                           dict(e.stats)) for e in line.events
                          if e.name.startswith("serving/")]
    return spans


@pytest.mark.parametrize("name", sorted(PARENT))
def test_span_is_in_the_profilers_trace_and_nested(traced, name):
    mine = [s for s in traced if s[0] == name]
    assert mine, f"no {name} span in the profiler's python line"
    parent = PARENT[name]
    for _, s, e, args in mine:
        assert ARGS.get(name, set()) <= set(args), (name, args)
        if parent is not None:
            assert any(p[0] == parent and p[1] <= s and e <= p[2]
                       for p in traced), f"{name} outside any {parent}"


def test_request_spans_carry_the_requests_trace_id(traced):
    ids = {s[3]["trace_id"] for s in traced if s[0] == "serving/prefill"}
    assert {"req-a", "req-b"} <= ids
    hits = [s[3] for s in traced if s[0] == "serving/prefix_copy"]
    assert hits and all(a["trace_id"] == "req-c" for a in hits)
    pre = [s[3] for s in traced if s[0] == "serving/prefill"
           and s[3]["trace_id"] == "req-b"]
    assert (int(pre[0]["s_real"]), int(pre[0]["s_pad"])) == (11, 16)


def test_no_program_span_is_named_like_a_benchmark_gap_span(traced):
    """``breakdown.idle_gaps`` labels idle time by the benchmark's own
    spans; a program span of the same name would change what it reads."""
    names = {s[0] for s in traced}
    assert names <= set(PARENT), names - set(PARENT)
    assert not names & set(GAP_SPANS)
    assert not {n.rsplit("/", 1)[-1] for n in names} & {
        "input", "submit", "engine_step"}


def test_without_a_session_nothing_is_recorded_and_counters_count(devices):
    from chainermn_tpu import observability as obs

    obs.reset()
    assert not obs.enabled()
    eng = _engine(devices)
    _serve(eng)
    assert obs.get_tracer().events() == []
    assert obs.get_tracer().counters() == {}
    m = eng.metrics()
    real = m["serving/prefill_tokens_real"]
    padded = m["serving/prefill_tokens_padded"]
    # prompts of 6 and 11 pad to 8 and 16; the repeat is a prefix hit
    # (copied, not prefilled)
    assert (real, padded) == (17.0, 24.0)
    eng.reset_stats()
    m = eng.metrics()
    assert m["serving/prefill_tokens_real"] == 0.0
    assert m["serving/prefill_tokens_padded"] == 0.0


def test_enabled_tracer_records_the_same_spans_as_chrome_events(devices):
    from chainermn_tpu import observability as obs

    obs.reset()
    obs.enable()
    try:
        eng = _engine(devices)
        _serve(eng)
        names = {e["name"] for e in obs.get_tracer().events()
                 if e["ph"] == "X"}
    finally:
        obs.disable()
        obs.reset()
    assert set(PARENT) <= names
    assert {"request/queue_wait", "request/decode_tick"} <= names
