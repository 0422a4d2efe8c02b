"""Registered entry points for the jaxpr engine.

Each entry point names one REAL program of this repo — the collective
vocabulary of ``ops/collective.py``, the TP decode tick the serving
engine drives, and the per-prompt-length prefill family — built at tiny
shapes (d_model=8, one layer, axis size 1) so the whole sweep traces in
seconds on one CPU device.  Axis size 1 is enough: collectives still
appear as jaxpr equations with their axis names, which is all the
unbound-axis check reads; the recompile probes execute for real but on
KB-sized arrays.

Entry points are the extension surface: a new subsystem that adds a
compiled program registers it here and the analyzer owns it from then
on (docs/ANALYSIS.md shows the recipe).
"""

from __future__ import annotations

from typing import Any, Dict

from .jaxpr_engine import EntryPoint

_SEED = 0  # analysis must trace the same program every run


def _tiny_lm(tp: int = 1):
    """Shared tiny TP transformer-LM fixture: (params, specs, mesh)."""
    import jax

    from chainermn_tpu import topology
    from chainermn_tpu.parallel.transformer import (
        init_tp_transformer_lm, transformer_lm_specs)

    params = init_tp_transformer_lm(
        jax.random.PRNGKey(_SEED), 16, 8, 2, 1, max_len=8)
    specs = transformer_lm_specs(params, "model")
    mesh = topology.make_nd_mesh(("model",), (tp,), jax.devices()[:tp])
    return params, specs, mesh


def _build_collective_ring() -> Dict[str, Any]:
    """The ops/collective.py vocabulary under one shard_map binding —
    psum / reduce_scatter / all_gather / shift in the gradient-ring order
    the train CLI demos."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from chainermn_tpu import topology
    from chainermn_tpu._compat import shard_map
    from chainermn_tpu.ops import collective as C
    from jax.sharding import PartitionSpec as P

    mesh = topology.make_nd_mesh(("mn",), (1,), jax.devices()[:1])

    def body(x):
        g = C.reduce_scatter(x, "mn")
        g = C.all_gather(g, "mn")
        g = C.shift(g, 1, "mn", size=1)
        return C.psum(g, "mn")

    fn = shard_map(body, mesh=mesh, in_specs=(P(),), out_specs=P())
    x = np.ones((4,), np.float32)

    def run(v):
        return fn(jnp.asarray(v))

    return {"trace": (run, (x,)), "bound_axes": {"mn"},
            # shard-flow: the ring's input is replicated by the P() feed
            # — deliberately NOT annotated, so the finding lives in the
            # checked-in .shardflow-baseline.json as the keeper proving
            # the replication gate is live
            "data_axis": "mn", "arg_labels": ("x",)}


def _build_decode_tick() -> Dict[str, Any]:
    """One serving decode tick (the pool-lifetime compiled program):
    traced for its collective sequence AND probed for recompilation —
    two calls with different token/pos VALUES must reuse ONE program."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from chainermn_tpu._compat import shard_map
    from chainermn_tpu.parallel.decode import lm_decode_tick, lm_prefill
    from jax.sharding import PartitionSpec as P

    params, specs, mesh = _tiny_lm()
    head_dim = 4
    total = 8

    prompt = np.zeros((1, 3), np.int32)

    def tick(p, tokens, caches, pos):
        return lm_decode_tick(p, tokens, caches, pos, head_dim=head_dim,
                              axis_name="model")

    def prefill(p, pr):
        return lm_prefill(p, pr, total, head_dim=head_dim,
                          axis_name="model")

    # the pool's own cache spec (serving/cache_pool.py): the flat K/V
    # rows shard their head dimension over 'model', so they are typed
    # varying over it and cannot leave through P()
    kv = P(None, None, "model")
    sm_prefill = shard_map(prefill, mesh=mesh, in_specs=(specs, P()),
                           out_specs=(P(), [(kv, kv)]))
    _, caches = sm_prefill(params, jnp.asarray(prompt))

    cache_specs = [(kv, kv) for _ in caches]
    sm_tick = jax.jit(shard_map(
        tick, mesh=mesh, in_specs=(specs, P(), cache_specs, P()),
        out_specs=(P(), cache_specs)))

    tokens = np.zeros((1,), np.int32)
    pos = np.asarray([3], np.int32)

    def run(p, t, c, q):
        return sm_tick(p, t, c, q)

    variants = (sm_tick, [
        (params, jnp.asarray(tokens), caches, jnp.asarray(pos)),
        (params, jnp.asarray(tokens + 1), caches,
         jnp.asarray(pos + 1)),
    ])
    return {"trace": (run, (params, jnp.asarray(tokens), caches,
                            jnp.asarray(pos))),
            "bound_axes": {"model"},
            "variants": variants,
            # shard-flow: TP shards the matmul weights over 'model';
            # norm scales/biases stay replicated by the Megatron layout;
            # the KV pool rows shard their heads.  tokens/pos are deliberately
            # UN-annotated: two tiny host-fed vectors kept as baseline
            # keepers (with comments) proving the gate bites.
            "data_axis": "model",
            "arg_labels": ("params", "tokens", "caches", "pos"),
            "expected_replication": {
                "params": "Megatron TP layout: matmul weights shard "
                          "over 'model', norm scales/biases/embedding "
                          "remainders replicate by design",
            }}


def _build_prefill_family() -> Dict[str, Any]:
    """The per-prompt-length prefill programs: one compile PER prompt
    length is the serving engine's documented design (docs/SERVING.md) —
    registered allow_recompile=True so the hazard is named, not flagged."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from chainermn_tpu._compat import shard_map
    from chainermn_tpu.parallel.decode import lm_prefill
    from jax.sharding import PartitionSpec as P

    params, specs, mesh = _tiny_lm()
    head_dim = 4
    total = 8

    def prefill(p, pr):
        return lm_prefill(p, pr, total, head_dim=head_dim,
                          axis_name="model")

    jfn = jax.jit(shard_map(
        prefill, mesh=mesh, in_specs=(specs, P()),
        out_specs=(P(), [(P(None, None, "model"),) * 2])))

    p2 = np.zeros((1, 2), np.int32)
    p3 = np.zeros((1, 3), np.int32)
    return {"trace": (lambda p, pr: jfn(p, pr), (params, jnp.asarray(p2))),
            "bound_axes": {"model"},
            "variants": (jfn, [(params, jnp.asarray(p2)),
                               (params, jnp.asarray(p3))]),
            "data_axis": "model",
            "arg_labels": ("params", "prompt"),
            "expected_replication": {
                "params": "Megatron TP layout: matmul weights shard "
                          "over 'model', norm scales/biases/embedding "
                          "remainders replicate by design",
                "prompt": "every TP rank consumes the full prompt "
                          "(vocab-parallel embedding resolves its own "
                          "vocab range)",
            }}


class _traced_obs_state:
    """Context manager: tracer enabled + flight tee installed for the
    duration of ONE entry-point call, prior state restored after — an
    analysis run must not leave process-global observability state
    flipped on for whatever runs next (the lint tier shares its pytest
    process with the whole suite)."""

    def __enter__(self):
        from chainermn_tpu import observability as obs
        from chainermn_tpu.observability import flight
        self._obs, self._flight = obs, flight
        self._was_enabled = obs.enabled()
        obs.enable()
        flight.install_tracer_tee()
        return self

    def __exit__(self, *exc):
        self._flight.uninstall_tracer_tee()
        if not self._was_enabled:
            self._obs.disable()
        return False


class _TracedVariantProbe:
    """Wraps the variant jit function so every probe call runs under
    the scoped tracer+tee state, while still exposing the underlying
    ``_cache_size`` the recompile gate reads."""

    def __init__(self, jfn):
        self._jfn = jfn

    def __call__(self, *a):
        from chainermn_tpu import observability as obs
        from chainermn_tpu.observability import flight
        with _traced_obs_state():
            with obs.span("serving/tick", cat="serving"):
                out = self._jfn(*a)
            flight.note("phase", name="serving/step")
        return out

    def _cache_size(self):
        return self._jfn._cache_size()


def _build_tick_with_tracing() -> Dict[str, Any]:
    """The ISSUE 5 hazard this entry point pins down: the serving tick
    with the TRACER ENABLED and the FLIGHT-RECORDER TEE installed must
    still be ONE compiled program across value variants — observability
    is host-side bookkeeping and must never leak into trace-time (a
    tracer value captured into the jaxpr would both recompile per call
    and be flagged as a tracer leak)."""
    from chainermn_tpu import observability as obs
    from chainermn_tpu.observability import flight

    base = _build_decode_tick()
    fn, args = base["trace"]

    def run_traced(*a):
        with _traced_obs_state():
            with obs.span("serving/tick", cat="serving"):
                out = fn(*a)
            flight.note("phase", name="serving/step")
        return out

    jfn, variant_args = base["variants"]
    return {"trace": (run_traced, args),
            "bound_axes": base["bound_axes"],
            "variants": (_TracedVariantProbe(jfn), variant_args)}


class _RouterTeeProbe:
    """Variant probe for the ROUTER-driven tick: every call runs under
    the scoped tracer+tee state AND the router's per-request emissions
    (dispatch complete-event, per-slot decode-tick complete-events with
    trace ids) — the full fleet observability surface the replica tick
    lives under in production (ISSUE 7)."""

    def __init__(self, jfn):
        self._jfn = jfn

    def __call__(self, *a):
        from chainermn_tpu import observability as obs
        from chainermn_tpu.observability import flight
        with _traced_obs_state():
            t0 = obs.now_us()
            obs.complete_event("router/dispatch", t0, 1,
                               cat="serving_request",
                               trace_id="req-analysis-rt00000000",
                               replica="replica0", prefix_match_len=0)
            with obs.span("serving/tick", cat="serving"):
                out = self._jfn(*a)
            obs.complete_event("request/decode_tick", t0,
                               obs.now_us() - t0, cat="serving_request",
                               trace_id="req-analysis-rt00000000",
                               request=0, slot=0, active=1)
            flight.note("router", event="dispatched",
                        trace_id="req-analysis-rt00000000",
                        replica="replica0")
            flight.note("phase", name="serving/step")
        return out

    def _cache_size(self):
        return self._jfn._cache_size()


def _build_router_tick() -> Dict[str, Any]:
    """The REPLICA decode tick as the serving router drives it (ISSUE
    7): tracer enabled, flight tee installed, router dispatch +
    per-request decode-tick complete-events emitted around the device
    call.  Registered shardflow=True (unlike the plain tracing tee
    variant) so the fleet path's collective bytes are INDEPENDENTLY
    reconciled against the comm ledger — the router hop must add zero
    device traffic and zero compiles: one program across variants."""
    base = _build_decode_tick()
    fn, args = base["trace"]
    probe = _RouterTeeProbe(base["variants"][0])

    def run_routed(*a):
        return probe(*a)

    return {"trace": (run_routed, args),
            "bound_axes": base["bound_axes"],
            "variants": (probe, base["variants"][1]),
            "data_axis": "model",
            "arg_labels": ("params", "tokens", "caches", "pos"),
            "expected_replication": {
                "params": "Megatron TP layout: matmul weights shard "
                          "over 'model', norm scales/biases/embedding "
                          "remainders replicate by design",
                "pos": "per-slot position vector: 4 host-fed bytes "
                       "copied to every TP rank each tick — the same "
                       "replication the base decode-tick entry keeps "
                       "as a baseline keeper",
                # `tokens` deliberately UN-annotated: this entry's
                # keeper finding (with comment) in the regenerated
                # .shardflow-baseline.json proves the replication gate
                # bites on the fleet path too
            }}


def _build_prefix_copy() -> Dict[str, Any]:
    """The prefix cache's copy-on-extend program (ISSUE 7):
    ``DecodeEngine.copy_prefix``'s slab copy over the REAL pool buffers
    at tiny shapes.  The contract under analysis: pure data movement —
    ZERO collectives (each TP rank copies its local columns; the comm
    reconciliation holds it to an empty ledger) and ONE compiled
    program across (src, dst) slot-index variants (the indices are
    traced operands, never static — a recompile per pair would rebuild
    the program on every cache hit)."""
    import jax.numpy as jnp

    from chainermn_tpu.serving.cache_pool import CachePool
    from chainermn_tpu.serving.engine import DecodeEngine

    params, specs, mesh = _tiny_lm()
    head_dim = 4
    n_kv = 2  # _tiny_lm: 2 heads, no GQA
    pool = CachePool(2, 8, 1, n_kv * head_dim, params["embed"].dtype,
                     mesh, "model")
    eng = DecodeEngine(params, pool, mesh, "model", head_dim=head_dim)
    jfn = eng._build_prefix_copy()

    def run(c, src, dst):
        return jfn(c, src, dst)

    # the program takes the pool DONATED (the arrays passed are deleted
    # by the call): every executed variant gets buffers of its own
    args0 = (pool.fresh_buffers(), jnp.int32(0), jnp.int32(1))
    variants = (jfn, [
        args0,
        (pool.fresh_buffers(), jnp.int32(1), jnp.int32(0)),
    ])
    return {"trace": (run, args0),
            "bound_axes": {"model"},
            "variants": variants,
            "data_axis": "model",
            "arg_labels": ("caches", "src", "dst"),
            # `caches` needs no annotation here: unlike the tick
            # registrations' P() feeds, this entry threads the REAL
            # pool buffers, sharded P(None, None, model) — the
            # replication report sees them sharded, which is itself
            # the regression signal (a future P() slip would flag)
            "expected_replication": {
                "src": "source slot index: one host-fed int32 scalar "
                       "per copy, replicated to every TP rank by "
                       "design",
                "dst": "destination slot index: same 4-byte host-fed "
                       "scalar as src",
            }}


def _build_kv_transfer() -> Dict[str, Any]:
    """The disaggregated fleet's same-process KV-slab transfer (ISSUE
    9): ``KvTransferPlane.local_program`` over two REAL pools — a
    prefill worker's staging pool and a decode worker's pool — at tiny
    shapes.  The contract under analysis: slot indices are traced
    operands, so ONE compiled program serves every (src, dst) slot
    pair (a recompile per pair would rebuild it on every transfer),
    and with both pools sharding the KV columns identically the PR 8
    reshard lowers to IDENTITY — zero collectives, held to an empty
    ledger by the comm reconciliation (the lane-mode path books its
    bytes as a noted ``kv_transfer_lane@dcn`` row instead, reconciled
    in tests/test_serving_disagg.py against ``transfer_cost``)."""
    import jax.numpy as jnp

    from chainermn_tpu.serving.cache_pool import CachePool
    from chainermn_tpu.serving.transfer import KvTransferPlane

    params, specs, mesh = _tiny_lm()
    head_dim = 4
    n_kv = 2  # _tiny_lm: 2 heads, no GQA
    dtype = params["embed"].dtype
    staging = CachePool(2, 8, 1, n_kv * head_dim, dtype, mesh, "model")
    decode = CachePool(3, 8, 1, n_kv * head_dim, dtype, mesh, "model")
    plane = KvTransferPlane()
    jfn = plane.local_program(staging, decode)

    def run(src_caches, dst_caches, src, dst):
        return jfn(src_caches, dst_caches, src, dst)

    # the destination pool is donated (never the source): a destination
    # of its own for every executed variant
    args0 = (staging.caches, decode.fresh_buffers(), jnp.int32(0),
             jnp.int32(1))
    variants = (jfn, [
        args0,
        (staging.caches, decode.fresh_buffers(), jnp.int32(1),
         jnp.int32(2)),
        (staging.caches, decode.fresh_buffers(), jnp.int32(0),
         jnp.int32(0)),
    ])
    return {"trace": (run, args0),
            "bound_axes": {"model"},
            "variants": variants,
            "data_axis": "model",
            "arg_labels": ("src_caches", "dst_caches", "src", "dst"),
            # both pools' caches thread in SHARDED P(None, None, model)
            # like the prefix-copy entry; only the host-fed slot scalars
            # replicate by design
            "expected_replication": {
                "src": "source staging-slot index: one host-fed int32 "
                       "scalar per transfer, replicated to every TP "
                       "rank by design",
                "dst": "destination (reserved) slot index: same 4-byte "
                       "host-fed scalar as src",
            }}


def _build_reshard() -> Dict[str, Any]:
    """The portable redistribution primitive (ISSUE 8,
    ``parallel/reshard.py``): BOTH wire-bearing (src, dst) spec pairs —
    S(0)→R (one all_gather) and S(0)→S(1) (one all_to_all) — in ONE
    compiled program, so the shard-flow reconciliation holds the static
    cost of each collective byte-exact against the runtime comm ledger
    (the elastic-resume acceptance: a reshard's cost is never
    invisible).  Spec pairs are static by construction, so value
    variants must reuse the single program."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from chainermn_tpu import topology
    from chainermn_tpu._compat import shard_map
    from chainermn_tpu.parallel.reshard import reshard
    from jax.sharding import PartitionSpec as P

    mesh = topology.make_nd_mesh(("mn",), (1,), jax.devices()[:1])

    def body(t):
        gathered = reshard(t, 0, None, "mn")       # S(0) -> R
        transposed = reshard(t, 0, 1, "mn")        # S(0) -> S(1)
        return gathered, transposed

    jfn = jax.jit(shard_map(
        body, mesh=mesh, in_specs=(P("mn", None),),
        out_specs=(P(), P(None, "mn"))))

    x = np.arange(32, dtype=np.float32).reshape(4, 8)

    def run(v):
        return jfn(jnp.asarray(v))

    variants = (jfn, [(jnp.asarray(x),), (jnp.asarray(x + 1),)])
    return {"trace": (run, (jnp.asarray(x),)),
            "bound_axes": {"mn"},
            "variants": variants,
            # the input rides in SHARDED (that is the primitive's whole
            # point) — the replication report must stay empty here
            "data_axis": "mn", "arg_labels": ("tree",)}


def _build_flight_ring_program() -> Dict[str, Any]:
    """Flight-recorder entry point: the accounted collective ring run
    UNDER the ring tee (comm deltas -> flight events).  Guards the other
    direction of the ISSUE 5 wiring — the accountant's flight tee fires
    from host callbacks only, so the traced program's collective
    sequence and compile count are byte-identical with the recorder
    on."""
    from chainermn_tpu.observability import flight

    base = _build_collective_ring()
    fn, args = base["trace"]

    def run_teed(*a):
        with _traced_obs_state():
            out = fn(*a)
            flight.note("phase", name="collective/ring")
        return out

    return {"trace": (run_teed, args), "bound_axes": base["bound_axes"]}


def _tiny_mlp_fixture():
    """Shared tiny-MLP (params, batch) for the train-step entry points —
    deterministic numpy, no jax PRNG (analysis must trace the same
    program every run)."""
    import numpy as np

    rng = np.random.RandomState(_SEED)
    params = {
        "w1": rng.randn(8, 16).astype(np.float32) / 4,
        "b1": np.zeros((16,), np.float32),
        "w2": rng.randn(16, 4).astype(np.float32) / 4,
        "b2": np.zeros((4,), np.float32),
    }
    batch = (rng.randn(4, 8).astype(np.float32),
             rng.randint(0, 4, (4,)).astype(np.int32))
    return params, batch


def _build_train_step() -> Dict[str, Any]:
    """The PRODUCTION train-step builder (`make_train_step` +
    `create_multi_node_optimizer`/adam) — the program whose replication
    report must name the full optimizer-state replication ZeRO-1
    (ROADMAP item 2) will remove.  Its gradient all-reduce on the default
    path is AUTODIFF-INSERTED and booked via ``comm.note`` — declared
    here as a ``noted`` row (held byte-exact by the reconciliation)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from chainermn_tpu import topology
    from chainermn_tpu.optimizers import create_multi_node_optimizer
    from chainermn_tpu.train import make_train_step

    mesh = topology.make_nd_mesh(("mn",), (1,), jax.devices()[:1])
    params, batch = _tiny_mlp_fixture()

    def loss_fn(p, b):
        x, y = b
        h = jnp.tanh(x @ p["w1"] + p["b1"])
        logits = h @ p["w2"] + p["b2"]
        logp = jax.nn.log_softmax(logits)
        return -jnp.take_along_axis(logp, y[:, None], axis=1).mean()

    optimizer = create_multi_node_optimizer(optax.adam(1e-3), "mn")
    # donate=False: the analyzer calls the step repeatedly on the same
    # buffers (ledger run, then make_jaxpr) — donation would poison them
    step = make_train_step(loss_fn, optimizer, mesh=mesh, donate=False)
    opt_state = optimizer.init(params)

    params_bytes = int(sum(
        np.prod(v.shape) * v.dtype.itemsize
        for v in jax.tree_util.tree_leaves(params)))

    def run(p, s, b):
        return step(p, s, b)

    return {"trace": (run, (params, opt_state, batch)),
            "bound_axes": {"mn"},
            "data_axis": "mn",
            "arg_labels": ("params", "opt_state", "batch"),
            "expected_replication": {
                "params": "data parallelism replicates parameters on "
                          "every replica by definition",
                "opt_state": "FULL optimizer-state replication — the "
                             "exact blowup ZeRO-1 weight-update sharding "
                             "(ROADMAP item 2, arxiv 2004.13336) removes; "
                             "delete this annotation when it lands and "
                             "the report diff goes red→green",
            },
            # the AD-inserted gradient psum, booked by train.py's
            # comm.note at exactly the params' byte size
            "noted": {"grad_allreduce_ad@mn": params_bytes}}


def _build_quantized_train_step() -> Dict[str, Any]:
    """The QUANTIZED train step (ISSUE 14): `make_train_step` +
    `create_multi_node_optimizer(allreduce_grad_dtype='int8',
    error_feedback=True, double_buffering=True)` — the combined
    quantized+double-buffered mode on a tiny MLP at the largest virtual
    axis this process has (2 under the lint tier's 8-device env; degrades
    to 1 on a bare CPU runner, where the ring short-circuits and the
    entry still pins the one-program discipline).

    Contracts under analysis: ONE compiled program across value variants
    (the EF builder binds shard_map lazily per opt-state structure — a
    per-call rebind would recompile every step), the EF residual rows
    SHARDED over the data axis (inner optimizer state stays replicated —
    annotated as the tracked ZeRO-1 debt), and the hand-written int8
    ring schedule held byte-exact: the composite ledger row
    (``quantized_ring_pmean@mn``, compressed-wire convention) is swapped
    for ``quantized_ring_static_groups``'s per-primitive bytes by the
    reconciliation."""
    import jax
    import numpy as np
    import optax

    from chainermn_tpu import topology
    from chainermn_tpu.ops.collective import (quantized_ring_cost,
                                              quantized_ring_static_groups)
    from chainermn_tpu.optimizers import create_multi_node_optimizer
    from chainermn_tpu.train import make_train_step

    ndev = min(2, len(jax.devices()))
    mesh = topology.make_nd_mesh(("mn",), (ndev,), jax.devices()[:ndev])
    params, batch = _tiny_mlp_fixture()
    block, pipeline = 8, 2

    def loss_fn(p, b):
        import jax.numpy as jnp

        x, y = b
        h = jnp.tanh(x @ p["w1"] + p["b1"])
        logits = h @ p["w2"] + p["b2"]
        logp = jax.nn.log_softmax(logits)
        return -jnp.take_along_axis(logp, y[:, None], axis=1).mean()

    optimizer = create_multi_node_optimizer(
        optax.sgd(1e-2, momentum=0.9), "mn",
        allreduce_grad_dtype="int8", error_feedback=True,
        double_buffering=True, quant_block=block,
        quant_pipeline=pipeline, world=ndev)
    # donate=False: the analyzer calls the step repeatedly on the same
    # buffers (ledger run, then make_jaxpr) — donation would poison them
    step = make_train_step(loss_fn, optimizer, mesh=mesh, donate=False,
                           allreduce_grad_dtype="int8",
                           error_feedback=True)
    opt_state = optimizer.init(params)

    n_total = int(sum(np.prod(v.shape)
                      for v in jax.tree_util.tree_leaves(params)))
    spec: Dict[str, Any] = {
        "bound_axes": {"mn"},
        "data_axis": "mn",
        "arg_labels": ("params", "opt_state", "batch"),
        "expected_replication": {
            # `params` deliberately UN-annotated: this entry's keeper
            # finding (with comment) in .shardflow-baseline.json proves
            # the replication gate bites on the quantized path too
            "opt_state.inner": "inner momentum replicates per replica — "
                               "the ZeRO-1 debt, tracked on train.step; "
                               "the EF residual rows (opt_state.ef) are "
                               "the SHARDED exception this entry proves "
                               "out, so they carry NO annotation and the "
                               "report shows them at 0 replicated bytes",
            "opt_state.stale_grads": "the double-buffer's 1-step-stale "
                                     "mean gradients are globally "
                                     "identical by construction — "
                                     "replicated like the params they "
                                     "update",
        },
    }
    if ndev > 1:
        # the hand-written int8 ring: one composite ledger row for the
        # whole gradient bucket, swapped for its per-primitive groups
        spec["composite"] = {
            "quantized_ring_pmean@mn": {
                "ledger_bytes": quantized_ring_cost(
                    n_total, ndev, "int8", block, pipeline)["ledger_bytes"],
                "static_groups": quantized_ring_static_groups(
                    n_total, ndev, "mn", "int8", block, pipeline),
            },
        }

    batch = tuple(np.ascontiguousarray(a[: 2 * ndev]) for a in batch)

    def run(p, s, b):
        return step(p, s, b)

    variants = (step, [
        (params, opt_state, batch),
        ({k: v + 0.01 for k, v in params.items()}, opt_state, batch),
    ])
    spec["trace"] = (run, (params, opt_state, batch))
    spec["variants"] = variants
    return spec


def _build_demo_train_step() -> Dict[str, Any]:
    """The train CLI's demo step (`make_demo_step`): local grads + the
    EXPLICIT accounted ring mean + accounted metric psums — no autodiff-
    inserted collectives at all, so this entry reconciles with zero
    declarations: every ledger row has its equation and vice versa."""
    import jax
    import optax

    from chainermn_tpu import topology
    from chainermn_tpu.train import make_demo_step

    mesh = topology.make_nd_mesh(("mn",), (1,), jax.devices()[:1])
    params, batch = _tiny_mlp_fixture()
    optimizer = optax.sgd(1e-2, momentum=0.9)
    step = make_demo_step(optimizer, mesh=mesh)
    state = (params, optimizer.init(params))

    def run(s, b):
        return step(s, b)

    return {"trace": (run, (state, batch)),
            "bound_axes": {"mn"},
            "data_axis": "mn",
            "arg_labels": ("state", "batch"),
            "expected_replication": {
                "state": "the demo step replicates (params, momentum) "
                         "per replica — same ZeRO-1 debt as train.step, "
                         "tracked there per-argument",
            }}


class _SupervisedTickProbe:
    """Variant probe for the SUPERVISED tick (ISSUE 10): every call
    runs under the scoped tracer+tee state AND one full supervision-
    plane round — heartbeat lease publish, supervisor-side lease read +
    epoch-fence admission, circuit-breaker consult — the host path a
    fleet worker's device call lives under in production.  The health
    plane must add ZERO device traffic and ZERO compiles."""

    def __init__(self, jfn, plane):
        self._jfn = jfn
        self._plane = plane   # (publisher, table, fence, breaker)

    def __call__(self, *a):
        from chainermn_tpu import observability as obs
        from chainermn_tpu.observability import flight
        pub, table, fence, breaker = self._plane
        with _traced_obs_state():
            pub.beat(queue_depth=0, free_slots=1, busy_slots=1)
            with obs.span("serving/tick", cat="serving"):
                out = self._jfn(*a)
            lease = table.read("analysis-worker")
            fence.admit("analysis-worker", lease["epoch"], "lease")
            breaker.allow()
            flight.note("fleet", event="supervisor_tick",
                        worker="analysis-worker",
                        lease_seq=lease["seq"])
            flight.note("phase", name="fleet/supervise")
        return out

    def _cache_size(self):
        return self._jfn._cache_size()


def _build_supervisor_tick() -> Dict[str, Any]:
    """The serving decode tick as a SUPERVISED fleet worker runs it
    (ISSUE 10): heartbeat publish on the loopback lane store, lease
    read + epoch-fence admission + breaker consult on the supervisor
    side, tracer + flight tee installed — all host-side bookkeeping.
    One program across value variants: liveness must never leak into
    trace-time."""
    from chainermn_tpu.serving.health import (CircuitBreaker, EpochFence,
                                              HeartbeatPublisher,
                                              LeaseTable)
    from chainermn_tpu.serving.transfer import InProcessLaneStore

    base = _build_decode_tick()
    fn, args = base["trace"]
    store = InProcessLaneStore()
    fence = EpochFence()
    epoch = fence.new_epoch("analysis-worker")
    plane = (HeartbeatPublisher(store, "analysis-worker", "engine", epoch),
             LeaseTable(store), fence, CircuitBreaker())
    probe = _SupervisedTickProbe(base["variants"][0], plane)

    def run_supervised(*a):
        return probe(*a)

    return {"trace": (run_supervised, args),
            "bound_axes": base["bound_axes"],
            "variants": (probe, base["variants"][1])}


class _AutoscaleTickProbe:
    """Variant probe for the AUTOSCALED tick (ISSUE 11): every call
    runs one full control-loop round around the compiled decode tick —
    degradation-ladder update on an overload pressure signal, tenant
    budget check + admission bookkeeping, and an
    :class:`~chainermn_tpu.serving.autoscale.AutoscalePolicy` decision
    over a synthetic oscillating signal trace (fake receiver clock, so
    the probe is deterministic).  The policy tick is pure host
    bookkeeping: it must add ZERO device traffic and ZERO compiles —
    scaling decisions never leak into trace-time."""

    def __init__(self, jfn, policy, table):
        self._jfn = jfn
        self._policy = policy
        self._table = table
        self._calls = 0

    def __call__(self, *a):
        from chainermn_tpu.observability import flight
        from chainermn_tpu.serving.scheduler import Request

        self._calls += 1
        now = float(self._calls)          # fake receiver clock
        # oscillating synthetic load: hysteresis must absorb it
        backlog = 512 if self._calls % 2 else 0
        self._table.ladder.update(0.5 if backlog else 0.0, now=now)
        tenant = self._table.resolve("analysis-tenant", "best_effort")
        refused = self._table.admission_check(tenant, now=now)
        if refused is None:
            self._table.on_admit(tenant, Request([1], 1), capped=False)
        out = self._jfn(*a)
        dec = self._policy.decide(
            {"live_workers": 1, "backlog_tokens": backlog,
             "queue_depth": 4 if backlog else 0, "shed_rate": 0.0},
            now)
        if dec is not None:
            flight.note("autoscale_decision",
                        **{k: v for k, v in dec.items()
                           if k != "event"})
        flight.note("phase", name="fleet/autoscale_tick")
        return out

    def _cache_size(self):
        return self._jfn._cache_size()


def _build_autoscale_tick() -> Dict[str, Any]:
    """The serving decode tick as the AUTOSCALED fleet runs it
    (ISSUE 11): ladder update + tenant budget bookkeeping + one policy
    decision per call, all host-side.  One program across value
    variants: elasticity must never leak into trace-time."""
    from chainermn_tpu.serving.autoscale import AutoscalePolicy
    from chainermn_tpu.serving.tenancy import TenantTable

    base = _build_decode_tick()
    fn, args = base["trace"]
    policy = AutoscalePolicy(min_workers=1, max_workers=2,
                             up_cooldown_s=3.0, down_cooldown_s=6.0,
                             down_stable_s=6.0)
    table = TenantTable()
    probe = _AutoscaleTickProbe(base["variants"][0], policy, table)

    def run_autoscaled(*a):
        return probe(*a)

    return {"trace": (run_autoscaled, args),
            "bound_axes": base["bound_axes"],
            "variants": (probe, base["variants"][1])}


class _WorkerLaneProbe:
    """Variant probe for the lane LANDING program (ISSUE 10): every
    call runs one worker-lane mailbox round trip (pickled control
    message out, consumed in order on the receiver side) around the
    compiled slab write — the cross-process protocol's host path.  The
    mailbox hop must add zero device traffic and zero compiles."""

    def __init__(self, jfn, sender, receiver):
        self._jfn = jfn
        self._sender = sender
        self._receiver = receiver

    def __call__(self, *a):
        from chainermn_tpu.observability import flight
        with _traced_obs_state():
            self._sender.send({"kind": "install", "epoch": 1,
                               "trace_id": "req-analysis-wl00000000",
                               "tag": "slab/req-analysis-wl00000000"})
            msg = self._receiver.recv()
            out = self._jfn(*a)
            flight.note("worker", event="installed",
                        worker="analysis-decode0",
                        trace_id=msg["trace_id"])
            flight.note("phase", name="worker/step")
        return out

    def _cache_size(self):
        return self._jfn._cache_size()


def _build_worker_lane() -> Dict[str, Any]:
    """The worker lane protocol's device half (ISSUE 10): the
    pool-lifetime compiled slab INJECT program
    (:meth:`KvTransferPlane.inject_program`) that lands every
    cross-process transfer, run under one mailbox round trip per call.
    Contract: pure data movement — ZERO collectives (each TP rank
    writes its local KV columns; held to an empty ledger by the comm
    reconciliation) and ONE compiled program across (slab values, dst
    slot) variants."""
    import jax.numpy as jnp
    import numpy as np

    from chainermn_tpu.serving.cache_pool import CachePool
    from chainermn_tpu.serving.lanes import (MailboxReceiver,
                                             MailboxSender)
    from chainermn_tpu.serving.transfer import (InProcessLaneStore,
                                                KvTransferPlane)

    params, specs, mesh = _tiny_lm()
    head_dim = 4
    n_kv = 2  # _tiny_lm: 2 heads, no GQA
    dtype = params["embed"].dtype
    pool = CachePool(2, 8, 1, n_kv * head_dim, dtype, mesh, "model")
    plane = KvTransferPlane()
    jfn = plane.inject_program(pool)

    rng = np.random.RandomState(_SEED)
    slab = [(jnp.asarray(rng.randn(8, n_kv * head_dim).astype(dtype)),
             jnp.asarray(rng.randn(8, n_kv * head_dim).astype(dtype)))]
    store = InProcessLaneStore()
    probe = _WorkerLaneProbe(
        jfn, MailboxSender(store, "ctl.analysis-decode0"),
        MailboxReceiver(store, "ctl.analysis-decode0"))

    def run(caches, slabs, dst):
        return probe(caches, slabs, dst)

    # the inject program takes the pool donated (not the slabs): buffers
    # of its own for every executed variant
    args0 = (pool.fresh_buffers(), slab, jnp.int32(0))
    variants = (probe, [
        args0,
        (pool.fresh_buffers(), slab, jnp.int32(1)),
    ])
    return {"trace": (run, args0),
            "bound_axes": {"model"},
            "variants": variants,
            "data_axis": "model",
            "arg_labels": ("dst_caches", "slabs", "dst"),
            # dst_caches/slabs thread in SHARDED (P(None, None, model) /
            # P(None, model)); only the host-fed slot scalar replicates
            "expected_replication": {
                "dst": "destination (reserved) slot index: one host-fed "
                       "int32 scalar per landing, replicated to every "
                       "TP rank by design",
            }}


def _is_tracing(args) -> bool:
    """True when ``args`` carry jax tracers (the probe is being traced
    for the jaxpr engine, not called on concrete variant values)."""
    import jax

    return any(isinstance(x, jax.core.Tracer)
               for x in jax.tree_util.tree_leaves(args))


class _KvSpillProbe:
    """Variant probe for the host-RAM spill tier (ISSUE 12): every call
    runs one full spill round trip around the compiled inject program —
    pack the source slot (CRC stamped), put/get through the bounded
    host store, CRC-verified ``unpack_into`` restore into the restore
    slot — and asserts the restore is BYTE-EXACT vs the packed rows
    with its ledger booking equal to the ``transfer_cost`` statics.
    The spill tier is host bookkeeping: one compiled program across
    (slab, slot) variants, zero device-traffic growth."""

    def __init__(self, jfn, pool, plane, spill, length):
        self._jfn = jfn
        self._pool = pool
        self._plane = plane
        self._spill = spill
        self._length = int(length)

    def __call__(self, *a):
        import pickle

        import jax
        import numpy as np

        from chainermn_tpu.observability import flight
        from chainermn_tpu.serving.transfer import (SPILL_AXIS, SPILL_OP,
                                                    transfer_cost)
        if _is_tracing(a):
            # under the jaxpr trace every jax op stages to tracers —
            # the host round trip (device_get inside pack) cannot run;
            # the trace captures the inject program, which is the
            # device contract under analysis
            return self._jfn(*a)
        pool, L = self._pool, self._length
        seq = tuple(range(L))
        with _traced_obs_state():
            payload = self._plane.pack(pool, 0, L,
                                       meta={"seq": list(seq),
                                             "length": L})
            assert self._spill.put(seq, L, payload)
            got = self._spill.get(seq)
            stats = self._plane.unpack_into(
                got, pool, 1, ledger_op=SPILL_OP,
                ledger_axis=SPILL_AXIS)
            want = transfer_cost(pool.n_layers, L, pool.kv_dim,
                                 pool.dtype, mode="lanes")
            assert stats["ledger_bytes"] == want["ledger_bytes"], (
                stats, want)
            # byte-exact round trip: the restored rows ARE the packed
            # rows (the ISSUE 12 acceptance, held here on every call)
            rows = pickle.loads(payload)["rows"]
            for (ks, vs), (kc, vc) in zip(rows, pool.caches):
                np.testing.assert_array_equal(
                    ks, np.asarray(jax.device_get(kc[1, :L])))
                np.testing.assert_array_equal(
                    vs, np.asarray(jax.device_get(vc[1, :L])))
            out = self._jfn(*a)
            flight.note("serving", event="restore", prefix_len=L)
            flight.note("phase", name="serving/spill_restore")
        return out

    def _cache_size(self):
        return self._jfn._cache_size()


def _build_kv_spill() -> Dict[str, Any]:
    """The host-RAM spill tier's device half (ISSUE 12): the SAME
    pool-lifetime compiled inject program every lane transfer lands
    through, here driven by the spill round trip (pack → bounded host
    LRU store → CRC verify → restore).  Contract: one program across
    (slab, dst slot) variants, byte-exact restores, ledger-reconciled
    against ``transfer_cost`` statics — all asserted in-probe on every
    call."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from chainermn_tpu.serving.cache_pool import CachePool
    from chainermn_tpu.serving.spill import HostSpillStore
    from chainermn_tpu.serving.transfer import KvTransferPlane

    params, specs, mesh = _tiny_lm()
    head_dim = 4
    n_kv = 2  # _tiny_lm: 2 heads, no GQA
    dtype = params["embed"].dtype
    pool = CachePool(2, 8, 1, n_kv * head_dim, dtype, mesh, "model")
    # give slot 0 real (random) K/V so the byte-exact check is honest
    # (keep the pool's sharding — an unsharded replacement would make
    # the first inject call compile a second program)
    from jax.sharding import NamedSharding
    sharding = NamedSharding(mesh, pool.cache_spec)
    rng = np.random.RandomState(_SEED)
    pool.caches = [
        (jax.device_put(rng.randn(2, 8, n_kv * head_dim).astype(dtype),
                        sharding),
         jax.device_put(rng.randn(2, 8, n_kv * head_dim).astype(dtype),
                        sharding))]
    plane = KvTransferPlane()
    spill = HostSpillStore(capacity_bytes=1 << 20)
    jfn = plane.inject_program(pool)
    probe = _KvSpillProbe(jfn, pool, plane, spill, length=6)

    def run(caches, slabs, dst):
        return probe(caches, slabs, dst)

    slab = [(jnp.asarray(rng.randn(8, n_kv * head_dim).astype(dtype)),
             jnp.asarray(rng.randn(8, n_kv * head_dim).astype(dtype)))]
    # the probe's own round trip goes through the pool (which threads
    # its donated buffers itself); the inject call it then makes on the
    # variant's operands gets buffers of its own
    args0 = (pool.fresh_buffers(), slab, jnp.int32(0))
    variants = (probe, [
        args0,
        (pool.fresh_buffers(), slab, jnp.int32(1)),
    ])
    return {"trace": (run, args0),
            "bound_axes": {"model"},
            "variants": variants,
            "data_axis": "model",
            "arg_labels": ("dst_caches", "slabs", "dst"),
            "expected_replication": {
                "dst": "restore-slot index: one host-fed int32 scalar "
                       "per restore, replicated to every TP rank by "
                       "design",
            }}


class _RemotePullProbe:
    """Variant probe for the fleet remote-pull path (ISSUE 12): every
    call runs the full cross-worker host protocol around the compiled
    inject program — owner pack (CRC stamped) → object lane put/get →
    RESERVED destination slot → CRC-verified ``unpack_into`` →
    reservation commit → recycle — and asserts the lane booking equals
    the ``transfer_cost(mode="lanes")`` statics the router prices the
    pull decision with.  The pull plane is host bookkeeping: one
    compiled program, reservation invariants intact on every call."""

    def __init__(self, jfn, src_pool, dst_pool, plane, length):
        self._jfn = jfn
        self._src = src_pool
        self._dst = dst_pool
        self._plane = plane
        self._length = int(length)
        self._calls = 0

    def __call__(self, *a):
        from chainermn_tpu.observability import flight
        from chainermn_tpu.serving.transfer import transfer_cost
        if _is_tracing(a):
            # see _KvSpillProbe: the host protocol cannot run under
            # the jaxpr trace; the inject program IS the device half
            return self._jfn(*a)
        self._calls += 1
        L = self._length
        tag = f"pfx/req-analysis-pull{self._calls:08d}"
        with _traced_obs_state():
            payload = self._plane.pack(
                self._src, 0, L,
                meta={"seq": list(range(L)), "length": L})
            self._plane.lane_put(tag, payload)
            slot = self._dst.reserve()
            assert slot is not None
            got = self._plane.lane_get(tag, 5.0)
            stats = self._plane.unpack_into(got, self._dst, slot)
            want = transfer_cost(self._dst.n_layers, L,
                                 self._dst.kv_dim,
                                 self._dst.dtype, mode="lanes")
            assert stats["ledger_bytes"] == want["ledger_bytes"], (
                stats, want)
            self._dst.commit_reservation(slot)
            self._dst.release(slot)      # recycle for the next call
            self._plane.lane_delete(tag)
            out = self._jfn(*a)
            flight.note("fleet", event="remote_pull_done",
                        prefix_len=L)
            flight.note("phase", name="fleet/remote_pull")
        return out

    def _cache_size(self):
        return self._jfn._cache_size()


def _build_remote_pull() -> Dict[str, Any]:
    """The fleet-global KV economy's remote prefix pull (ISSUE 12):
    owner-side pack → object lane → CRC-verified landing into a
    router-reserved slot through the pool-lifetime compiled inject
    program.  Contract: one program across (slab, slot) variants, the
    reservation state machine exercised on every call, lane bytes
    ledger-reconciled against the same ``transfer_cost`` statics the
    router's transfer-vs-re-prefill decision prices in token units."""
    import jax.numpy as jnp
    import numpy as np

    from chainermn_tpu.serving.cache_pool import CachePool
    from chainermn_tpu.serving.transfer import (InProcessLaneStore,
                                                KvTransferPlane)

    params, specs, mesh = _tiny_lm()
    head_dim = 4
    n_kv = 2  # _tiny_lm: 2 heads, no GQA
    dtype = params["embed"].dtype
    owner = CachePool(2, 8, 1, n_kv * head_dim, dtype, mesh, "model")
    dst = CachePool(2, 8, 1, n_kv * head_dim, dtype, mesh, "model")
    plane = KvTransferPlane(transport=InProcessLaneStore())
    jfn = plane.inject_program(dst)
    probe = _RemotePullProbe(jfn, owner, dst, plane, length=5)

    rng = np.random.RandomState(_SEED)
    slab = [(jnp.asarray(rng.randn(8, n_kv * head_dim).astype(dtype)),
             jnp.asarray(rng.randn(8, n_kv * head_dim).astype(dtype)))]

    def run(caches, slabs, dst_slot):
        return probe(caches, slabs, dst_slot)

    # as in the spill entry: the pull lands through ``dst`` itself, the
    # variant's own inject call takes buffers of its own
    args0 = (dst.fresh_buffers(), slab, jnp.int32(0))
    variants = (probe, [
        args0,
        (dst.fresh_buffers(), slab, jnp.int32(1)),
    ])
    return {"trace": (run, args0),
            "bound_axes": {"model"},
            "variants": variants,
            "data_axis": "model",
            "arg_labels": ("dst_caches", "slabs", "dst_slot"),
            "expected_replication": {
                "dst_slot": "reserved destination-slot index: one "
                            "host-fed int32 scalar per landing, "
                            "replicated to every TP rank by design",
            }}


def select_entrypoints(names=None, for_shardflow: bool = False):
    """Resolve ``--entry`` names against the registry — the ONE resolver
    both runners share (``cli.py`` and ``shardflow.main``).

    Returns ``(entrypoints, error)``.  ``names=None`` selects everything
    (minus ``shardflow=False`` entries when ``for_shardflow``).  An
    unknown name is an error, and so is EXPLICITLY naming a
    ``shardflow=False`` entry under ``for_shardflow`` — silently
    analyzing 0 entry points would read as a clean verdict.
    """
    if not names:
        eps = list(ENTRYPOINTS)
        if for_shardflow:
            eps = [ep for ep in eps if getattr(ep, "shardflow", True)]
        return eps, None
    by_name = {ep.name: ep for ep in ENTRYPOINTS}
    unknown = [n for n in names if n not in by_name]
    if unknown:
        return None, (f"unknown entry point(s): {', '.join(unknown)} "
                      f"(known: {', '.join(sorted(by_name))})")
    eps = [by_name[n] for n in names]
    if for_shardflow:
        skipped = [ep.name for ep in eps
                   if not getattr(ep, "shardflow", True)]
        if skipped:
            return None, (
                f"entry point(s) registered shardflow=False — the base "
                f"entry owns their compiled program's shard-flow "
                f"analysis: {', '.join(skipped)}")
    return eps, None


ENTRYPOINTS = [
    EntryPoint(
        name="ops.collective.ring",
        build=_build_collective_ring,
        description="reduce_scatter+all_gather+shift+psum gradient ring "
                    "over axis 'mn' (the train CLI's demo reduction)"),
    EntryPoint(
        name="train.step",
        build=_build_train_step,
        description="make_train_step + MultiNodeOptimizer(adam) on a "
                    "tiny MLP — the production DP step; replication "
                    "report names the optimizer-state blowup ZeRO-1 "
                    "removes (ROADMAP item 2)"),
    EntryPoint(
        name="train.quantized_step",
        build=_build_quantized_train_step,
        description="make_train_step + MultiNodeOptimizer(int8 wire, "
                    "error feedback, double buffering) — the combined "
                    "quantized+double-buffered step (ISSUE 14): one "
                    "program across value variants, EF residual rows "
                    "sharded per rank, the int8 ring schedule "
                    "reconciled byte-exact via its composite "
                    "declaration"),
    EntryPoint(
        name="train.demo_step",
        build=_build_demo_train_step,
        description="the train CLI's demo step: explicit accounted ring "
                    "mean, fully reconciled with no declarations"),
    EntryPoint(
        name="parallel.reshard",
        build=_build_reshard,
        description="portable redistribution primitive: S(0)->R "
                    "(all_gather) + S(0)->S(1) (all_to_all) in one "
                    "compiled program — static reshard cost reconciled "
                    "byte-exact against the comm ledger (ISSUE 8)"),
    EntryPoint(
        name="parallel.decode.lm_decode_tick",
        build=_build_decode_tick,
        description="serving decode tick under shard_map('model') — one "
                    "program for the pool's lifetime"),
    EntryPoint(
        name="serving.prefill_family",
        build=_build_prefill_family,
        allow_recompile=True,
        description="per-prompt-length prefill programs (intentional "
                    "program family, see docs/SERVING.md)"),
    EntryPoint(
        name="serving.router_tick",
        build=_build_router_tick,
        description="replica decode tick under the ROUTER tee: tracer "
                    "+ flight tee + router dispatch/per-request "
                    "emissions — one program, zero extra device "
                    "traffic, bytes reconciled independently of the "
                    "base entry (ISSUE 7)"),
    EntryPoint(
        name="serving.prefix_copy",
        build=_build_prefix_copy,
        description="prefix-cache copy-on-extend slab copy "
                    "(DecodeEngine.copy_prefix): zero collectives, one "
                    "compiled program across (src, dst) slot variants "
                    "(ISSUE 7)"),
    EntryPoint(
        name="serving.kv_transfer",
        build=_build_kv_transfer,
        description="disaggregated KV-slab transfer "
                    "(KvTransferPlane.local_program): one compiled "
                    "program across (src, dst) slot variants, identity "
                    "reshard at matching pool specs — zero collectives, "
                    "bytes ledger-reconciled (ISSUE 9)"),
    EntryPoint(
        name="serving.supervisor_tick",
        build=_build_supervisor_tick,
        shardflow=False,  # same compiled program as the decode tick —
        #                   the base entry owns its shard-flow analysis
        description="serving decode tick under the fleet supervision "
                    "plane: heartbeat lease publish + supervisor lease "
                    "read + epoch-fence admission + breaker consult — "
                    "liveness is host-side bookkeeping: one program, "
                    "zero extra device traffic (ISSUE 10)"),
    EntryPoint(
        name="serving.autoscale_tick",
        build=_build_autoscale_tick,
        shardflow=False,  # same compiled program as the decode tick —
        #                   the base entry owns its shard-flow analysis
        description="serving decode tick under the autoscale control "
                    "loop: degradation-ladder update + tenant budget "
                    "bookkeeping + one AutoscalePolicy decision per "
                    "call over a synthetic oscillating trace — "
                    "elasticity is host-side bookkeeping: one program, "
                    "zero extra device traffic (ISSUE 11)"),
    EntryPoint(
        name="serving.worker_lane",
        build=_build_worker_lane,
        description="cross-process worker lane landing program "
                    "(KvTransferPlane.inject_program) under a mailbox "
                    "round trip per call: zero collectives, one "
                    "compiled program across (slab, dst slot) variants "
                    "(ISSUE 10)"),
    EntryPoint(
        name="serving.kv_spill",
        build=_build_kv_spill,
        shardflow=False,  # same compiled inject program as
        #                   serving.worker_lane — the base entry owns
        #                   its shard-flow analysis
        description="host-RAM spill tier round trip (pack -> bounded "
                    "LRU store -> CRC verify -> compiled restore): one "
                    "program across (slab, slot) variants, byte-exact "
                    "restores ledger-reconciled against transfer_cost "
                    "statics (ISSUE 12)"),
    EntryPoint(
        name="serving.remote_pull",
        build=_build_remote_pull,
        shardflow=False,  # same compiled inject program as
        #                   serving.worker_lane — the base entry owns
        #                   its shard-flow analysis
        description="fleet remote prefix pull (owner pack -> object "
                    "lane -> reserved-slot CRC-verified landing): one "
                    "program, reservation state machine exercised per "
                    "call, lane bytes reconciled against the pricing "
                    "statics (ISSUE 12)"),
    EntryPoint(
        name="serving.tick_with_tracing",
        build=_build_tick_with_tracing,
        shardflow=False,  # same compiled program as the decode tick —
        #                   the base entry owns its shard-flow analysis
        description="serving decode tick with the tracer enabled and "
                    "the flight-recorder tee installed — observability "
                    "must stay host-side: one program, no tracer leak "
                    "(ISSUE 5)"),
    EntryPoint(
        name="observability.flight_ring",
        build=_build_flight_ring_program,
        shardflow=False,  # same compiled program as ops.collective.ring
        description="accounted collective ring under the flight-"
                    "recorder comm tee — the ring records from host "
                    "callbacks only, leaving the traced program "
                    "unchanged (ISSUE 5)"),
]
