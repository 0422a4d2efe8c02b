"""Threaded serving API: submit → handle, streaming tokens, metrics.

:class:`ServingEngine` glues the host scheduler, the slot pool, and the
compiled per-tick programs into the loop a service actually runs::

    eng = ServingEngine(params, head_dim=8, n_slots=4, max_total=64)
    h = eng.submit([3, 1, 4], max_new_tokens=16,
                   on_token=lambda tok, req_id: print(tok))
    eng.start()            # background driver thread (or drive eng.step()
    h.wait(timeout=30)     # synchronously from a test)
    print(h.tokens, h.status, h.ttft_ms)

Each ``step()`` is one engine iteration: expire overdue queued work,
admit (prefill) up to the interleaving bound, LAUNCH one decode tick over
the pool, read back the tick launched a step ago (the device ran it
meanwhile), stream its tokens, evict finished sequences.  Requests
therefore join and leave between ticks — a late submit starts decoding
as soon as a slot frees, while earlier sequences keep running
(iteration-level / continuous batching).

Observability (the PR 1/2 substrate + the ISSUE 5 production triad,
docs/OBSERVABILITY.md):

* per-request PHASE TIMESTAMPS on the handle (``submitted``,
  ``prefill_start``, ``first_token``, ``finished``) — the span data the
  integration test asserts on — mirrored into the tracer as
  ``serving/request/*`` instants (+ a real ``serving/prefill`` /
  ``serving/tick`` span around each device call) when tracing is on;
* **per-request distributed tracing**: every request carries a
  ``trace_id``; queue-wait / prefill / each decode tick become REAL
  tracer spans carrying it, plus one Chrome async flow (``cat
  "serving_request"``, ``id`` = trace id) from submit to finish — so a
  request renders as its own lane in the PR 2 merged Perfetto doc;
* **goodput attribution**: a :class:`~chainermn_tpu.observability.slo
  .GoodputLedger` partitions the engine's wall clock into compute /
  compile / host / queue-wait / stall buckets (sums match wall within
  5% — the acceptance gate), reported via :meth:`metrics`;
* **SLO tracking**: an optional :class:`~chainermn_tpu.observability
  .slo.SLOTracker` observes every TTFT and the rolling tokens/s, firing
  multi-window burn-rate findings down the PR 2 anomaly path;
* **flight recorder**: admissions, evictions, expiries, errors, and
  engine phases tee into the ring, and the engine registers a
  ``serving`` state provider so every debug bundle / ``/statusz`` hit
  carries live queue/slot/request state;
* serving GAUGES through the tracer (``serving/queue_depth``,
  ``serving/active_slots``, ``serving/tokens_per_sec``) so
  ``observability.export.write_prometheus_textfile`` scrapes them with
  everything else, plus :meth:`ServingEngine.metrics` (TTFT p50/p99,
  per-token latency, slot occupancy — O(1)-memory reservoir samples,
  never unbounded lists) as the ``extra_gauges`` / summary-record
  payload;
* optional per-step JSONL via ``observability.export.MetricsWriter``
  (kind ``serving_step`` records + one ``serving_summary``), each a
  versioned record ``observability.read_metrics_jsonl`` validates.
"""

from __future__ import annotations

import sys
import threading
import time
from collections import deque
from typing import Any, Callable, Dict, List, NamedTuple, Optional

import numpy as np

from .. import observability as obs
from ..observability import flight as _flight
from ..observability.slo import GoodputLedger, ReservoirSample, SLOTracker
from ..ops.decode_attention import live_blocks
from ..ops.selective_scan import walked as _scan_walked
from .cache_pool import CachePool
from .engine import DecodeEngine
from .prefix_cache import PrefixCache
from .scheduler import AdmissionError, Request, Scheduler


class _TickInFlight(NamedTuple):
    """A launched tick whose result is not read yet: the device array, the
    rows it ran for — ``{slot: (request, emits)}`` as they stood AT LAUNCH,
    ``emits`` False while the row fed a prompt token whose prediction is
    known — and when it was launched (monotonic seconds; tracer's us)."""
    result: Any
    rows: Dict[int, tuple]
    t_launch: float
    t_launch_us: int


class RequestHandle:
    """Caller's view of one submitted request (thread-safe reads)."""

    def __init__(self, req: Request):
        self._req = req

    @property
    def id(self) -> int:
        return self._req.id

    @property
    def trace_id(self) -> str:
        return self._req.trace_id

    @property
    def status(self) -> str:
        return self._req.status

    @property
    def finish_reason(self) -> Optional[str]:
        return self._req.finish_reason

    @property
    def tokens(self) -> List[int]:
        return list(self._req.tokens)

    @property
    def routes(self) -> list:
        """For a model with expert layers, the experts the serving
        programs chose for the input of each emitted token, an ``(expert
        layers, top_k)`` int32 array a token, as the prefill and the ticks
        read them back; empty otherwise."""
        return list(self._req.routes)

    @property
    def timestamps(self) -> Dict[str, float]:
        return dict(self._req.timestamps)

    @property
    def ttft_ms(self) -> Optional[float]:
        ts = self._req.timestamps
        if "submitted" in ts and "first_token" in ts:
            return (ts["first_token"] - ts["submitted"]) * 1e3
        return None

    @property
    def shed_payload(self) -> Optional[Dict[str, Any]]:
        """The machine-readable ``AdmissionError.to_dict()`` payload
        when a fleet shed this ALREADY-ACCEPTED request (reason
        ``worker_lost``): its disagg prefill worker died mid-transfer
        with no retry budget (ISSUE 9), or its cross-process worker
        missed the lease window with no survivor / spent the failover
        budget (ISSUE 10).  Carries ``retry_after_ms`` — clients honor
        it with ``serving.fleet.submit_with_retry``.  None otherwise."""
        return getattr(self._req, "shed_payload", None)

    def wait(self, timeout: Optional[float] = None) -> bool:
        """Block until the request finishes; True iff it did."""
        return self._req.done_event.wait(timeout)


def _band_pairs(s_real: int, windows) -> int:
    """The (query, key) pairs a prompt of ``s_real`` tokens needs in the
    layers with a window (``windows``: one entry a windowed layer): query
    ``t`` meets ``min(t + 1, W)`` keys."""
    head = np.minimum(windows, s_real)
    return int((head * (head + 1) // 2
                + (s_real - head) * np.asarray(windows)).sum())


def _request_row(req: Request) -> Dict[str, Any]:
    """One JSON-able /requestz row (also the bundle's serving view)."""
    ts = dict(req.timestamps)
    row = {
        "id": req.id,
        "trace_id": req.trace_id,
        "status": req.status,
        "finish_reason": req.finish_reason,
        "slot": req.slot,
        "prompt_len": req.prompt_len,
        "max_new_tokens": req.max_new_tokens,
        "n_tokens": len(req.tokens),
        "timestamps": {k: round(v, 6) for k, v in ts.items()},
        # tenancy columns (ISSUE 11 plane, ISSUE 17 satellite): always
        # present so the table schema is stable — None means the
        # request never crossed a tenant-aware router
        "tenant": req.tenant,
        "priority": getattr(req, "priority", None),
        "rung": getattr(req, "rung", None),
    }
    if "submitted" in ts and "first_token" in ts:
        row["ttft_ms"] = round(
            (ts["first_token"] - ts["submitted"]) * 1e3, 3)
    return row


class ServingEngine:
    """Continuous-batching inference engine over a slot-managed KV pool.

    ``params``: GLOBAL ``init_tp_transformer_lm`` arrays.  Decoding is
    greedy by default; ``submit(temperature=..., rng=...)`` samples
    per-request through the shared tick under the ``lm_generate`` rng
    contract (ISSUE 9; temperature > 0 REQUIRES an explicit key — see
    docs/SERVING.md).
    ``max_total`` bounds each slot's sequence (prompt + generated); a
    request that cannot fit is REJECTED at submit (``AdmissionError``,
    reason ``too_long``), as is any submit while the bounded queue is
    full (``queue_full``) — backpressure is explicit, never buffered.
    """

    def __init__(self, params, *, head_dim: int, n_slots: int = 4,
                 max_total: int = 128, mesh=None, axis_name: str = "model",
                 queue_capacity: int = 16, max_prefills_per_tick: int = 1,
                 prefill_bucket: int = 1, metrics_writer=None,
                 stats_capacity: int = 1024,
                 slo: Optional[SLOTracker] = None,
                 recent_capacity: int = 64,
                 prefix_cache: bool = True,
                 min_prefix_len: int = 2,
                 spill_bytes: int = 32 << 20,
                 arch=None):
        from ..parallel import blocks as _blocks
        from ..parallel.decode import _kv_heads

        # ``arch`` (parallel.blocks.LMArch; None = the GPT-2-style block)
        # names the model's block vocabulary and, with it, what each
        # layer's attention keeps per token: the pool allocates exactly
        # that declaration
        arch = _blocks.resolve(arch)
        n_kv = _kv_heads(params, head_dim, arch)
        dtype = params["embed"].dtype
        # pool and engine share one mesh (created here when not given,
        # like make_lm_generator)
        if mesh is None:
            from ..topology import make_mesh
            mesh = make_mesh(axis_name=axis_name)
        n_layers = len(params["blocks"])
        self.pool = CachePool(
            n_slots, max_total, n_layers, n_kv * head_dim, dtype, mesh,
            axis_name, layout=_blocks.cache_layout(
                arch, n_layers, n_kv * head_dim, axis_name))
        self.engine = DecodeEngine(params, self.pool, mesh, axis_name,
                                   head_dim=head_dim,
                                   prefill_bucket=prefill_bucket, arch=arch)
        self.scheduler = Scheduler(
            queue_capacity, max_total,
            max_prefills_per_tick=max_prefills_per_tick,
            max_positions=self.engine.max_positions)
        # radix-trie prefix cache (ISSUE 7): finished requests donate
        # their slot (busy -> cached, read-only, refcounted); admission
        # scavenges rc==0 entries LRU-first when the free list is empty
        # On a pool with STATE layers a donated slot holds rows for every
        # position but a state for one, its donated length: an entry is
        # usable only whole (``whole_only``), and the spill tier, which
        # packs "rows [0, len)", is refused rather than left to drop it.
        # A RING is the same case: it holds the window's rows before the
        # donated length, and a shorter match has lost some of its own.
        has_state = bool(self.pool.state_bytes_per_slot)
        has_ring = bool(self.pool.ring_bytes_per_slot)
        if has_state and prefix_cache and int(spill_bytes) > 0:
            raise ValueError(
                "this model has 'kda' layers or 'mamba' layers, which keep a "
                "per-slot recurrent state and no rows: the host spill tier "
                "packs rows [0, len) of each buffer and would drop the "
                "state; construct the engine with spill_bytes=0")
        if has_ring and prefix_cache and int(spill_bytes) > 0:
            raise ValueError(
                "this model has windowed attention layers, which declare a "
                "ring (columns, spec, window) of their last `window` rows: "
                "the host spill tier packs rows [0, len) of each buffer "
                "and would drop the ring; construct the engine with "
                "spill_bytes=0")
        self.prefix_cache: Optional[PrefixCache] = None
        if prefix_cache:
            self.prefix_cache = PrefixCache(
                retain_slot=self.pool.retain,
                release_slot=self.pool.unretain,
                evict_slot=self.pool.uncache,
                min_prefix_len=min_prefix_len,
                on_insert=self._on_prefix_insert,
                on_evict=self._on_prefix_evict,
                whole_only=has_state or has_ring)
        # host-RAM spill tier (ISSUE 12): a scavenged rc==0 prefix slot
        # spills its CRC-stamped slab into a bounded LRU host store
        # instead of vanishing; a later matching prompt restores it
        # through the pool-lifetime compiled inject program instead of
        # re-prefilling.  spill_bytes=0 disables the tier.
        self.spill = None
        self._spill_plane = None
        if prefix_cache and int(spill_bytes) > 0:
            from .spill import HostSpillStore
            from .transfer import KvTransferPlane
            self.spill = HostSpillStore(
                capacity_bytes=int(spill_bytes),
                on_evict=self._on_spill_evict)
            self._spill_plane = KvTransferPlane()
        # fleet-economy hooks (ISSUE 12): the cross-process worker
        # announces this engine's cache lifecycle over the mailbox wire
        # so the router's global index can route remote pulls here.
        # ``on_cache_insert(entry)``, ``on_cache_evict(entry, spilled)``,
        # ``on_spill_evict(seq, length)``.
        self.on_cache_insert = None
        self.on_cache_evict = None
        self.on_spill_evict = None
        self.metrics_writer = metrics_writer
        self._running: Dict[int, Request] = {}   # slot -> request
        self._lock = threading.Lock()            # guards _running + stats
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()
        # rolling stats (host floats only).  Latency percentiles come
        # from FIXED-SIZE reservoirs, not unbounded lists: metrics() is
        # O(1) memory however long the serve loop runs (ISSUE 5).
        self.stats_capacity = int(stats_capacity)
        self._ttft_ms = ReservoirSample(self.stats_capacity)
        self._tok_lat_ms = ReservoirSample(self.stats_capacity)
        # decode tick-GAP: wall between consecutive tick starts while
        # work is active — the inter-token latency a decoding request
        # actually experiences.  In a fused engine a prefill between
        # ticks inflates it; on a disagg decode worker it stays tight —
        # the ISSUE 9 acceptance metric (tick_gap p99/p50 collapse).
        self._tick_gap_ms = ReservoirSample(self.stats_capacity)
        self._last_tick_start: Optional[float] = None
        # the tick launched by the last step and not read back yet (the
        # stepping thread's alone), when the last read ended, and the rows
        # computed for a request that had ended before they were read
        self._in_flight: Optional[_TickInFlight] = None
        self._last_collect_end = 0.0
        self._tick_rows_discarded = 0
        # per-slot sampling operands (ISSUE 9): each slot's request rng
        # key + temperature ride every tick; greedy slots carry zeros
        # (their key is never consumed)
        self._slot_keys = np.zeros((self.pool.n_slots, 2), np.uint32)
        self._slot_temps = np.zeros(self.pool.n_slots, np.float32)
        self._tokens_emitted = 0
        self._ticks = 0
        self._occupancy_sum = 0.0
        self._rejected = 0
        self._prefill_tokens_real = 0
        self._prefill_tokens_padded = 0
        # how much of the pool the tick's attention reads: the busy slots'
        # cache blocks at or below their positions (the flash-decode
        # kernels' work list, by its own arithmetic) over the blocks the
        # pool holds (all layers alike, so one is counted)
        self._tick_cache_blocks_read = 0
        self._tick_cache_blocks_total = 0
        self._tick_cache_rows_written = 0
        self._tick_cache_rows_offered = 0
        # the rows those blocks had to hold: each busy slot's own length
        self._tick_cache_rows_live = 0
        # state layers: (busy slot, state layer) pairs a tick moved on —
        # the state it had to read and write is that times a slot's
        # state bytes a layer, as the rows it had to read are the live
        # rows times ``bytes_per_token`` (host arithmetic, both)
        self._tick_state_slots_live = 0
        # rings (windowed layers): the busy slots' ring rows, ``min(pos +
        # 1, W)`` each, summed over ring layers, in rows and in blocks —
        # averaged with the row layers' into ``tick_cache_*``, so that
        # share stays a share
        self._tick_ring_blocks_read = 0
        self._tick_ring_blocks_total = 0
        self._tick_ring_rows_live = 0
        # (window, ring layers that have it): a tick counts a kind once
        self._ring_kinds = [(int(w), int(c)) for w, c in zip(*np.unique(
            self.pool.ring_windows, return_counts=True))]
        # the windowed prefills' needed score work: per real query
        # position the keys in its band, x windowed layers
        self._prefill_band_pairs = 0
        self._prefill_band_pairs_padded = 0     # the same of the padded rows
        # the selective-scan prefills: (real token, scan layer) pairs, and
        # the pairs the scan walks — whole chunks, up to the one that holds
        # the last real token (``ops/selective_scan.py::walked``)
        self._n_scan_layers = sum(
            arch.attn_kind(i) == "mamba" for i in range(n_layers))
        self._prefill_scan_tokens = 0
        self._prefill_scan_tokens_padded = 0
        self._t0 = time.monotonic()
        # goodput attribution: step() partitions its own wall clock, and
        # the gap between steps books as queue_wait (work was waiting)
        # or stall (engine idle) — sums reconcile against wall within 5%
        self.goodput = GoodputLedger()
        self._last_step_end: Optional[float] = None
        self.slo = slo
        # last SLO throughput observation point (tokens, monotonic t):
        # the tracker must see the RECENT rate, not the run-cumulative
        # average a long healthy history would pin above any target
        self._slo_last = (0, self._t0)
        # recently finished requests for /requestz and the debug bundle
        self._recent: deque = deque(maxlen=int(recent_capacity))
        # flight provider: every bundle / statusz hit carries live
        # queue/slot/request state (survives because dump reads it at
        # crash time, not at construction time)
        _flight.register_provider("serving", self.introspect_state)

    # ---- submission ----
    def submit(self, prompt, max_new_tokens: int, *,
               eos_id: Optional[int] = None,
               deadline_s: Optional[float] = None,
               on_token: Optional[Callable[[int, int], None]] = None,
               trace_id: Optional[str] = None,
               temperature: float = 0.0,
               rng=None,
               tenant: Optional[str] = None) -> RequestHandle:
        """Enqueue a generation request; raises :class:`AdmissionError`
        (with ``.reason``) when the queue is full or it can never fit.
        ``on_token(token, request_id)`` streams each token from the
        driver thread as it is emitted; ``deadline_s`` is relative to
        now.  ``trace_id`` lets an upstream hop (the serving router)
        mint the distributed trace identity so its spans and the
        engine's merge into one Perfetto lane.  ``temperature > 0``
        samples this request's tokens through the shared tick and
        REQUIRES an explicit ``rng`` key (the ``lm_generate`` contract:
        a silent default key would draw identical sequences every
        call); greedy requests omit both.  ``tenant`` stamps the
        request's billing identity (ISSUE 11) — budgets and priority
        live at the ROUTER's tenant plane; the engine only carries the
        attribution into /requestz rows and shed payloads."""
        now = time.monotonic()
        prompt = [int(t) for t in np.asarray(prompt).reshape(-1)]
        temperature = float(temperature)
        if temperature > 0.0 and rng is None:
            raise ValueError(
                "temperature > 0 samples tokens and needs an explicit "
                "rng: pass jax.random.PRNGKey(...) (the lm_generate "
                "contract — a silent default key would make every "
                "sampled request draw IDENTICAL token sequences)")
        key = (None if rng is None
               else np.asarray(rng, np.uint32).reshape(2))
        req = Request(prompt, max_new_tokens, eos_id=eos_id,
                      deadline_t=(now + deadline_s
                                  if deadline_s is not None else None),
                      on_token=on_token, trace_id=trace_id,
                      temperature=temperature, rng=key, tenant=tenant)
        # tracer-clock stamp + flow BEGIN before the request becomes
        # visible to the scheduler: with start()'s driver thread, a
        # request can be admitted (even finished) the instant submit()
        # publishes it, and a later 'b' event would postdate its own
        # 'n'/'e' — the queue-wait span reads trace_us at admission
        req.trace_us = {"submitted": obs.now_us()}
        obs.async_event("b", "request", req.trace_id,
                        cat="serving_request", request=req.id,
                        prompt_len=req.prompt_len)
        try:
            # the PADDED prefill length is what must fit the slot (and
            # the learned-pos table) — the scheduler only knows raw
            # lengths, so the bucket-aware check lives here
            s_pad = self.engine.padded_len(req.prompt_len)
            cap = self.pool.max_total
            if self.engine.max_positions is not None:
                cap = min(cap, self.engine.max_positions)
            if s_pad > cap:
                raise AdmissionError(
                    "too_long",
                    f"prompt {req.prompt_len} pads to {s_pad} "
                    f"(prefill_bucket {self.engine.prefill_bucket}), "
                    f"exceeding per-slot capacity {cap}")
            self.scheduler.submit(req, now)
        except AdmissionError as e:
            with self._lock:
                self._rejected += 1
            # close the flow we opened: a rejected request must not
            # leave a dangling async lane
            obs.async_event("e", "request", req.trace_id,
                            cat="serving_request", reason="rejected",
                            admission_reason=e.reason)
            _flight.note("serving", event="rejected", request=req.id,
                         trace_id=req.trace_id, reason=e.reason)
            raise
        obs.instant("serving/request/queued", cat="serving",
                    request=req.id, trace_id=req.trace_id)
        _flight.note("serving", event="queued", request=req.id,
                     trace_id=req.trace_id, prompt_len=req.prompt_len)
        obs.set_gauge("serving/queue_depth", self.scheduler.queue_depth)
        return RequestHandle(req)

    # ---- the engine iteration ----
    def step(self) -> Dict[str, float]:
        """ONE engine iteration: expire → admit/prefill → LAUNCH the next
        tick → READ BACK the tick launched a step ago → emit its tokens →
        evict.  One tick is always in flight while rows owe tokens: the
        device runs tick N+1 (fed tick N's tokens where they are, on the
        device) while the host emits N's, books, takes submissions and
        stages N+2.  A request whose tokens are all emitted or in flight
        launches no row; one that ends where the host cannot foresee it
        (EOS, a deadline) leaves one row behind, whose token is dropped
        (``serving/tick_rows_discarded``).  A step with nothing to launch
        only reads back, so the pipeline drains by itself.
        Returns host-side stats for the iteration (also streamed to the
        JSONL metrics writer when configured).

        Spans: the iteration is one ``serving/step``; its phases are its
        children (``serving/expire``, ``/admit``, ``/prefill``,
        ``/prefix_copy``, ``/spill_restore``, ``/tick``, ``/emit``,
        ``/bookkeeping``), laid at the ``time.monotonic()`` boundaries
        the goodput ledger already takes.  A ``jax.profiler`` session
        sees them on the device trace's clock (docs/OBSERVABILITY.md).
        The body stays in THIS frame under the root span: one more Python
        frame between here and a program's first call made JAX's tracing
        of the prefill programs 40 % slower on the v5e host (PERF.md,
        Findings PR 25).

        Goodput attribution: the whole iteration's wall clock lands in
        ledger buckets — a prefill's device call and the wait for a tick's
        result as ``compute`` (``compile`` on a call that built a new
        program), everything around them, the tick's launch included, as
        ``host``, and the gap since the previous step as
        ``queue_wait`` (work was waiting) or ``stall`` (idle)."""
        with obs.span("serving/step", cat="serving", tick=self._ticks):
            t_step0 = time.monotonic()
            # the gap since the previous step — or, on the FIRST step, since
            # construction/reset: a fleet replica can idle a long time while
            # a sibling compiles, and leaving that window unattributed would
            # swamp its ledger's coverage (ISSUE 7)
            last = (self._last_step_end if self._last_step_end is not None
                    else self._t0)
            gap = t_step0 - last
            if gap > 0:
                had_work = (self.scheduler.queue_depth > 0
                            or self.pool.busy_count > 0)
                self.goodput.add("queue_wait" if had_work else "stall", gap)
            t_host = t_step0               # start of current host segment

            now = time.monotonic()
            with obs.span("serving/expire", cat="serving"):
                for req in self.scheduler.expire_queued(now):
                    self._finish_tracing(req, "deadline")

            # admit up to the interleave bound into free slots; rc==0 cached
            # prefix slots count as free-after-eviction (scavengeable)
            with obs.span("serving/admit", cat="serving"):
                avail = self.pool.free_count
                if self.prefix_cache is not None:
                    avail += self.prefix_cache.evictable_count()
                admitted_batch = self.scheduler.admissions(avail, now)
            for batch_i, req in enumerate(admitted_batch):
                with obs.span("serving/admit", cat="serving",
                              admitted=len(admitted_batch)):
                    slot, entry, mlen = self._match_and_acquire(req)
                    if slot is None:
                        # every scavengeable slot is pinned by EARLIER
                        # admissions in this batch — put THIS request AND
                        # every later one admissions() already popped back
                        # at the queue head (reverse order keeps FIFO;
                        # dropping them would strand their handles un-done
                        # forever); a finishing request unblocks the next
                        # step
                        for later in reversed(admitted_batch[batch_i:]):
                            self.scheduler.requeue_front(later)
                        break
                req.slot = slot
                req.status = "running"
                t_admit = time.monotonic()
                req.timestamps["prefill_start"] = t_admit
                # the queue-wait span, retrospectively: submit → this admit
                t_us = getattr(req, "trace_us", None)
                if t_us is not None and obs.enabled():
                    now_us = obs.now_us()
                    obs.complete_event(
                        "request/queue_wait", t_us["submitted"],
                        now_us - t_us["submitted"], cat="serving_request",
                        trace_id=req.trace_id, request=req.id)
                obs.instant("serving/request/prefill", cat="serving",
                            request=req.id, slot=slot, trace_id=req.trace_id)
                _flight.note("serving", event="admitted", request=req.id,
                             trace_id=req.trace_id, slot=slot)
                # prefix HIT (matched above): copy the cached slot's K/V
                # instead of re-prefilling the shared prefix; the un-cached
                # suffix feeds through the shared decode tick one token per
                # iteration (``req.forced``)
                if entry is not None:
                    req.forced.extend(req.prompt[mlen:])
                    self._set_slot_sampling(slot, req)
                    self.goodput.add("host", t_admit - t_host)
                    t_cp = time.monotonic()
                    try:
                        with obs.span("serving/prefix_copy",
                                      cat="serving_request", request=req.id,
                                      trace_id=req.trace_id, slot=slot,
                                      src_slot=entry.slot, prefix_len=mlen):
                            self.engine.copy_prefix(entry.slot, slot, mlen)
                        t_host = time.monotonic()
                        self.goodput.add("compute", t_host - t_cp)
                    except Exception as e:
                        t_host = time.monotonic()
                        self.goodput.add("compute", t_host - t_cp)
                        self._abort_slot(req, slot)
                        req.finish("error", time.monotonic())
                        _flight.note("serving", event="error",
                                     request=req.id, trace_id=req.trace_id,
                                     error=repr(e))
                        self._finish_tracing(req, "error")
                        print(f"chainermn_tpu.serving: prefix copy for "
                              f"request {req.id} failed: {e!r}",
                              file=sys.stderr)
                        continue
                    _flight.note("serving", event="prefix_hit",
                                 request=req.id, trace_id=req.trace_id,
                                 slot=slot, prefix_len=mlen)
                    with self._lock:
                        self._running[slot] = req
                    # no token yet: the suffix's LAST tick emits the first
                    # one; only the deadline can evict before that
                    self._maybe_evict(req, time.monotonic())
                    continue
                # device-cache miss: the host spill tier may still hold the
                # prefix (ISSUE 12) — restore lands the CRC-verified slab
                # straight into THIS request's slot and feeds the suffix
                # through the shared tick, exactly the copy-on-extend shape
                if self.spill is not None:
                    t_rs = time.monotonic()
                    self.goodput.add("host", t_rs - t_host)
                    with obs.span("serving/spill_restore",
                                  cat="serving_request", request=req.id,
                                  trace_id=req.trace_id, slot=slot):
                        rlen = self._try_restore(req, slot)
                    t_host = time.monotonic()
                    self.goodput.add("compute" if rlen else "host",
                                     t_host - t_rs)
                    if rlen:
                        req.forced.extend(req.prompt[rlen:])
                        self._set_slot_sampling(slot, req)
                        _flight.note("serving", event="restore",
                                     request=req.id, trace_id=req.trace_id,
                                     slot=slot, prefix_len=rlen)
                        with self._lock:
                            self._running[slot] = req
                        self._maybe_evict(req, time.monotonic())
                        continue
                try:
                    # a failed restore attempt above already booked its own
                    # wall and advanced t_host past t_admit — never book a
                    # negative host segment
                    self.goodput.add("host", max(t_admit - t_host, 0.0))
                    compiles_before = self.engine.prefill_compiles
                    s_pad = self.engine.padded_len(req.prompt_len)
                    t_pf = time.monotonic()
                    with obs.span("serving/prefill", cat="serving_request",
                                  request=req.id, trace_id=req.trace_id,
                                  slot=slot, s_real=req.prompt_len,
                                  s_pad=s_pad):
                        first = self.engine.prefill_into_slot(
                            req.prompt, slot, rng=req.rng,
                            temperature=req.temperature)
                    self._set_slot_sampling(slot, req)
                    t_host = time.monotonic()
                    # the engine's own counter says whether THIS call built
                    # a new program — no probing of its cache internals
                    self.goodput.add(
                        "compile" if self.engine.prefill_compiles
                        > compiles_before else "compute", t_host - t_pf)
                except Exception as e:
                    t_host = time.monotonic()
                    self.goodput.add("compute", t_host - t_pf)
                    # never die holding a slot: a failed prefill (engine bug,
                    # OOM, ...) releases the slot and fails THIS request only
                    # — with start() an escaping exception would kill the
                    # background thread and stall every other request, so the
                    # engine sheds the request and keeps serving
                    self._abort_slot(req, slot)
                    req.finish("error", time.monotonic())
                    _flight.note("serving", event="error", request=req.id,
                                 trace_id=req.trace_id, error=repr(e))
                    self._finish_tracing(req, "error")
                    print(f"chainermn_tpu.serving: prefill of request "
                          f"{req.id} failed: {e!r}", file=sys.stderr)
                    continue
                with obs.span("serving/emit", cat="serving", tokens=1):
                    self._keep_routes(req, self.engine.prefill_routes)
                    self._emit(req, first, time.monotonic())
                    with self._lock:
                        self._running[slot] = req
                        self._prefill_tokens_real += req.prompt_len
                        self._prefill_tokens_padded += s_pad
                        self._prefill_band_pairs += _band_pairs(
                            req.prompt_len, self.pool.ring_windows)
                        self._prefill_band_pairs_padded += _band_pairs(
                            s_pad, self.pool.ring_windows)
                        self._prefill_scan_tokens += (
                            req.prompt_len * self._n_scan_layers)
                        self._prefill_scan_tokens_padded += (
                            _scan_walked(req.prompt_len, s_pad)
                            * self._n_scan_layers)
                    self._maybe_evict(req, time.monotonic())

            # one decode tick IN FLIGHT: launch the next tick over the rows
            # that still owe a token, then read back the one launched a step
            # ago, which the device ran meanwhile.  A row's input is the
            # tick-before's result on the device unless the host knows it
            # (a first token, an owed prompt token, a drained pipeline)
            flying = self._in_flight
            with self._lock:
                running = dict(self._running)
            rows: Dict[int, tuple] = {}
            override = np.zeros(self.pool.n_slots, np.int32)
            for slot, req in running.items():
                ahead = flying.rows.get(slot) if flying is not None else None
                emits_ahead = (ahead is not None and ahead[0] is req
                               and ahead[1])
                if len(req.tokens) + emits_ahead >= req.max_new_tokens:
                    # every token it may have is emitted or in flight: no
                    # row; the slot stays its own until that one is read
                    continue
                if req.forced:
                    # a prefix-hit request still owing suffix tokens feeds
                    # the next PROMPT token (its K/V row gets written; the
                    # prediction is known and discarded until the last one)
                    override[slot] = req.forced.popleft()
                elif emits_ahead:
                    override[slot] = -1    # the token in flight, on the device
                else:
                    override[slot] = req.tokens[-1]
                rows[slot] = (req, not req.forced)
            launched = None
            with obs.span("serving/tick", cat="serving", active=len(rows)):
                if rows:
                    live = np.zeros(self.pool.n_slots, bool)
                    live[list(rows)] = True
                    t_tick = time.monotonic()
                    # inter-tick gap, launch to launch: what a decoding
                    # request waits between its tokens — includes any
                    # prefill that ran above (the fused engine's tail;
                    # disaggregation exists to cut it, ISSUE 9).  Locked
                    # with reset_stats: a warm-up reset racing this
                    # read-modify-write could book one warm-up gap into the
                    # measured window (the unguarded-shared-write lint class)
                    read, total = live_blocks(self.pool.pos,
                                              self.pool.max_total, busy=live)
                    ring_read = ring_total = 0
                    for w, n_layers in self._ring_kinds:
                        r, t = live_blocks(self.pool.pos, w, busy=live)
                        ring_read += n_layers * r
                        ring_total += n_layers * t
                    with self._lock:
                        if self._last_tick_start is not None:
                            self._tick_gap_ms.add(
                                (t_tick - self._last_tick_start) * 1e3)
                        self._last_tick_start = t_tick
                        self._tick_cache_blocks_read += read
                        self._tick_cache_blocks_total += total
                        self._tick_cache_rows_written += (
                            len(rows) * self.pool.n_row_buffers)
                        self._tick_cache_rows_offered += (
                            self.pool.n_slots * self.pool.n_row_buffers)
                        self._tick_cache_rows_live += int(np.minimum(
                            self.pool.pos[live], self.pool.max_total - 1
                            ).sum()) + len(rows)
                        self._tick_state_slots_live += (
                            len(rows) * self.pool.n_state_layers)
                        if self._ring_kinds:
                            self._tick_ring_blocks_read += ring_read
                            self._tick_ring_blocks_total += ring_total
                            self._tick_ring_rows_live += \
                                self.pool.ring_rows_live(live)
                    # the tracer's clock is read only for its own Chrome sink
                    t_tick_us = obs.now_us() if obs.enabled() else 0
                    first = self.engine.tick_calls == 0
                    launched = _TickInFlight(
                        self.engine.launch_tick(override, self._slot_keys,
                                                self._slot_temps, live),
                        rows, t_tick, t_tick_us)
                    if first:
                        # a launch is the host's time, but for the first,
                        # which builds the program
                        self.goodput.add("host", t_tick - t_host)
                        t_host = time.monotonic()
                        self.goodput.add("compile", t_host - t_tick)
                else:
                    # a step that launches nothing breaks the tick cadence:
                    # the next gap would measure stall, not inter-token
                    # latency — restart the clock
                    with self._lock:
                        self._last_tick_start = None
                nxt = None
                if flying is not None:
                    t_host, nxt = self._collect_tick(flying, t_host)
            self._in_flight = launched
            if nxt is not None:
                self._emit_tick(flying, nxt, t_host)
                if launched is not None and all(
                        req.finish_reason is not None
                        for req, _ in launched.rows.values()):
                    # every request of the tick in flight ended on the token
                    # just read (EOS, a deadline): nothing will step the
                    # engine for its sake, so read it here and drop its rows
                    self._drain_tick()
                    t_host = time.monotonic()
            active = bool(rows) or flying is not None

            with obs.span("serving/bookkeeping", cat="serving"):
                with self._lock:
                    self._ticks += 1
                    self._occupancy_sum += (self.pool.busy_count
                                            / self.pool.n_slots)
                    stats = {
                        "queue_depth": float(self.scheduler.queue_depth),
                        "active_slots": float(self.pool.busy_count),
                        "tokens_emitted": float(self._tokens_emitted),
                    }
                obs.set_gauge("serving/queue_depth", stats["queue_depth"])
                obs.set_gauge("serving/active_slots", stats["active_slots"])
                el = time.monotonic() - self._t0
                if el > 0:
                    obs.set_gauge("serving/tokens_per_sec",
                                  self._tokens_emitted / el)
                if self.slo is not None and active:
                    # per-step instantaneous rate: tokens since the
                    # previous observation over the elapsed gap (idle
                    # steps don't count — zero demand is not an SLO
                    # violation).  The read-modify-write of _slo_last is
                    # atomic vs reset_stats; the SLO observation happens
                    # OUTSIDE the lock (SLOTracker has its own — nesting
                    # them would order the two locks)
                    now_t = time.monotonic()
                    with self._lock:
                        last_tok, last_t = self._slo_last
                        emitted = self._tokens_emitted
                        self._slo_last = (emitted, now_t)
                    dt = now_t - last_t
                    if dt > 0:
                        self.slo.observe_throughput((emitted - last_tok) / dt)
                if self.metrics_writer is not None:
                    self.metrics_writer.write(
                        {f"serving/{k}": v for k, v in stats.items()},
                        kind="serving_step")
                t_end = time.monotonic()
                self.goodput.add("host", t_end - t_host)
                with self._lock:
                    self._last_step_end = t_end
                # phase stamp: the ring's "last completed unit of work" marker
                # (what explain_bundle names when a serve loop dies mid-flight)
                _flight.note("phase", name="serving/step", tick=self._ticks,
                             active=int(stats["active_slots"]))
            return stats

    def _keep_routes(self, req: Request, routes) -> None:
        """A model with expert layers: the experts the program chose for
        the input of the token about to be emitted (``routes``: None for
        a model without)."""
        if routes is not None:
            req.routes.append(routes)

    def _collect_tick(self, flying: _TickInFlight, t_host: float):
        """Block until ``flying`` has run and read its result: ``(now, next
        token per slot)``.  The wait is the ledger's ``compute``, the host
        segment since ``t_host`` its ``host``."""
        t_wait = time.monotonic()
        self.goodput.add("host", t_wait - t_host)
        nxt = self.engine.collect_tick(flying.result)
        now = time.monotonic()
        self.goodput.add("compute", now - t_wait)
        return now, nxt

    def _emit_tick(self, flying: _TickInFlight, nxt, now: float) -> None:
        """Hand a collected tick's tokens to the requests it ran for — the
        rows as they stood at ITS launch, never ``_running`` as it stands
        now — and evict what finished.  A row whose request ended while it
        was in flight (EOS, a deadline: ends the host cannot foresee) is
        dropped and counted; the engine's ``tick_routes`` are this tick's."""
        # the wall time the engine needed for this tick: read to read while
        # ticks follow each other, launch to read for the first of a run
        dt_ms = (now - max(flying.t_launch, self._last_collect_end)) * 1e3
        self._last_collect_end = now
        recording = obs.enabled() and flying.t_launch_us
        dt_us = obs.now_us() - flying.t_launch_us if recording else 0
        routes = self.engine.tick_routes
        n_rows = len(flying.rows)
        with obs.span("serving/emit", cat="serving", tokens=n_rows):
            for slot, (req, emits) in flying.rows.items():
                if req.finish_reason is not None:
                    with self._lock:
                        self._tick_rows_discarded += 1
                    continue
                if recording:
                    # per-request decode-tick span, launch to read, keyed
                    # by the trace id
                    obs.complete_event(
                        "request/decode_tick", flying.t_launch_us, dt_us,
                        cat="serving_request", trace_id=req.trace_id,
                        request=req.id, slot=slot, active=n_rows)
                if emits:
                    # miss path, or the suffix's last prompt token just
                    # ran: the tick's prediction IS the next real token
                    if routes is not None:
                        self._keep_routes(req, routes[slot])
                    self._emit(req, int(nxt[slot]), now)
                self._tok_lat_ms.add(dt_ms / n_rows)
                self._maybe_evict(req, now)

    def _drain_tick(self) -> None:
        """Read back the tick in flight, if any, and emit its tokens: no
        driver leaves a launched tick unread."""
        flying, self._in_flight = self._in_flight, None
        if flying is None:
            return
        with obs.span("serving/tick", cat="serving", active=0):
            now, nxt = self._collect_tick(flying, time.monotonic())
        self._emit_tick(flying, nxt, now)
        with self._lock:
            self._last_step_end = time.monotonic()

    def _emit(self, req: Request, token: int, now: float) -> None:
        req.tokens.append(int(token))
        if "first_token" not in req.timestamps:
            req.timestamps["first_token"] = now
            ttft = (now - req.timestamps["submitted"]) * 1e3
            with self._lock:
                self._ttft_ms.add(ttft)
            if self.slo is not None:
                self.slo.observe_ttft(ttft)
            obs.instant("serving/request/first_token", cat="serving",
                        request=req.id, trace_id=req.trace_id)
            obs.async_event("n", "first_token", req.trace_id,
                            cat="serving_request",
                            ttft_ms=round(ttft, 3))
        with self._lock:
            self._tokens_emitted += 1
        obs.add_counter("serving/tokens_total", 1)
        if req.on_token is not None:
            req.on_token(int(token), req.id)

    def _set_slot_sampling(self, slot: int, req: Request) -> None:
        """Install the occupant's rng key + temperature as the slot's
        tick operands (zeros for greedy — the key is never consumed)."""
        self._slot_keys[slot] = (req.rng if req.rng is not None
                                 else np.zeros(2, np.uint32))
        self._slot_temps[slot] = np.float32(req.temperature)

    # ---- disaggregation inject face (ISSUE 9) ----
    def install_request(self, req: Request, slot: int,
                        tokens) -> None:
        """Adopt an already-prefilled request whose KV slab the
        transfer plane just landed in ``slot`` (reservation committed
        and ``pool.pos[slot]`` set by the caller): install sampling
        operands, emit the tokens the prefill side already produced
        (the first one stamps TTFT and streams), and start ticking it
        next step.  The decode half of the disaggregated fleet — this
        engine never ran a prefill for ``req``."""
        req.slot = slot
        req.status = "running"
        now = time.monotonic()
        req.timestamps.setdefault("prefill_start", now)
        self._set_slot_sampling(slot, req)
        obs.instant("serving/request/installed", cat="serving",
                    request=req.id, slot=slot, trace_id=req.trace_id)
        _flight.note("serving", event="installed", request=req.id,
                     trace_id=req.trace_id, slot=slot,
                     pos=int(self.pool.pos[slot]))
        for tok in tokens:
            self._emit(req, int(tok), now)
        with self._lock:
            self._running[slot] = req
        self._maybe_evict(req, now)

    def _finish_tracing(self, req: Request, reason: str) -> None:
        """Close the request's async flow + tee the terminal event."""
        obs.async_event("e", "request", req.trace_id,
                        cat="serving_request", reason=reason,
                        n_tokens=len(req.tokens))
        _flight.note("serving", event="finished", request=req.id,
                     trace_id=req.trace_id, reason=reason,
                     n_tokens=len(req.tokens))
        with self._lock:
            self._recent.append(req)

    def _maybe_evict(self, req: Request, now: float) -> None:
        reason = self.scheduler.eviction_reason(req, now)
        if reason is None:
            return
        slot = req.slot
        req.finish(reason, now)
        with self._lock:
            self._running.pop(slot, None)
        self._retire_slot(req, slot)
        obs.instant("serving/request/complete", cat="serving",
                    request=req.id, reason=reason, trace_id=req.trace_id)
        self._finish_tracing(req, reason)

    # ---- slot lifecycle (prefix-cache aware; ISSUE 7) ----
    def _match_and_acquire(self, req: Request):
        """One admission's slot: ``(slot, prefix entry, matched length)``,
        slot ``None`` when every scavengeable slot is pinned by earlier
        admissions of the batch."""
        # match-and-PIN the radix trie BEFORE taking a slot: the
        # acquire below may scavenge an rc==0 cached slot, and an
        # unpinned match would be its own eviction victim — under a
        # saturated pool every donation would be scavenged by the
        # next admission and the cache could never produce a hit
        entry = None
        mlen = 0
        if self.prefix_cache is not None:
            entry, mlen = self.prefix_cache.match(req.prompt)
            if entry is not None:
                self.prefix_cache.retain(entry)
                req.prefix_entry, req.prefix_len = entry, mlen
        slot = self._acquire_slot()
        if slot is None and entry is not None:
            # OUR OWN match is the only scavengeable slot: with no
            # busy slots nothing else will ever free one, so give
            # up the hit rather than stall the pool — unpin and
            # scavenge it like any other cold entry (and back the
            # counters out: this became a miss)
            self.prefix_cache.release(entry)
            self.prefix_cache.hits -= 1
            self.prefix_cache.misses += 1
            self.prefix_cache.tokens_reused -= mlen
            req.prefix_entry, req.prefix_len = None, 0
            entry, mlen = None, 0
            slot = self._acquire_slot()
        return slot, entry, mlen

    def _acquire_slot(self) -> Optional[int]:
        """Free slot, scavenging the LRU unpinned prefix entry when the
        free list is empty — the cache borrows capacity, never owns it."""
        slot = self.pool.acquire()
        if slot is None and self.prefix_cache is not None:
            if self.prefix_cache.evict_lru() is not None:
                slot = self.pool.acquire()
        return slot

    def _abort_slot(self, req: Request, slot: int) -> None:
        """Failed admission: unpin the request's prefix source (if any)
        and return the slot to the free list — never donate K/V that
        was only partially written."""
        if req.prefix_entry is not None and self.prefix_cache is not None:
            self.prefix_cache.release(req.prefix_entry)
            req.prefix_entry = None
        self._slot_temps[slot] = 0.0
        self.pool.release(slot)

    def _retire_slot(self, req: Request, slot: int) -> None:
        """Finished request: unpin its prefix source, then DONATE the
        slot to the prefix cache (busy → cached, rc=0) keyed by every
        token the slot has consumed — ``prompt + generated`` clipped to
        the slot's position — falling back to a plain release when the
        cache dedups the donation or is disabled.  The last generated
        token is consumed only where the request ended under a tick in
        flight (EOS, a deadline): that tick's row for it, which any later
        program on the pool runs after, is the token's own row — and, on
        a state layer, its state — so the slot is booked where the device
        stands."""
        cache = self.prefix_cache
        if req.prefix_entry is not None and cache is not None:
            cache.release(req.prefix_entry)
            req.prefix_entry = None
        # a freed/cached slot keeps ticking (one fixed program): force
        # its discarded garbage row back to the cheap greedy path
        self._slot_temps[slot] = 0.0
        if cache is not None:
            length = int(self.pool.pos[slot])
            seq = list(req.prompt) + list(req.tokens)
            if length >= cache.min_prefix_len \
                    and cache.insert(seq[:length], slot, length) is not None:
                self.pool.cache(slot)
                return
        self.pool.release(slot)

    # ---- KV-economy lifecycle (ISSUE 12): spill tier + fleet hooks ----
    def _on_prefix_insert(self, entry) -> None:
        if self.on_cache_insert is not None:
            self.on_cache_insert(entry)

    def _on_prefix_evict(self, entry) -> None:
        """Fires BEFORE the evicted entry's slot returns to the free
        list: pack its K/V into the host spill tier (so the prefix
        stays restorable), then tell the fleet layer whether the
        eviction demoted (spilled) or dropped the prefix."""
        spilled = self._maybe_spill(entry)
        if self.on_cache_evict is not None:
            self.on_cache_evict(entry, spilled)

    def _maybe_spill(self, entry) -> bool:
        if self.spill is None:
            return False
        try:
            payload = self._spill_plane.pack(
                self.pool, entry.slot, entry.length,
                meta={"seq": list(entry.seq), "length": entry.length})
            ok = self.spill.put(entry.seq, entry.length, payload)
        except Exception as e:  # noqa: BLE001 — a failed spill must
            # never break the eviction it rides on; the prefix just
            # re-prefills like it always did
            _flight.note("serving", event="spill_failed",
                         slot=entry.slot, error=repr(e))
            return False
        if ok:
            _flight.note("serving", event="spill", slot=entry.slot,
                         prefix_len=entry.length,
                         bytes=len(payload),
                         store_bytes=self.spill.bytes_held)
        return ok

    def _on_spill_evict(self, seq, length) -> None:
        if self.on_spill_evict is not None:
            self.on_spill_evict(seq, length)

    def _try_restore(self, req: Request, slot: int) -> int:
        """Restore a spilled prefix directly into the request's own
        slot through the compiled inject program; returns the restored
        prefix length (0 = no usable spill, or the payload failed its
        CRC and the request falls back to a normal prefill).

        The payload may hold MORE rows than the prompt shares with the
        spilled sequence: every row is injected (the program takes no
        length operand), then ``pos`` is clamped to the matched length
        — rows above it are stale-but-unreachable by the standard
        masking argument (the occupant rewrites row ``p`` before its
        own ``pos`` reaches ``p``)."""
        from .transfer import SPILL_AXIS, SPILL_OP

        min_len = (self.prefix_cache.min_prefix_len
                   if self.prefix_cache is not None else 2)
        hit = self.spill.match(req.prompt, min_len=min_len)
        if hit is None:
            return 0
        seq, mlen = hit
        payload = self.spill.get(seq)
        if payload is None:
            return 0
        try:
            self._spill_plane.unpack_into(
                payload, self.pool, slot,
                ledger_op=SPILL_OP, ledger_axis=SPILL_AXIS)
        except ValueError as e:
            # CRC/schema/shape refusal: corrupt spill state is dropped
            # and counted, and the request re-prefills — wrong KV is
            # never served (the ISSUE 12 acceptance)
            self.spill.crc_refusals += 1
            self.spill.drop(seq)
            _flight.note("serving", event="spill_crc_refused",
                         request=req.id, trace_id=req.trace_id,
                         error=str(e))
            return 0
        except Exception as e:  # noqa: BLE001 — inject failure: the
            # pool is unchanged (functional update never assigned);
            # fall back to the normal prefill
            _flight.note("serving", event="restore_failed",
                         request=req.id, trace_id=req.trace_id,
                         error=repr(e))
            return 0
        self.pool.pos[slot] = int(mlen)
        self.spill.restores += 1
        return int(mlen)

    # ---- driving ----
    def run(self, steps_budget: Optional[int] = None,
            drain: bool = True) -> int:
        """Drive ``step()`` until the engine is idle (queue empty, no
        active slots) or ``steps_budget`` iterations elapse; returns the
        number of iterations run.  ``drain=False`` stops at the budget
        even with work pending (the CLI's ``--steps-budget``)."""
        n = 0
        while not self._stop.is_set():
            if steps_budget is not None and n >= steps_budget:
                break
            busy = (self.scheduler.queue_depth > 0
                    or self.pool.busy_count > 0)
            if not busy:
                if drain:
                    break
                time.sleep(0.001)
                continue
            self.step()
            n += 1
        self._drain_tick()
        return n

    def start(self) -> None:
        """Background driver thread (idles when there is no work)."""
        if self._thread is not None:
            return
        self._stop.clear()

        def loop():
            while not self._stop.is_set():
                if (self.scheduler.queue_depth == 0
                        and self.pool.busy_count == 0):
                    time.sleep(0.002)
                    continue
                self.step()

        self._thread = threading.Thread(target=loop, daemon=True,
                                        name="serving-engine")
        self._thread.start()

    def stop(self) -> None:
        """Stop the driver thread; the tick in flight is read back and its
        tokens emitted (from the caller's thread)."""
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=10)
            self._thread = None
        self._drain_tick()

    def close(self) -> None:
        """Retire the engine: stop the driver thread and drop the
        flight/statusz provider registration (which otherwise pins the
        engine — params + KV pool — for the process lifetime and would
        report this dead engine's state as live)."""
        self.stop()
        if _flight._PROVIDERS.get("serving") == self.introspect_state:
            _flight.unregister_provider("serving")

    # ---- metrics ----
    def reset_stats(self) -> None:
        """Zero the rolling serving stats and restart the throughput
        clock — call after warm-up (compiles) so steady-state numbers
        don't absorb one-off costs (benchmark/families/gpt2.py does)."""
        with self._lock:
            self._t0 = time.monotonic()
            self._ttft_ms = ReservoirSample(self.stats_capacity)
            self._tok_lat_ms = ReservoirSample(self.stats_capacity)
            self._tick_gap_ms = ReservoirSample(self.stats_capacity)
            self._last_tick_start = None
            self._tokens_emitted = 0
            self._ticks = 0
            self._occupancy_sum = 0.0
            self._rejected = 0
            self._prefill_tokens_real = 0
            self._prefill_tokens_padded = 0
            self._prefill_scan_tokens = 0
            self._prefill_scan_tokens_padded = 0
            self._tick_cache_blocks_read = 0
            self._tick_cache_blocks_total = 0
            self._tick_cache_rows_written = 0
            self._tick_cache_rows_offered = 0
            self._tick_cache_rows_live = 0
            self._tick_state_slots_live = 0
            self._tick_ring_blocks_read = 0
            self._tick_ring_blocks_total = 0
            self._tick_ring_rows_live = 0
            self._tick_rows_discarded = 0
            self.engine.moe_counts_tick[:] = 0
            self.engine.moe_counts_prefill[:] = 0
            self.pool.calls = self.pool.calls_donated = 0
            self.goodput.reset()
            self._last_step_end = None
            self._slo_last = (0, self._t0)
            if self.prefix_cache is not None:
                # zero the cumulative counters; entries/pins stay (the
                # warm cache IS the steady state a measurement wants)
                pc = self.prefix_cache
                pc.hits = pc.misses = pc.tokens_reused = 0
                pc.insertions = pc.rejected_insertions = 0
                pc.evictions = pc.state_misses = 0
            if self.spill is not None:
                # same discipline: counters reset, spilled payloads stay
                sp = self.spill
                sp.spills = sp.restores = sp.hits = sp.misses = 0
                sp.crc_refusals = sp.evictions = 0
                sp.rejected_oversize = 0

    def _moe_metrics(self) -> Dict[str, float]:
        """The expert layers' routing counters (a model without experts:
        none).  Rows are the tokens served — a tick's busy slots, a
        prompt's real positions; free slots and padding go to no expert
        and are in no count.  ``serving/moe_*``: ticks and prefills together;
        ``serving/moe_tick_*``: the ticks alone (the tick's kernel time
        is read against them)."""
        eng = self.engine
        if not eng.n_counts:
            return {}
        from ..parallel.moe import COUNT_FIELDS
        n = len(COUNT_FIELDS)
        both = eng.moe_counts_tick + eng.moe_counts_prefill
        out = {
            "serving/moe_assignments_total": float(both[0]),
            "serving/moe_assignments_held": float(both[1]),
            "serving/moe_experts_hit": float(both[2]),
            "serving/moe_tick_assignments_held": float(
                eng.moe_counts_tick[1]),
            "serving/moe_tick_experts_hit": float(eng.moe_counts_tick[2]),
        }
        # one key per held expert (every value of metrics() is a float)
        out.update({f"serving/moe_expert_tokens/{i}": float(v)
                    for i, v in enumerate(both[n:])})
        return out

    def metrics(self) -> Dict[str, float]:
        """Host-side serving summary (the Prometheus ``extra_gauges`` /
        summary-record payload).  Each end-to-end metric's direction and
        bound are BENCHMARK.json's to state, not a key name's."""
        pool = self.pool
        n_rings = len(pool.ring_windows)
        ring_row_bytes = (pool.ring_bytes_per_slot
                          // max(int(pool.ring_windows.sum()), 1))

        def per_layer(of_a_row_layer, over_ring_layers):
            if not n_rings:
                return float(of_a_row_layer)
            return float(pool.n_row_layers * of_a_row_layer
                         + over_ring_layers) / (pool.n_row_layers + n_rings)

        with self._lock:
            el = max(time.monotonic() - self._t0, 1e-9)
            out = {
                "serving/tokens_per_sec": self._tokens_emitted / el,
                "serving/tokens_total": float(self._tokens_emitted),
                "serving/ticks": float(self._ticks),
                "serving/queue_depth": float(self.scheduler.queue_depth),
                "serving/active_slots": float(self.pool.busy_count),
                "serving/rejected_total": float(self._rejected),
                # useful over attempted prefill work: a prompt is padded
                # up to a multiple of the engine's ``prefill_bucket``
                "serving/prefill_tokens_real": float(
                    self._prefill_tokens_real),
                "serving/prefill_tokens_padded": float(
                    self._prefill_tokens_padded),
                # the selective-scan layers' share of that: (real token,
                # scan layer) pairs over the pairs the scan walks
                "serving/prefill_scan_tokens": float(
                    self._prefill_scan_tokens),
                "serving/prefill_scan_tokens_padded": float(
                    self._prefill_scan_tokens_padded),
                # read over held: the share of the pool's cache blocks
                # the ticks' attention read — the busy slots' live blocks
                # (a layer of the pool on average: layers that keep rows
                # are all alike, a ring layer reads ``min(pos + 1, W)``
                # rows of its one block a busy slot)
                "serving/tick_cache_blocks_read": per_layer(
                    self._tick_cache_blocks_read,
                    self._tick_ring_blocks_read),
                "serving/tick_cache_blocks_total": per_layer(
                    self._tick_cache_blocks_total,
                    self._tick_ring_blocks_total),
                "serving/tick_cache_rows_live": per_layer(
                    self._tick_cache_rows_live, self._tick_ring_rows_live),
                # written over offered: the new rows the ticks' writers
                # put into the pool — one a busy slot a buffer (every
                # layer's rows and rings, K and V each; not a state) —
                # over one a slot a buffer, what a write of every slot
                # would touch
                "serving/tick_cache_rows_written": float(
                    self._tick_cache_rows_written),
                "serving/tick_cache_rows_offered": float(
                    self._tick_cache_rows_offered),
                # what one token keeps in the pool, all row layers, and
                # what one slot keeps whatever its length, all state
                # layers and all ring layers (gauges)
                "serving/cache_bytes_per_token": float(
                    self.pool.bytes_per_token),
                "serving/cache_state_bytes_per_slot": float(
                    self.pool.state_bytes_per_slot),
                "serving/cache_ring_bytes_per_slot": float(
                    self.pool.ring_bytes_per_slot),
                # what the ticks touch of a pool with rings: the busy
                # slots' ring rows, ``min(pos + 1, W)`` each, summed over
                # ring layers, their bytes, and the bytes of the busy
                # slots' rows in the layers that keep every row; and the
                # windowed prefills' needed (query, key) pairs
                "serving/tick_ring_rows_live": float(
                    self._tick_ring_rows_live),
                "serving/tick_ring_bytes": float(
                    self._tick_ring_rows_live * ring_row_bytes),
                "serving/tick_row_bytes": float(
                    self._tick_cache_rows_live * self.pool.bytes_per_token),
                "serving/prefill_band_pairs": float(
                    self._prefill_band_pairs),
                "serving/prefill_band_pairs_padded": float(
                    self._prefill_band_pairs_padded),
                # what the ticks had to touch of each kind of cache: the
                # busy slots' state (read and written once a tick; other
                # slots' is not touched), and the live rows
                "serving/tick_state_slots_live": float(
                    self._tick_state_slots_live),
                "serving/tick_state_bytes": float(
                    self._tick_state_slots_live
                    * (self.pool.state_bytes_per_slot
                       // max(self.pool.n_state_layers, 1))),
                "serving/tick_latent_bytes": float(
                    self._tick_cache_rows_live * self.pool.bytes_per_token),
                "serving/tick_calls": float(self.engine.tick_calls),
                # of the launches, those made while the tick before was
                # still unread (the device never waited for the host), and
                # the rows computed for a request that had already ended
                "serving/tick_launches_overlapped": float(
                    self.engine.tick_launches_overlapped),
                "serving/tick_rows_discarded": float(
                    self._tick_rows_discarded),
                # program calls that returned the pool's buffers (ticks,
                # prefills, prefix copies, landed slabs), and those after
                # which the buffers passed were deleted: donated, so
                # written in place and not copied first
                "serving/pool_calls": float(self.pool.calls),
                "serving/pool_calls_donated": float(
                    self.pool.calls_donated),
                "serving/slot_occupancy_pct": 100.0 * (
                    self._occupancy_sum / self._ticks if self._ticks
                    else 0.0),
            }
            out.update(self._moe_metrics())
            for name, res in (("ttft", self._ttft_ms),
                              ("token_latency", self._tok_lat_ms),
                              ("tick_gap", self._tick_gap_ms)):
                p50 = res.percentile(50)
                p99 = res.percentile(99)
                if p50 is not None:
                    out[f"serving/{name}_p50_ms"] = p50
                    out[f"serving/{name}_p99_ms"] = p99
            gaps = self._tick_gap_ms.values()
            if len(gaps) >= 2:
                mean = sum(gaps) / len(gaps)
                out["serving/tick_gap_variance_ms2"] = (
                    sum((g - mean) ** 2 for g in gaps) / len(gaps))
        if self.prefix_cache is not None:
            for k, v in self.prefix_cache.stats().items():
                out[f"serving/prefix/{k}"] = v
            # a ring no longer holds a shorter match's rows: the same
            # refusal as ``state_misses``, under the ring's name
            out["serving/prefix/window_misses"] = (
                out["serving/prefix/state_misses"] if n_rings else 0.0)
            out["serving/prefix/cached_slots"] = float(
                self.pool.cached_count)
        if self.spill is not None:
            for k, v in self.spill.stats().items():
                out[f"serving/spill/{k}"] = v
        out.update(self.goodput.gauges("serving/goodput"))
        return out

    # ---- live introspection (/requestz, /statusz, debug bundles) ----
    def requests_table(self) -> Dict[str, Any]:
        """Queued + running + recently finished requests with their
        trace ids and phase timestamps (the /requestz payload)."""
        with self._lock:
            running = [_request_row(r) for r in self._running.values()]
            recent = [_request_row(r) for r in self._recent]
        return {
            "schema": "chainermn_tpu.requestz.v1",
            "queued": [_request_row(r)
                       for r in self.scheduler.queued_requests()],
            "running": running,
            "recent": list(reversed(recent)),  # newest first
        }

    def introspect_state(self) -> Dict[str, Any]:
        """The ``serving`` flight/statusz provider: engine config, slot
        and queue occupancy, compile counts, goodput, SLO state, and
        the request table — everything a postmortem asks first."""
        state: Dict[str, Any] = {
            "n_slots": self.pool.n_slots,
            "max_total": self.pool.max_total,
            "busy_slots": self.pool.busy_count,
            "free_slots": self.pool.free_count,
            "reserved_slots": self.pool.reserved_count,
            "queue_depth": self.scheduler.queue_depth,
            "queue_capacity": self.scheduler.queue_capacity,
            "ticks": self._ticks,
            "tokens_emitted": self._tokens_emitted,
            "rejected": self._rejected,
            "prefill_compiles": self.engine.prefill_compiles,
            "tick_calls": self.engine.tick_calls,
            "tick_in_flight": self._in_flight is not None,
            "prefix_copies": self.engine.prefix_copies,
            "goodput": self.goodput.report(),
            "requests": self.requests_table(),
        }
        if self.prefix_cache is not None:
            state["prefix_cache"] = dict(
                self.prefix_cache.stats(),
                cached_slots=self.pool.cached_count,
                total_refcount=self.prefix_cache.total_refcount())
        if self.spill is not None:
            state["spill"] = self.spill.state()
        if self.slo is not None:
            state["slo"] = self.slo.status()
        return state

    def write_prometheus(self, path: str) -> str:
        """Atomic Prometheus textfile: tracer counters/gauges + the
        serving summary as extra gauges."""
        from ..observability.export import write_prometheus_textfile
        return write_prometheus_textfile(path, extra_gauges=self.metrics())

    def finalize_metrics(self) -> None:
        """Append the ``serving_summary`` JSONL record (clean-exit
        roll-up) when a metrics writer is configured."""
        if self.metrics_writer is not None:
            self.metrics_writer.write(self.metrics(), kind="serving_summary")
