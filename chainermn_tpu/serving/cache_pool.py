"""Slot-managed KV-cache pool for continuous-batching decode.

The pool is the device half of iteration-level scheduling: ONE set of
per-layer flat K/V buffers shaped ``(n_slots, max_total, H_kv·head_dim)``
(the same flat layout ``parallel/decode.py`` streams at full lane
density), allocated once, plus a per-slot int32 write-position vector.
Admitting a request means writing its prefill slab into a free slot's
rows ``[0, s_p)`` and setting ``pos[slot] = s_p``; every decode tick
appends one row per slot at its own ``pos`` (the per-row vector
``ops.kv_cache.cache_append`` path) and advances it; eviction just
returns the slot index to the free list.  Nothing is reallocated and
nothing re-jits: the tick program's operand shapes are fixed for the
pool's lifetime, which is the whole point — a freed slot is recycled by
the NEXT prefill while the other slots keep decoding.

The buffers have a single owner, the pool: every program that returns
them (tick, prefill, prefix copy, the transfer plane's two) takes them
donated, so its ``dynamic_update_slice`` lands in place instead of on a
copy of every buffer, and the arrays bound before the call are deleted by
it.  ``CachePool.update`` / ``CachePool.read`` are the only way a program
reaches them — read, launch and rebind under one lock.

A layer declares one of three kinds of buffer (``parallel/blocks.py::
cache_layout``), and recycling a slot without zeroing it rests on a
different invariant for each:

* ROWS ``(n_slots, max_total, columns)``, one row a token.  *Unreachable
  above ``pos``*: a slot's rows ``> pos`` may hold a previous occupant's
  K/V (a tick writes the BUSY slots' rows alone: a free or cached slot's
  buffers come out of it bit for bit, ``ops/kv_cache.py::write_rows``),
  but every attention read is masked to the occupant's
  own prefix ``[0, pos]``, and row ``p`` is written by the current
  occupant strictly before ``pos`` reaches ``p`` (prefill writes ``[0,
  s_p)``; each tick writes row ``pos`` before attending it).  Stale rows
  are therefore unreachable — asserted token-exactly by the cross-talk
  fuzz in tests/test_serving.py.
* STATE ``(n_slots,) + shape``, one a sequence, overwritten in place (a
  delta-rule or a selective-scan layer's recurrent state and convolution
  window).  There is
  no "above": whatever is written is read by the next token.  *Never
  written unless busy, overwritten whole on admission*: the tick is told
  which slots are busy and leaves every other slot's state bit-identical
  (``ops/kda_step.py``, ``ops/ssm_step.py``), so a cached slot's state stays the state of its
  donated length and a free slot's is nobody's; and every way into a slot
  — a prefill, a prefix copy — writes the WHOLE state (the prefill's own,
  started from zero; the source slot's), so an occupant never reads its
  predecessor's.  ``release`` / ``uncache`` therefore reset ``pos`` alone
  for both kinds.  What cannot be done with a state is slicing it: a slot
  holds rows for every position but a state for ONE, the ``pos`` it
  stands at, which is why a prefix hit on such a layout is usable only at
  the donated length, and why spill and the transfer plane — which pack
  "rows ``[0, len)``" — refuse such a pool (``transfer.py``).
* RING ``(n_slots, W, columns)``, the last ``W`` rows of a sequence (a
  windowed attention layer: a query at position ``q`` sees keys ``q - W <
  k <= q``), position ``p`` at ring row ``p % W``.  *The row a write lands
  on is the one row the next query cannot see.*  The tick writes a row
  for every BUSY slot (and READS only the busy slots' caches), on ring
  row ``pos % W``.  That row holds position ``pos - W``: exactly one
  position OUTSIDE the window of the query at ``pos``, which
  sees ``pos - W + 1 .. pos`` — the row that query's own
  token overwrites before attending.  A free or cached slot's ring is not
  written at all.  So a cached slot's ring serves
  a request that continues at the donated length ``pos`` (and only there:
  as with a state, a shorter prefix has lost rows, ``[m - W, pos - W)``
  for a match of ``m``, so such a pool's prefix cache is ``whole_only``
  too), and a recycled slot's occupant writes ring row ``p % W`` at every
  position ``p`` strictly before a query that can see row ``p % W`` as
  position ``p`` arrives: the prefill writes the rows of ``[max(0, s_p -
  W), s_p)`` and zeros the rest, each tick writes ``pos % W`` before it
  attends, and a query at ``pos < W`` is masked to rows ``[0, pos]``.  The
  argument needs the ring to be EXACTLY ``W`` rows and the mask exactly
  ``q - k < W``: were the ring rounded up, row ``pos % W'`` would hold a
  position inside the window and the write would have to be masked by the
  busy mask, as a state's is.  Spill and the transfer plane refuse a ring
  as they refuse a state.

:class:`SlotAllocator` is the jax-free bookkeeping half (fuzzable
standalone); :class:`CachePool` adds the device buffers.

Transfer-destination reservations (ISSUE 9): the disaggregated fleet
lands finished prefill KV slabs into a DECODE worker's slot, and the
destination must be held from the moment the transfer is chosen until
the slab arrives — otherwise the worker's own admission path (which
admits up to ``min(free_slots, max_prefills_per_tick)``) can take the
slot out from under an in-flight transfer, and a burst of arriving
slabs deadlocks against admission.  Reservations are therefore
FIRST-CLASS allocator state: ``reserve()`` moves a slot free →
reserved (it no longer counts in ``free_count``, so admission can never
see it), ``commit_reservation()`` promotes it to busy when the slab
lands, and ``cancel_reservation()`` returns it to the free list when
the transfer fails (lane fault, dead source worker).  The invariants
are hard errors for the same reason double-release is: a leaked
reservation silently shrinks the pool forever.

Spill-tier extension (ISSUE 12): evicting a cached rc==0 slot no longer
simply frees its K/V — the frontend packs the slab (CRC-stamped
``chainermn_tpu.kv_transfer.v1`` payload) into the bounded host-RAM
spill store (``spill.py``) BEFORE ``uncache`` resets the position, and
a later matching prompt re-lands it through the compiled inject path.
The allocator is untouched by the tier: spill rides the existing
``cached → free`` transition via the prefix cache's pre-evict hook, so
every slot-state invariant below holds unchanged.

Prefix-cache extension (ISSUE 7): a slot now has THREE states, not two
— ``free`` (on the free list), ``busy`` (a live request's K/V), and
``cached`` (a finished request's prompt K/V donated to the radix-trie
prefix cache as a READ-ONLY shared prefix, with a refcount of the
in-flight requests currently built on it).  Cached slots are
*scavengeable* capacity: admission treats an rc==0 cached slot as
free-after-eviction, so the prefix cache can never starve decoding —
it only borrows slots nobody needs yet.  Refcounts are the allocator's
(hard-error) invariants for the same reason double-release is: a leaked
ref pins a slot forever, silently shrinking the pool.
"""

from __future__ import annotations

import contextlib
import itertools
import threading
from typing import Dict, List, Optional

from ..observability import journal as _journal

#: Distinguishes interleaved allocators in ONE process's journal (an
#: in-process fleet runs several engines, each with its own pool).
_ALLOC_IDS = itertools.count()


class SlotAllocator:
    """Free/busy/cached slot bookkeeping: acquire → busy, release →
    recycled, cache → read-only prefix slot (refcounted) until evicted.

    Slots are handed out lowest-index-first (deterministic for tests);
    double-release, foreign releases, and refcount underflow raise — a
    slot leak in a serving loop is silent capacity loss, so the
    invariants are hard errors.
    """

    def __init__(self, n_slots: int):
        if n_slots < 1:
            raise ValueError(f"n_slots must be >= 1, got {n_slots}")
        self.n_slots = int(n_slots)
        self._free: List[int] = list(range(self.n_slots))
        self._busy: set = set()
        self._cached: Dict[int, int] = {}   # slot -> refcount
        self._reserved: set = set()         # in-flight transfer dests
        # the disagg fleet's role-parallel drive reserves from the
        # prefill thread while commit/cancel/release run on the decode
        # thread — every state transition is a compound read-then-write,
        # so the lock is load-bearing, not defensive
        self._lock = threading.Lock()
        # the conformance monitor replays these against the ISSUE 15
        # slot_lifecycle model — op=init carries the universe size
        self._aid = next(_ALLOC_IDS)
        self._jemit("init", n_slots=self.n_slots)

    def _jemit(self, op: str, **fields) -> None:
        _journal.emit("slot", op=op, alloc=self._aid, **fields)

    def acquire(self) -> Optional[int]:
        """Lowest free slot index, or None when the pool is saturated."""
        with self._lock:
            if not self._free:
                return None
            slot = self._free.pop(0)
            self._busy.add(slot)
        self._jemit("acquire", slot=slot)
        return slot

    def release(self, slot: int) -> None:
        with self._lock:
            if slot not in self._busy:
                raise ValueError(
                    f"slot {slot} is not busy (double release or "
                    f"foreign slot); busy={sorted(self._busy)}")
            self._busy.remove(slot)
            # keep the free list sorted so acquisition order is
            # deterministic
            self._free.append(slot)
            self._free.sort()
        self._jemit("release", slot=slot)

    # ---- transfer-destination reservations: free -> reserved -> busy ----
    def reserve(self) -> Optional[int]:
        """Hold the lowest free slot for an in-flight KV transfer, or
        None when the pool is saturated.  A reserved slot is invisible
        to ``acquire``/``free_count`` — admission can never race the
        arriving slab for it."""
        with self._lock:
            if not self._free:
                return None
            slot = self._free.pop(0)
            self._reserved.add(slot)
        self._jemit("reserve", slot=slot)
        return slot

    def commit_reservation(self, slot: int) -> None:
        """The slab landed: promote the reservation to a busy slot."""
        with self._lock:
            if slot not in self._reserved:
                raise ValueError(
                    f"slot {slot} is not reserved (commit "
                    f"without reserve, or double commit); "
                    f"reserved={sorted(self._reserved)}")
            self._reserved.remove(slot)
            self._busy.add(slot)
        self._jemit("commit_reservation", slot=slot)

    def cancel_reservation(self, slot: int) -> None:
        """The transfer failed: return the held slot to the free list."""
        with self._lock:
            if slot not in self._reserved:
                raise ValueError(
                    f"slot {slot} is not reserved (cancel "
                    f"without reserve, or double cancel); "
                    f"reserved={sorted(self._reserved)}")
            self._reserved.remove(slot)
            self._free.append(slot)
            self._free.sort()
        self._jemit("cancel_reservation", slot=slot)

    # ---- prefix-cache faces: busy -> cached(rc) -> free ----
    def cache(self, slot: int) -> None:
        """Donate a busy slot to the prefix cache (read-only, rc=0)."""
        with self._lock:
            if slot not in self._busy:
                raise ValueError(f"slot {slot} is not busy (only a live "
                                 f"request's slot can be donated); "
                                 f"busy={sorted(self._busy)}")
            self._busy.remove(slot)
            self._cached[slot] = 0
        self._jemit("cache", slot=slot)

    def retain(self, slot: int) -> int:
        """Pin a cached slot for one more in-flight reader."""
        with self._lock:
            if slot not in self._cached:
                raise ValueError(f"slot {slot} is not cached; "
                                 f"cached={sorted(self._cached)}")
            self._cached[slot] += 1
            rc = self._cached[slot]
        self._jemit("retain", slot=slot)
        return rc

    def unretain(self, slot: int) -> int:
        with self._lock:
            if slot not in self._cached:
                raise ValueError(f"slot {slot} is not cached; "
                                 f"cached={sorted(self._cached)}")
            if self._cached[slot] <= 0:
                raise ValueError(f"slot {slot} refcount underflow "
                                 f"(double unretain)")
            self._cached[slot] -= 1
            rc = self._cached[slot]
        self._jemit("unretain", slot=slot)
        return rc

    def uncache(self, slot: int) -> None:
        """Evict a cached slot back to the free list (rc must be 0: an
        entry someone is still built on must never be recycled)."""
        with self._lock:
            rc = self._cached.get(slot)
            if rc is None:
                raise ValueError(f"slot {slot} is not cached; "
                                 f"cached={sorted(self._cached)}")
            if rc != 0:
                raise ValueError(f"slot {slot} still has {rc} reader(s); "
                                 f"refusing to evict a pinned prefix")
            del self._cached[slot]
            self._free.append(slot)
            self._free.sort()
        self._jemit("uncache", slot=slot)

    def refcount(self, slot: int) -> Optional[int]:
        return self._cached.get(slot)

    def busy_slots(self) -> List[int]:
        """The busy slots, as a list taken under the lock."""
        with self._lock:
            return list(self._busy)

    @property
    def free_count(self) -> int:
        return len(self._free)

    @property
    def busy_count(self) -> int:
        return len(self._busy)

    @property
    def cached_count(self) -> int:
        return len(self._cached)

    @property
    def reserved_count(self) -> int:
        return len(self._reserved)

    def check_invariants(self) -> None:
        """No leak, no alias: free ∪ busy ∪ cached ∪ reserved is exactly
        {0..n_slots-1}, pairwise disjoint, and every refcount >= 0."""
        free, busy = set(self._free), set(self._busy)
        cached, reserved = set(self._cached), set(self._reserved)
        assert not (free & busy), (free, busy)
        assert not (free & cached), (free, cached)
        assert not (busy & cached), (busy, cached)
        assert not (reserved & (free | busy | cached)), \
            (reserved, free, busy, cached)
        assert free | busy | cached | reserved \
            == set(range(self.n_slots)), (free, busy, cached, reserved)
        assert all(rc >= 0 for rc in self._cached.values()), self._cached


def _is_state(buf) -> bool:
    """A layout declaration of the STATE form ``(shape, dtype, spec)``
    (``parallel/blocks.py::is_state``; this module imports no jax)."""
    return isinstance(buf[0], tuple)


def _is_ring(buf) -> bool:
    """A layout declaration of the RING form ``(columns, spec, window)``."""
    return len(buf) == 3 and not _is_state(buf)


class CachePool:
    """Device-buffer half: per-layer flat cache pools + per-slot positions.

    ``caches`` is the pytree the engine's compiled programs thread
    through: per layer, a tuple of ``(n_slots, max_total, columns)``
    buffers — whatever that layer's attention DECLARES it keeps per token
    (``layout``: per layer a tuple of ``(columns, PartitionSpec)``, from
    ``parallel/blocks.py::cache_layout``) — or of ``(n_slots,) + shape``
    STATE buffers, one a slot, where it declares ``(shape, dtype,
    PartitionSpec)`` (``dtype`` None: the pool's), or of ``(n_slots,
    window, columns)`` RING buffers where it declares ``(columns,
    PartitionSpec, window)``.  Without a ``layout`` every
    layer is the MHA/GQA declaration: a ``(k, v)`` pair of ``kv_dim``
    columns sharded ``P(None, None, axis)`` over the model axis — each
    chip holds only its local heads' columns, exactly the closed-batch
    decoder's TP layout.  A latent-attention layer declares ONE replicated
    buffer.  Prefix copy, spill and transfer walk the same declaration.
    ``pos`` lives HOST-side as numpy (the scheduler reads/writes it every
    tick; shipping it to device happens once per tick as a tiny operand).

    The buffers have ONE owner, this pool.  Every compiled program that
    returns them takes them DONATED and writes in place, so the arrays
    bound before a call are deleted by it; nobody keeps a reference to
    ``caches`` across a call.  A program call goes through :meth:`update`
    (it replaces the buffers) or :meth:`read` (it only reads them): both
    do "read ``caches`` → launch → bind the result" under the pool's
    lock, so two threads on one pool (the disaggregated fleet's role
    drivers) can never hand a program buffers the other has just given
    away.  ``calls`` / ``calls_donated`` count the updates and those that
    did delete what they were given (``serving/pool_calls*``).
    """

    def __init__(self, n_slots: int, max_total: int, n_layers: int,
                 kv_dim: int, dtype, mesh, axis_name: str = "model",
                 layout=None):
        import jax
        import jax.numpy as jnp
        import numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P

        if max_total < 2:
            raise ValueError(f"max_total must be >= 2, got {max_total}")
        self.allocator = SlotAllocator(n_slots)
        self.n_slots = int(n_slots)
        self.max_total = int(max_total)
        self.n_layers = int(n_layers)
        self.kv_dim = int(kv_dim)
        self.axis_name = axis_name
        self.mesh = mesh
        if layout is None:
            pair = P(None, None, axis_name)
            layout = [((self.kv_dim, pair), (self.kv_dim, pair))
                      ] * self.n_layers
        if len(layout) != self.n_layers:
            raise ValueError(f"layout names {len(layout)} layers, the "
                             f"model has {self.n_layers}")
        self.dtype = jnp.dtype(dtype)
        # rows ``(columns, spec)``; state ``(shape, dtype, spec)``; ring
        # ``(columns, spec, window)``
        self.layout = [tuple(
            (tuple(int(n) for n in buf[0]), jnp.dtype(buf[1] or self.dtype),
             buf[2]) if _is_state(buf)
            else (int(buf[0]), buf[1]) + tuple(int(w) for w in buf[2:])
            for buf in bufs) for bufs in layout]
        #: the caches pytree's PartitionSpecs (programs' in/out specs)
        self.cache_specs = [tuple(buf[2] if _is_state(buf) else buf[1]
                                  for buf in bufs) for bufs in self.layout]
        # the first buffer's spec: what a K/V pool's every buffer has
        self.cache_spec = self.cache_specs[0][0]
        self.caches = self.fresh_buffers()
        # held for a program's LAUNCH only (dispatch is asynchronous)
        self._buffers_lock = threading.Lock()
        self.calls = 0           # updates: program calls that returned
        self.calls_donated = 0   # the buffers; those that deleted theirs
        #: bytes one token keeps across all ROW layers (whole model axis)
        self.bytes_per_token = self.dtype.itemsize * sum(
            buf[0] for bufs in self.layout for buf in bufs
            if len(buf) == 2)
        #: bytes one slot keeps across all RING layers, whatever its length
        #: (0: no layer has a window); per layer, the window (0: none) —
        #: ``ring_rows_live`` reads it
        self.ring_bytes_per_slot = self.dtype.itemsize * sum(
            buf[0] * buf[2] for bufs in self.layout for buf in bufs
            if _is_ring(buf))
        self.ring_windows = np.array(
            [bufs[0][2] for bufs in self.layout if _is_ring(bufs[0])],
            np.int64)
        #: layers that keep rows a token (the others: a ring, a state)
        self.n_row_layers = sum(
            any(len(buf) == 2 for buf in bufs) for bufs in self.layout)
        #: buffers a tick writes ONE row a busy slot into: every layer's
        #: rows and rings (K and V each count), not a state
        self.n_row_buffers = sum(
            not _is_state(buf) for bufs in self.layout for buf in bufs)
        #: bytes one slot keeps across all STATE layers, whatever its
        #: length (0: every layer keeps rows), and how many layers those are
        self.state_bytes_per_slot = sum(
            int(np.prod(buf[0])) * buf[1].itemsize
            for bufs in self.layout for buf in bufs if _is_state(buf))
        self.n_state_layers = sum(
            any(_is_state(buf) for buf in bufs) for bufs in self.layout)
        # host-side per-slot NEXT-WRITE position (== sequence length so
        # far).  The tick runs EVERY slot (one fixed program) but only a
        # BUSY slot's position advances (``advance``) and only a busy
        # slot is written: the tick is given the busy mask, and a free,
        # cached or reserved slot's rows, ring and state come back bit
        # for bit (``ops/kv_cache.py::write_rows``, ``ops/kda_step.py``,
        # ``ops/ssm_step.py``).
        self.pos = np.zeros(self.n_slots, np.int32)

    def fresh_buffers(self):
        """A zeroed caches pytree as the layout declares it, placed like
        the pool's own (what ``__init__`` binds; the analysis entries
        give each call variant of a donating program its own)."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding

        def zeros(buf):
            if _is_state(buf):
                shape, dtype, spec = buf
                z = jnp.zeros((self.n_slots,) + shape, dtype)
            else:
                rows = buf[2] if _is_ring(buf) else self.max_total
                z = jnp.zeros((self.n_slots, rows, buf[0]), self.dtype)
                spec = buf[1]
            return jax.device_put(z, NamedSharding(self.mesh, spec))

        return [tuple(zeros(buf) for buf in bufs) for bufs in self.layout]

    def ring_rows_live(self, slots=None) -> int:
        """Σ over ring layers and ``slots`` (a mask; None: every slot) of
        ``min(pos + 1, W)``: the ring rows a tick's attention has to read
        for them (host arithmetic on ``pos``)."""
        import numpy as np

        pos = self.pos if slots is None else self.pos[slots]
        return int(np.minimum(pos[None, :] + 1,
                              self.ring_windows[:, None]).sum())

    def update(self, launch, *sources):
        """Run a program that RETURNS the buffers: ``launch(caches,
        *source_caches)`` dispatches it and gives ``(result, new
        caches)``; the new buffers are bound and ``result`` returned.
        ``sources`` are pools the same program only reads (a transfer's
        staging pool): their locks are held too, in one global order."""
        with contextlib.ExitStack() as held:
            for p in sorted({id(p): p for p in (self,) + sources}.values(),
                            key=id):
                held.enter_context(p._buffers_lock)
            old = self.caches
            result, self.caches = launch(old, *(p.caches for p in sources))
            self.calls += 1
            # one leaf says it (O(1), no wait on the device): a backend
            # that declines the donation copies, and shows here
            self.calls_donated += bool(old[0][0].is_deleted())
        return result

    def read(self, launch):
        """Run something that only READS the buffers (a slice for the
        wire, a spill): ``launch(caches)`` under the lock, so no update
        deletes them between the read and the dispatch."""
        with self._buffers_lock:
            return launch(self.caches)

    def busy_mask(self):
        """``(n_slots,) bool``: the slots a request holds right now."""
        import numpy as np

        mask = np.zeros(self.n_slots, bool)
        mask[self.allocator.busy_slots()] = True
        return mask

    def advance(self, busy) -> None:
        """One tick happened: every BUSY slot (``busy``: the mask the tick
        ran with) consumed a token.  Out of place — a
        dispatched program may still read the old vector.  (The tick used
        to advance every slot without bound; past a learned position table
        a free slot then read NaN into its last row and the next occupant
        served the sentinel: PERF.md, Findings PR 24.)"""
        import numpy as np

        self.pos = self.pos + busy.astype(np.int32)

    # thin faces over the allocator (the frontend talks to the pool)
    def acquire(self) -> Optional[int]:
        return self.allocator.acquire()

    def release(self, slot: int) -> None:
        # ``pos`` alone: the slot's rows are unreachable above it, its
        # state is written whole by whatever admits the next occupant, and
        # so are the rows of a ring that occupant can see
        self.pos[slot] = 0
        self.allocator.release(slot)

    # transfer-destination reservations (ISSUE 9).  The committing
    # caller (the KV-transfer plane) sets ``pos[slot]`` itself — the
    # landed slab's length is transfer metadata the pool cannot know.
    def reserve(self) -> Optional[int]:
        return self.allocator.reserve()

    def commit_reservation(self, slot: int) -> None:
        self.allocator.commit_reservation(slot)

    def cancel_reservation(self, slot: int) -> None:
        self.pos[slot] = 0
        self.allocator.cancel_reservation(slot)

    # prefix-cache faces.  A cached slot's ``pos`` is deliberately NOT
    # reset, and held (only a busy slot's advances): a tick writes
    # nothing of a slot that is not busy, so the read-only rows [0,
    # length) the copy-on-extend path reads, the ring and the STATE stay
    # as donated — the state at ``pos``, the one length a hit on it can
    # be used at.
    def cache(self, slot: int) -> None:
        self.allocator.cache(slot)

    def uncache(self, slot: int) -> None:
        self.pos[slot] = 0        # as ``release``: ``pos`` alone
        self.allocator.uncache(slot)

    def retain(self, slot: int) -> int:
        return self.allocator.retain(slot)

    def unretain(self, slot: int) -> int:
        return self.allocator.unretain(slot)

    @property
    def free_count(self) -> int:
        return self.allocator.free_count

    @property
    def busy_count(self) -> int:
        return self.allocator.busy_count

    @property
    def cached_count(self) -> int:
        return self.allocator.cached_count

    @property
    def reserved_count(self) -> int:
        return self.allocator.reserved_count
