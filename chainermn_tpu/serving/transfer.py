"""KV-transfer plane: ship a finished prefill's KV slab between pools.

The disaggregation primitive (ISSUE 9, ROADMAP item 4): a PREFILL
worker computes a prompt's K/V slab into a staging slot of its own
:class:`~chainermn_tpu.serving.cache_pool.CachePool`; this plane moves
that slab — plus the request metadata riding with it — into a DECODE
worker's reserved slot.  Two transports, one contract:

* **Same-process** (:meth:`KvTransferPlane.transfer_local`): ONE
  compiled program per (src-pool, dst-pool) shape pair — slot row out
  of the source caches, through the PR 8 redistribution primitive
  (``parallel/reshard.py::reshard``: each (src, dst) cache-spec pair
  lowers to its MINIMAL collective — identity when both pools shard
  the KV columns the same way, one accounted all_to_all if they ever
  differ), ``dynamic_update_slice`` into the destination slot.  Slot
  indices are traced operands, so every transfer after the first hits
  the jit cache (the ``serving.kv_transfer`` analysis entry point
  asserts one program across src/dst variants and reconciles its
  collective bytes against the comm ledger).
* **Cross-process** (:meth:`pack` → a DCN object lane →
  :meth:`unpack_into`): the slab's written rows ``[0, pos)`` are
  serialized with the request wire dict and shipped over the hardened
  KV-store lanes (``communicators/base.py::lane_call`` — retry/backoff
  on transients, loud :class:`~chainermn_tpu.communicators.base
  .DcnLaneError` NAMING the lane otherwise), then injected through a
  pool-lifetime compiled slab write on the receiving side.  Every lane
  transfer books its RAW slab bytes in the comm ledger as a noted
  ``kv_transfer_lane@dcn`` row — the same number
  :func:`transfer_cost` predicts statically, held byte-exact by
  tests/test_serving_disagg.py.

Correctness of the full-row copy without a length operand: rows beyond
the prompt's ``pos`` carry the source slot's stale K/V, but they land
ABOVE the destination occupant's position and are unreachable by the
standard per-slot masking argument (cache_pool.py module docstring) —
the same reasoning that makes slot recycling and the prefix-cache copy
exact, asserted token-exactly by the disagg fuzz tests.
"""

from __future__ import annotations

import pickle
import threading
import time
import zlib
from typing import Any, Dict, Optional

import numpy as np

#: Wire schema of one packed transfer (bump on layout change — a
#: receiver must refuse a slab it cannot interpret, never guess).
WIRE_SCHEMA = "chainermn_tpu.kv_transfer.v1"

#: The ledger key every lane-mode transfer books under (op@axis) — the
#: shard-flow reconciliation joins on it.
LANE_OP = "kv_transfer_lane"
LANE_AXIS = "dcn"

#: The ledger key a host-RAM spill RESTORE books under (ISSUE 12): the
#: same payload format and inject program as a lane transfer, but the
#: slab never crossed DCN — it round-tripped through the local spill
#: tier, so pricing it as DCN traffic would corrupt the wire-byte gate.
SPILL_OP = "kv_spill_restore"
SPILL_AXIS = "host"


def slab_crc32(rows) -> int:
    """CRC32 over the packed slab's raw K/V bytes, in layer order (K
    then V per layer) — the end-to-end integrity stamp every
    ``chainermn_tpu.kv_transfer.v1`` payload carries (ISSUE 12).  The
    checksum covers the KV numbers themselves, so a slab corrupted
    anywhere between :meth:`KvTransferPlane.pack` and
    :meth:`KvTransferPlane.unpack_into` (lane store, host spill tier,
    a bad DIMM) is REFUSED at landing rather than silently decoded
    into wrong-but-plausible tokens."""
    crc = 0
    for layer in rows:      # a layer's buffers in declared order: (k, v),
        for buf in layer:   # or one latent buffer
            crc = zlib.crc32(np.ascontiguousarray(buf).tobytes(), crc)
    return crc & 0xFFFFFFFF


def _widths(pool):
    """The pool's declaration as plain data: per layer, the columns of
    each buffer (``[(kv_dim, kv_dim), ...]`` for a K/V pool).  Every
    program and payload of this plane starts here, and a pool that holds
    per-slot STATE is refused here: the plane moves "rows ``[0, len)`` of
    every declared buffer", and a state is no rows — moved so it would be
    silently dropped, and the receiver would decode from a stale one."""
    if getattr(pool, "state_bytes_per_slot", 0):
        raise ValueError(
            "the KV-transfer plane (local transfer, pack/unpack, the host "
            "spill tier) moves rows [0, len) of each buffer; this pool "
            "holds per-slot state of 'kda' layers or 'mamba' layers (a "
            "recurrent state and a convolution window), which it would "
            "drop — refused")
    if getattr(pool, "ring_bytes_per_slot", 0):
        raise ValueError(
            "the KV-transfer plane (local transfer, pack/unpack, the host "
            "spill tier) moves rows [0, len) of each buffer; this pool "
            "holds a ring declaration (columns, spec, window) for its "
            "windowed attention layers — the last `window` rows of a "
            "sequence at row p % window — which is no such rows: refused")
    return [tuple(w for w, _ in bufs) for bufs in pool.layout]


def _shard_axis_of(spec, axis_name: str) -> Optional[int]:
    """The logical axis a pool's cache PartitionSpec shards over
    ``axis_name`` — the glue into ``reshard``'s spec language (None =
    replicated)."""
    for i, s in enumerate(tuple(spec)):
        names = s if isinstance(s, tuple) else (s,)
        if axis_name in [n for n in names if n is not None]:
            return i
    return None


def slab_nbytes(n_layers: int, length: int, kv_dim: int, dtype) -> int:
    """RAW K/V payload bytes of one transferred slab: 2 (K and V) ×
    layers × written rows × kv_dim — the ledger-convention number
    (pickle framing excluded; the wire adds a few % on top)."""
    item = np.dtype(dtype).itemsize
    return 2 * int(n_layers) * int(length) * int(kv_dim) * item


def transfer_cost(n_layers: int, length: int, kv_dim: int, dtype, *,
                  mode: str, axis_size: int = 1,
                  src_spec: Optional[int] = 2,
                  dst_spec: Optional[int] = 2,
                  copy_rows: Optional[int] = None) -> Dict[str, Any]:
    """Static prediction of one transfer's comm-ledger booking — the
    number the runtime must reproduce byte-exactly (the shard-flow
    discipline applied to the transfer plane).

    ``mode="local"``: the compiled same-process path — per-(K|V)-row
    :func:`~chainermn_tpu.parallel.reshard.reshard_cost` of the
    (1, copy_rows, kv_dim) block between the two pools' cache specs
    (zero when they match, one all_to_all per row otherwise).
    ``mode="lanes"``: the DCN object-lane path — :func:`slab_nbytes`
    of the written rows, booked as one noted ``kv_transfer_lane@dcn``
    row per transfer.
    """
    if mode == "lanes":
        nbytes = slab_nbytes(n_layers, length, kv_dim, dtype)
        return {"mode": mode, "primitive": LANE_OP,
                "ledger_bytes": nbytes, "wire_bytes": nbytes,
                "messages": 1}
    if mode != "local":
        raise ValueError(f"mode must be 'local' or 'lanes', got {mode!r}")
    from ..parallel.reshard import reshard_cost

    rows = int(copy_rows if copy_rows is not None else length)
    total = {"mode": mode, "primitive": None, "ledger_bytes": 0,
             "wire_bytes": 0, "messages": 0}
    for _ in range(2 * int(n_layers)):
        c = reshard_cost((1, rows, int(kv_dim)), dtype, src_spec,
                         dst_spec, axis_size)
        total["ledger_bytes"] += c["ledger_bytes"]
        total["wire_bytes"] += c["wire_bytes"]
        total["messages"] += c["messages"]
        if c["primitive"]:
            total["primitive"] = c["primitive"]
    return total


class InProcessLaneStore:
    """Loopback object-lane transport: the single-process stand-in for
    the jax.distributed KV store (``XlaCommunicator``'s client), with
    the same put/get/delete face the cross-process deployment wires in.
    Faults are injected through ``lane_call``'s injector, NOT here —
    the chaos tests exercise the real retry/classification path."""

    def __init__(self):
        self._store: Dict[str, bytes] = {}
        self._cv = threading.Condition()

    def put(self, tag: str, payload: bytes) -> None:
        with self._cv:
            self._store[str(tag)] = bytes(payload)
            self._cv.notify_all()

    def get(self, tag: str, timeout_s: float = 10.0) -> bytes:
        deadline = time.monotonic() + float(timeout_s)
        with self._cv:
            while str(tag) not in self._store:
                left = deadline - time.monotonic()
                if left <= 0:
                    raise TimeoutError(
                        f"kv transfer tag {tag!r} not published within "
                        f"{timeout_s}s (deadline exceeded)")
                self._cv.wait(left)
            return self._store[str(tag)]

    def delete(self, tag: str) -> None:
        with self._cv:
            self._store.pop(str(tag), None)

    def tags(self):
        """Snapshot of every published tag — the supervisor's orphan
        sweep face (ISSUE 12): a slab tag left by a worker that died
        between pack-publish and install-ack is visible here, owned by
        nobody, and must eventually be GC'd."""
        with self._cv:
            return list(self._store)


class KvTransferPlane:
    """The transfer-plane object a disaggregated fleet shares.

    ``transport``: an object-lane (put/get/delete) for the cross-
    process path — :class:`InProcessLaneStore` by default; a
    multi-controller deployment passes the communicator-backed lanes
    (``CommunicatorBase.kv_lane_transport()``).  The local compiled
    path needs no transport and is used whenever source and
    destination pools share a mesh.
    """

    def __init__(self, transport=None, lane_config=None):
        self.transport = transport or InProcessLaneStore()
        self.lane_config = lane_config
        self._programs: Dict[Any, Any] = {}   # local-path program cache
        self._inject_programs: Dict[Any, Any] = {}
        # host-side counters (the fleet's /statusz reads these)
        self.transfers = 0
        self.lane_transfers = 0
        self.bytes_moved = 0            # ledger-convention slab bytes
        self.last_transfer_ms = 0.0

    # ------------------------------------------------------------------
    # same-process: one compiled program per pool-shape pair
    # ------------------------------------------------------------------
    def _local_key(self, src_pool, dst_pool):
        def sig(pool):
            return (pool.n_layers, pool.n_slots, pool.max_total,
                    tuple(_widths(pool)), str(pool.dtype))
        return (sig(src_pool), sig(dst_pool), id(src_pool.mesh),
                id(dst_pool.mesh), src_pool.axis_name)

    def _build_local(self, src_pool, dst_pool):
        import jax
        from jax.sharding import PartitionSpec as P

        from .._compat import shard_map
        from ..parallel.reshard import reshard

        if src_pool.mesh is not dst_pool.mesh \
                or src_pool.axis_name != dst_pool.axis_name:
            raise ValueError(
                "local transfer needs src and dst pools on ONE mesh/"
                "axis; cross-mesh transfers go over the object lanes "
                "(pack/unpack_into)")
        if _widths(src_pool) != _widths(dst_pool):
            raise ValueError(
                f"pool shape mismatch: src (layers={src_pool.n_layers}, "
                f"kv_dim={src_pool.kv_dim}) vs dst "
                f"(layers={dst_pool.n_layers}, kv_dim={dst_pool.kv_dim})"
                f" — the layers' declared buffers differ")
        axis = src_pool.axis_name
        copy_rows = min(src_pool.max_total, dst_pool.max_total)
        src_specs, dst_specs = src_pool.cache_specs, dst_pool.cache_specs

        def move(src, dst, s_spec, d_spec, src_slot, dst_slot):
            row = jax.lax.dynamic_index_in_dim(src, src_slot, axis=0,
                                               keepdims=True)[:, :copy_rows]
            # the portable redistribution primitive: identity while
            # both pools shard the buffer's columns identically, the
            # minimal accounted collective the moment they differ
            row = reshard(row, _shard_axis_of(s_spec, axis),
                          _shard_axis_of(d_spec, axis), axis)
            return jax.lax.dynamic_update_slice(dst, row.astype(dst.dtype),
                                                (dst_slot, 0, 0))

        def body(src_caches, dst_caches, src_slot, dst_slot):
            # every buffer each layer declares (a K/V pair, one latent)
            return [tuple(move(s_, d_, ss, ds, src_slot, dst_slot)
                          for s_, d_, ss, ds in zip(*layer))
                    for layer in zip(src_caches, dst_caches, src_specs,
                                     dst_specs)]

        return jax.jit(shard_map(
            body, mesh=src_pool.mesh,
            in_specs=(src_specs, dst_specs, P(), P()),
            out_specs=dst_specs), donate_argnums=(1,))   # dst, never src

    def local_program(self, src_pool, dst_pool):
        """The compiled (src-pool, dst-pool) transfer program — cached;
        the analysis entry point probes it for recompiles."""
        key = self._local_key(src_pool, dst_pool)
        prog = self._programs.get(key)
        if prog is None:
            prog = self._programs[key] = self._build_local(src_pool,
                                                           dst_pool)
            from ..observability import flight as _flight
            _flight.note("compile", program="serving_kv_transfer",
                         family_size=len(self._programs))
        return prog

    def transfer_local(self, src_pool, src_slot: int, dst_pool,
                       dst_slot: int, length: int) -> Dict[str, Any]:
        """Move slot ``src_slot``'s slab into ``dst_slot`` on the same
        mesh and set ``dst_pool.pos[dst_slot] = length``.  Returns the
        transfer stats row (mode, ms, ledger bytes)."""
        import jax.numpy as jnp

        copy_rows = min(src_pool.max_total, dst_pool.max_total)
        if not (0 < int(length) <= copy_rows):
            raise ValueError(
                f"transfer length {length} out of range (0, {copy_rows}] "
                f"(src max_total {src_pool.max_total}, dst "
                f"{dst_pool.max_total})")
        prog = self.local_program(src_pool, dst_pool)
        t0 = time.monotonic()
        src, dst = jnp.int32(src_slot), jnp.int32(dst_slot)
        dst_pool.update(
            lambda dst_caches, src_caches: (
                None, prog(src_caches, dst_caches, src, dst)), src_pool)
        dst_pool.pos[dst_slot] = int(length)
        ms = (time.monotonic() - t0) * 1e3
        self.transfers += 1
        self.last_transfer_ms = ms
        axis = src_pool.axis_name
        cost = transfer_cost(
            src_pool.n_layers, length, src_pool.kv_dim,
            src_pool.dtype, mode="local",
            axis_size=src_pool.mesh.shape[axis],
            src_spec=_shard_axis_of(src_pool.cache_spec, axis),
            dst_spec=_shard_axis_of(dst_pool.cache_spec, axis),
            copy_rows=copy_rows)
        return {"mode": "local", "ms": ms,
                "ledger_bytes": cost["ledger_bytes"],
                "length": int(length)}

    # ------------------------------------------------------------------
    # cross-process: pack -> object lane -> unpack_into
    # ------------------------------------------------------------------
    def pack(self, src_pool, src_slot: int, length: int,
             meta: Dict[str, Any]) -> bytes:
        """Serialize slot ``src_slot``'s written rows ``[0, length)``
        plus the request wire dict.  Host-side numpy throughout — the
        payload is transport-agnostic bytes."""
        import jax

        _widths(src_pool)       # refuses a pool that holds state
        if not (0 < int(length) <= src_pool.max_total):
            raise ValueError(f"pack length {length} out of range "
                             f"(0, {src_pool.max_total}]")
        # the slices are dispatched under the pool's lock (an update may
        # delete the buffers right after); the copy to the host is not
        rows = src_pool.read(lambda caches: [
            tuple(buf[src_slot, :length] for buf in layer)
            for layer in caches])
        rows = [tuple(np.asarray(jax.device_get(buf)) for buf in layer)
                for layer in rows]
        return pickle.dumps({
            "schema": WIRE_SCHEMA,
            "meta": dict(meta),
            "pos": int(length),
            "n_layers": src_pool.n_layers,
            "kv_dim": src_pool.kv_dim,
            # what each layer keeps per token: (kv_dim, kv_dim) for a K/V
            # pool, one latent width for a latent-attention layer
            "widths": _widths(src_pool),
            "dtype": str(rows[0][0].dtype),
            # end-to-end integrity stamp (ISSUE 12): the receiver
            # recomputes this over the decoded rows and REFUSES a
            # mismatch — a corrupt slab degrades to re-prefill, it is
            # never served
            "crc32": slab_crc32(rows),
            "rows": rows,
        }, protocol=pickle.HIGHEST_PROTOCOL)

    def lane_put(self, tag: str, payload: bytes) -> None:
        """Publish a packed slab on the object lane, under the hardened
        retry discipline — the flight ring records every retry and the
        terminal fault NAMES the lane (``kv_transfer/put/<tag>``)."""
        from ..communicators.base import lane_call

        lane_call(f"kv_transfer/put/{tag}",
                  lambda: self.transport.put(tag, payload),
                  self.lane_config)

    def lane_get(self, tag: str, timeout_s: float = 10.0) -> bytes:
        from ..communicators.base import lane_call

        return lane_call(
            f"kv_transfer/get/{tag}",
            lambda: self.transport.get(tag, timeout_s),
            self.lane_config)

    def lane_delete(self, tag: str) -> None:
        from ..communicators.base import lane_call

        lane_call(f"kv_transfer/gc/{tag}",
                  lambda: self.transport.delete(tag), self.lane_config)

    def inject_program(self, dst_pool):
        """The pool-lifetime compiled slab WRITE — the landing half of
        every lane-mode transfer (and the ``serving.worker_lane``
        analysis entry point's program): host-padded slab rows
        ``dynamic_update_slice``\\ d into the destination slot, slot
        index a traced operand so every landing after the first hits
        the jit cache.  Zero collectives: each TP rank writes its local
        KV columns."""
        import jax
        from jax.sharding import PartitionSpec as P

        from .._compat import shard_map

        key = (dst_pool.n_layers, dst_pool.n_slots, dst_pool.max_total,
               tuple(_widths(dst_pool)), str(dst_pool.dtype),
               id(dst_pool.mesh))
        prog = self._inject_programs.get(key)
        if prog is None:
            dst_specs = dst_pool.cache_specs
            # a slab row is the cache row minus the slot dim: same
            # column sharding, one rank lower
            slab_specs = [tuple(P(*tuple(spec)[1:]) for spec in layer)
                          for layer in dst_specs]

            def body(dst_caches, slabs, dst_slot):
                return jax.tree_util.tree_map(
                    lambda d, slab: jax.lax.dynamic_update_slice(
                        d, slab[None].astype(d.dtype), (dst_slot, 0, 0)),
                    dst_caches, slabs)

            prog = self._inject_programs[key] = jax.jit(shard_map(
                body, mesh=dst_pool.mesh,
                in_specs=(dst_specs, slab_specs, P()),
                out_specs=dst_specs), donate_argnums=(0,))   # not the slabs
            from ..observability import flight as _flight
            _flight.note("compile", program="serving_kv_inject")
        return prog

    def unpack_into(self, payload: bytes, dst_pool, dst_slot: int, *,
                    ledger_op: str = LANE_OP,
                    ledger_axis: str = LANE_AXIS) -> Dict[str, Any]:
        """Inject a packed slab into ``dst_slot`` (compiled pool-
        lifetime slab write; the host pads the slab to the pool row so
        the program needs no length operand) and book the RAW slab
        bytes as a noted ``ledger_op@ledger_axis`` row — by default the
        ``kv_transfer_lane@dcn`` key, the exact
        :func:`transfer_cost(mode="lanes")` prediction; the host spill
        tier restores under ``kv_spill_restore@host`` so its traffic
        never pollutes the DCN wire-byte gate (ISSUE 12).  The payload's
        CRC32 stamp is verified BEFORE anything touches the pool: a
        corrupt or foreign slab is refused with :class:`ValueError`,
        never decoded.  Returns the wire dict's ``meta`` + transfer
        stats."""
        import jax.numpy as jnp

        t0 = time.monotonic()
        data = pickle.loads(payload)
        if data.get("schema") != WIRE_SCHEMA:
            raise ValueError(
                f"refusing KV transfer with schema "
                f"{data.get('schema')!r} (this receiver speaks "
                f"{WIRE_SCHEMA})")
        if data["n_layers"] != dst_pool.n_layers \
                or data["kv_dim"] != dst_pool.kv_dim \
                or [tuple(w) for w in data.get(
                    "widths", _widths(dst_pool))] != _widths(dst_pool):
            raise ValueError(
                f"slab shape mismatch: wire (layers={data['n_layers']}, "
                f"kv_dim={data['kv_dim']}) vs pool "
                f"(layers={dst_pool.n_layers}, kv_dim={dst_pool.kv_dim})")
        length = int(data["pos"])
        if length > dst_pool.max_total:
            raise ValueError(
                f"slab length {length} exceeds destination per-slot "
                f"capacity {dst_pool.max_total}")
        want_crc = data.get("crc32")
        if want_crc is not None:
            got_crc = slab_crc32(data["rows"])
            if got_crc != int(want_crc):
                raise ValueError(
                    f"refusing KV transfer: CRC mismatch (payload says "
                    f"{int(want_crc):#010x}, rows hash {got_crc:#010x}) "
                    f"— the slab was corrupted in transit/storage and "
                    f"must re-prefill, never serve")

        prog = self.inject_program(dst_pool)
        # pad each layer's rows to the pool row (rows above ``length``
        # are stale-but-unreachable, the standard masking argument)
        dt = dst_pool.dtype

        def padded(buf):
            buf = np.asarray(buf)
            out = np.zeros((dst_pool.max_total, buf.shape[1]), buf.dtype)
            out[:length] = buf
            return jnp.asarray(out.astype(dt))

        slabs = [tuple(padded(buf) for buf in layer)
                 for layer in data["rows"]]
        slot = jnp.int32(dst_slot)
        dst_pool.update(lambda caches: (None, prog(caches, slabs, slot)))
        dst_pool.pos[dst_slot] = length

        # the raw bytes of the written rows of every declared buffer (a
        # K/V pool: exactly ``slab_nbytes``)
        nbytes = sum(np.asarray(buf).nbytes for layer in data["rows"]
                     for buf in layer)
        ms = (time.monotonic() - t0) * 1e3
        self.transfers += 1
        self.lane_transfers += 1
        self.bytes_moved += nbytes
        self.last_transfer_ms = ms
        # comm-ledger booking (the acceptance contract: every transfer
        # priced, byte-exact vs transfer_cost) — noted, like the
        # AD-inserted gradient psum: traffic no collective wrapper sees
        from ..observability import comm as _comm
        from ..observability import trace as _trace
        if _trace.get_tracer().enabled:
            _comm.get_accountant().record(
                ledger_op, ledger_axis, nbytes, data["dtype"],
                in_jit=False, latency_s=ms / 1e3, noted=True)
        return {"mode": "lanes", "ms": ms, "ledger_bytes": nbytes,
                "wire_payload_bytes": len(payload), "length": length,
                "meta": data["meta"]}

    def stats(self) -> Dict[str, float]:
        return {
            "transfers": float(self.transfers),
            "lane_transfers": float(self.lane_transfers),
            "bytes_moved": float(self.bytes_moved),
            "last_transfer_ms": float(self.last_transfer_ms),
            "programs": float(len(self._programs)
                              + len(self._inject_programs)),
        }
