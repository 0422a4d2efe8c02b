"""In-jit collective face — what the hot path uses.

The reference's hot loop calls eager NCCL collectives between autograd and
the optimizer (SURVEY.md §3.2).  TPU-native, the entire training step is ONE
compiled SPMD program, and collectives are `jax.lax` ops *inside* it that
XLA lowers onto ICI and schedules/overlaps itself — this module is the thin,
named wrapper layer so framework code and user code share one vocabulary
with the eager face (`communicators/`).

All functions take `axis_name` (default ``"mn"``) and must be called inside
a `shard_map`/`pmap` context where that axis is bound.  `pmean_if_bound`
(the gradient-sync primitive) degrades to identity when the axis is not
bound, which lets the same optimizer wrapper run unmodified under
(a) shard_map SPMD, (b) plain pjit (where XLA inserts gradient reductions
automatically from shardings), and (c) single-device tests.
"""

from __future__ import annotations

from typing import Optional

import jax

from .._compat import axis_size as _axis_size_compat
from .._compat import all_gather_invariant as _all_gather_invariant
from .._compat import pcast_varying as _pcast_varying
from ..observability.comm import collective as _acc
from ..topology import DEFAULT_AXIS_NAME


#: Ledger-op → jaxpr collective primitive: which equation each wrapper's
#: wire leg lowers to.  This is the join key of the static↔dynamic
#: reconciliation (``analysis/shardflow.py``): the runtime comm ledger is
#: keyed by WRAPPER name (``reduce_scatter@mn``), the traced program by
#: PRIMITIVE name (``psum_scatter`` / this jax's ``reduce_scatter``), and
#: several wrappers share one primitive (``psum``/``pmean``/the autodiff
#: grad note all land on ``psum``), so reconciliation happens per
#: primitive group.  ``None`` marks a COMPOSITE op whose wire legs are a
#: hand-written schedule (the quantized int8 ring: per-hop sub-chunk
#: ppermutes at the wire dtype plus fp32 block scales, then a tiled int8
#: all_gather ring) — its cost comes from :func:`quantized_ring_cost`,
#: its per-equation groups from :func:`quantized_ring_static_groups`
#: (declared as ``composite`` by the owning entry point), never from a
#: single equation.  Kept as a literal so the jax-free analysis registry
#: can read it by parsing.
LEDGER_TO_PRIMITIVE = {
    "psum": "psum",
    "pmean": "psum",
    "pmax": "pmax",
    "pmin": "pmin",
    "pmean_if_bound": "psum",
    "all_gather": "all_gather",
    "all_to_all": "all_to_all",
    "reduce_scatter": "psum_scatter",
    "ppermute": "ppermute",
    "shift": "ppermute",
    "bcast": "all_gather",
    "hierarchical_pmean": "psum",
    "quantized_ring_pmean": None,
    # comm.note() declarations used by the shipped builders (train.py):
    # the autodiff-inserted cross-rank gradient psum.
    "grad_allreduce_ad": "psum",
}


def collective_wire_cost(primitive: str, payload_bytes: int,
                         axis_size: int) -> dict:
    """Physical wire cost of ONE collective equation on a ring schedule:
    ``{"wire_bytes": per-rank bytes on the wire, "messages": per-rank
    message count}``.

    ``payload_bytes`` follows the LEDGER convention (the input payload of
    the call — ``observability.comm.payload_info``); this function maps
    it to the ring decomposition every textbook (and XLA's default ICI
    schedule) uses: an all-reduce is reduce-scatter + all-gather, each
    moving ``(P-1)/P`` of the payload over ``P-1`` hops.  At axis size 1
    everything is free.  Used by the shard-flow cost model and the comm
    ledger's tests — one formula, not two.
    """
    p = int(axis_size)
    if p <= 1:
        return {"wire_bytes": 0, "messages": 0}
    b = int(payload_bytes)
    if primitive in ("psum", "pmax", "pmin"):            # all-reduce
        return {"wire_bytes": 2 * b * (p - 1) // p, "messages": 2 * (p - 1)}
    if primitive in ("psum_scatter", "reduce_scatter"):  # reduce-scatter
        return {"wire_bytes": b * (p - 1) // p, "messages": p - 1}
    if primitive == "all_gather":   # payload = the PER-RANK input block
        return {"wire_bytes": b * (p - 1), "messages": p - 1}
    if primitive == "all_to_all":
        return {"wire_bytes": b * (p - 1) // p, "messages": p - 1}
    if primitive in ("ppermute", "pshuffle"):
        return {"wire_bytes": b, "messages": 1}
    return {"wire_bytes": b, "messages": 1}  # unknown: conservative


#: Default quantization block: ~256 elements per fp32 scale bounds the
#: per-block error at ``blockmax/254`` while keeping scale traffic under
#: 1.6% of the int8 payload (4 bytes per 256).  EQuARX (PAPERS.md) uses
#: the same block ≪ chunk regime.
DEFAULT_QUANT_BLOCK = 256


def _ring_layout(n_elements: int, axis_size: int, block: int,
                 pipeline: int):
    """The ONE chunk/block/sub-chunk layout both the kernel
    (:func:`quantized_ring_pmean`) and the static cost model
    (:func:`quantized_ring_cost`) derive their numbers from — byte-exact
    reconciliation is only possible if padding is decided in one place.

    Returns ``(chunk_len, eff_block, nb_sub, k)``: each rank owns one
    chunk of ``chunk_len = k * nb_sub * eff_block`` elements (``n``
    padded up to ``p * chunk_len``), organized as ``k`` pipeline
    sub-chunks of ``nb_sub`` quantization blocks each.  ``eff_block``
    shrinks to the raw chunk for tiny leaves so a 64-element leaf is not
    padded to 256.
    """
    p = max(1, int(axis_size))
    raw = -(-max(1, int(n_elements)) // p)       # ceil(n / p)
    eff_block = max(1, min(int(block), raw))
    k = max(1, int(pipeline))
    nb_sub = -(-raw // (k * eff_block))          # blocks per sub-chunk
    return k * nb_sub * eff_block, eff_block, nb_sub, k


def quantized_ring_cost(n_elements: int, axis_size: int,
                        wire_dtype="int8",
                        block: int = DEFAULT_QUANT_BLOCK,
                        pipeline: int = 1) -> dict:
    """Analytic wire cost of :func:`quantized_ring_pmean` — the composite
    op ``LEDGER_TO_PRIMITIVE`` maps to ``None``.

    Returns ``{"ledger_bytes", "wire_bytes", "scale_bytes", "messages"}``
    per rank: ``ledger_bytes`` is what the accountant books for the call
    (``n_elements × itemsize(wire_dtype)`` — the documented compressed-
    wire convention), ``wire_bytes`` the physical payload hops, and
    ``scale_bytes`` the fp32 per-BLOCK scales that ride alongside — the
    scale-traffic carve-out of the reconciliation contract
    (docs/ANALYSIS.md).

    The schedule is the MINIMAL ring decomposition: the reduce-scatter
    phase re-quantizes and forwards one ``chunk`` per hop for ``P-1``
    hops (``k`` pipelined sub-chunk messages per hop, fp32 block scales
    bitcast IN-BAND behind each payload — one message, not two), and
    the gather phase is one tiled int8 ``all_gather`` of the packed
    finished chunk — a gather ring at ``(P-1) × (chunk + scales)`` wire
    bytes, replacing the old one-hot-psum phase that paid ``2×`` that
    (its ``ag_bytes = 2·(p·chunk)·(p−1)/p`` accounting is gone with it).
    """
    p = int(axis_size)
    item = _as_wire_itemsize(wire_dtype)
    n = int(n_elements)
    if p <= 1:
        return {"ledger_bytes": 0, "wire_bytes": 0, "scale_bytes": 0,
                "messages": 0}
    chunk, _, nb_sub, k = _ring_layout(n, p, block, pipeline)
    nb = k * nb_sub                              # scale blocks per chunk
    rs_bytes = (p - 1) * chunk * item            # k packed msgs per hop
    ag_bytes = (p - 1) * chunk * item            # tiled all_gather ring
    scales = 2 * (p - 1) * nb * 4                # in-band, both phases
    return {
        "ledger_bytes": n * item,
        "wire_bytes": rs_bytes + ag_bytes,
        "scale_bytes": scales,
        # RS phase: k packed sub-chunk ppermutes per hop over p-1 hops;
        # AG phase: one packed all_gather at p-1 ring messages
        "messages": k * (p - 1) + (p - 1),
    }


def quantized_ring_static_groups(n_elements: int, axis_size: int,
                                 axis_name: str = DEFAULT_AXIS_NAME,
                                 wire_dtype="int8",
                                 block: int = DEFAULT_QUANT_BLOCK,
                                 pipeline: int = 1) -> dict:
    """The quantized ring's traced equations as LEDGER-convention
    ``primitive@axis -> payload bytes`` groups — what
    ``analysis.shardflow.static_costs`` derives from the jaxpr.  A
    declaring entry point (``train.quantized_step``) passes this as its
    ``composite`` declaration so the reconciliation can hold the
    hand-written schedule to the traced program byte-exactly."""
    p = int(axis_size)
    if p <= 1:
        return {}
    item = _as_wire_itemsize(wire_dtype)
    chunk, _, nb_sub, k = _ring_layout(n_elements, p, block, pipeline)
    nb = k * nb_sub
    return {
        # per hop: k packed sub-chunk ppermutes (int8 payload + in-band
        # bitcast scales); payload convention = the call's input bytes
        f"ppermute@{axis_name}": (p - 1) * (chunk * item + nb * 4),
        # gather phase: one tiled all_gather of the packed finished
        # chunk (payload = the per-rank input block incl. scales)
        f"all_gather@{axis_name}": chunk * item + nb * 4,
    }


def choose_pipeline_depth(chunk_bytes: int, bw_bytes_per_s: float = 1.8e11,
                          alpha_s: float = 1e-6,
                          dequant_bytes_per_s: float = 4e11,
                          candidates=(1, 2, 4, 8)) -> int:
    """Pick the pipeline depth ``k`` for :func:`quantized_ring_pmean`
    from the r04 multislice cost-model terms (per-hop latency ``alpha``
    and link bandwidth — v5e ICI defaults, same table as
    ``analysis/schedule.py``'s ``CostModel``).

    Model per ring hop with ``k`` sub-chunks: the transfer of sub-chunk
    ``j+1`` overlaps the dequant+accumulate of sub-chunk ``j``, so the
    hop costs ``k·alpha + max(T, D) + min(T, D)/k`` where ``T =
    chunk_bytes/bw`` and ``D = chunk_bytes/dequant_bw`` — deeper
    pipelines hide more of the smaller term but pay one ``alpha`` per
    extra message.  Tiny chunks pick ``k=1``; multi-MB chunks pick the
    deepest candidate that still amortizes its alphas."""
    chunk_bytes = max(0, int(chunk_bytes))
    t = chunk_bytes / float(bw_bytes_per_s)
    d = chunk_bytes / float(dequant_bytes_per_s)

    def hop_cost(k):
        return k * float(alpha_s) + max(t, d) + min(t, d) / k

    return min(candidates, key=hop_cost)


def block_quantize(v, wire_dtype="int8", block: int = DEFAULT_QUANT_BLOCK):
    """Symmetric per-BLOCK quantization: ``(q, scales)`` where ``v``
    (any shape) is flattened, zero-padded to a multiple of the effective
    block, and quantized as ``q = round(v / scale)`` with one fp32
    ``scale = blockmax / qmax`` per block — error ≤ ``blockmax/254`` per
    block for int8.  Pure arithmetic (no wire): the quantizer of the ring
    schedule and of the error-feedback residual, exposed so tests and the
    EF transform share the exact operator."""
    import jax.numpy as jnp

    wire = jnp.dtype(wire_dtype)
    if not jnp.issubdtype(wire, jnp.integer):
        raise ValueError(f"wire_dtype must be an integer type, got {wire}")
    qmax = float(jnp.iinfo(wire).max)
    flat = v.ravel().astype(jnp.float32)
    n = flat.shape[0]
    eff = max(1, min(int(block), n))
    flat = jnp.pad(flat, (0, (-n) % eff))
    vb = flat.reshape(-1, eff)
    scales = jnp.maximum(jnp.max(jnp.abs(vb), axis=-1), 1e-30) / qmax
    q = jnp.clip(jnp.round(vb / scales[:, None]), -qmax, qmax).astype(wire)
    return q, scales.astype(jnp.float32)


def block_dequantize(q, scales, shape=None, n_elements=None):
    """Inverse of :func:`block_quantize`: fp32 values, un-padded to
    ``n_elements`` (or ``prod(shape)``) and reshaped to ``shape``."""
    import jax.numpy as jnp
    import numpy as np

    flat = (q.astype(jnp.float32) * scales[:, None]).ravel()
    if shape is not None and n_elements is None:
        n_elements = int(np.prod(shape)) if shape else 1
    if n_elements is not None:
        flat = flat[:n_elements]
    return flat.reshape(shape) if shape is not None else flat


def _as_wire_itemsize(wire_dtype) -> int:
    # one dtype-coercion fallback for the whole codebase: the
    # accountant's (np.dtype, else getattr(jnp, name)) rule
    from ..observability.comm import _as_dtype

    return _as_dtype(wire_dtype).itemsize


def _axis_bound(axis_name) -> bool:
    """True when `axis_name` (a name or tuple of names) is bound in the
    current trace.

    Only the unbound-axis error (NameError in current JAX) means "not SPMD";
    anything else propagates — silently treating an unexpected failure as
    unbound would turn gradient averaging into identity and corrupt training.
    """
    names = axis_name if isinstance(axis_name, (tuple, list)) else (axis_name,)
    try:
        for name in names:
            jax.lax.axis_index(name)
        return True
    except NameError:
        return False


def zeros_like_vma(x, dtype=None, shape=None):
    """Zeros carrying ``x``'s varying-mesh-axes type.

    Inside ``shard_map``, ``lax.scan`` demands carry-in/out types agree,
    so accumulators must be *varying* like the data they will absorb — but
    deriving them as ``x * 0`` would turn a single inf/NaN in ``x`` into an
    all-NaN accumulator.  This builds honest zeros and pcasts them to
    ``x``'s vma set instead.
    """
    import jax.numpy as jnp

    z = jnp.zeros(x.shape if shape is None else shape,
                  x.dtype if dtype is None else dtype)
    vma = getattr(getattr(x, "aval", None), "vma", None)
    if vma:
        z = _pcast_varying(z, tuple(vma))
    return z


# Every public collective routes through the observability accounting
# (`observability.comm.collective`): op name, axis, payload bytes and wire
# dtype are booked per call — once per trace for in-jit calls, with host
# latency for eager ones.  With tracing disabled the wrapper is a single
# attribute read before dispatching to `jax.lax`.

def psum(x, axis_name: str = DEFAULT_AXIS_NAME):
    return _acc("psum", axis_name, x, lambda: jax.tree_util.tree_map(
        lambda v: jax.lax.psum(v, axis_name), x))


def pmean(x, axis_name: str = DEFAULT_AXIS_NAME):
    return _acc("pmean", axis_name, x, lambda: jax.tree_util.tree_map(
        lambda v: jax.lax.pmean(v, axis_name), x))


def pmax(x, axis_name: str = DEFAULT_AXIS_NAME):
    return _acc("pmax", axis_name, x, lambda: jax.tree_util.tree_map(
        lambda v: jax.lax.pmax(v, axis_name), x))


def pmin(x, axis_name: str = DEFAULT_AXIS_NAME):
    return _acc("pmin", axis_name, x, lambda: jax.tree_util.tree_map(
        lambda v: jax.lax.pmin(v, axis_name), x))


def pmean_if_bound(x, axis_name: Optional[str] = DEFAULT_AXIS_NAME):
    """Mean across the axis if it is bound; identity otherwise.

    This is the gradient-sync primitive of `create_multi_node_optimizer`:
    under shard_map it is a real ICI all-reduce; under pjit-with-shardings
    the axis is unbound and XLA's sharding propagation already produced
    globally-correct mean gradients, so identity is exactly right.
    """
    if axis_name is None or not _axis_bound(axis_name):
        return x
    return pmean(x, axis_name)


def all_gather(x, axis_name: str = DEFAULT_AXIS_NAME, axis: int = 0,
               tiled: bool = True, invariant: bool = False):
    """``invariant=True``: the same wire collective typed varying →
    INVARIANT, for a result that is replicated by definition and leaves
    ``shard_map`` through ``out_specs=P()`` (plain ``all_gather`` is
    varying → varying under vma typing)."""
    gather = _all_gather_invariant if invariant else jax.lax.all_gather
    return _acc("all_gather", axis_name, x, lambda: gather(
        x, axis_name, axis=axis, tiled=tiled))


def all_to_all(x, axis_name: str = DEFAULT_AXIS_NAME, split_axis: int = 0,
               concat_axis: int = 0, tiled: bool = True):
    return _acc("all_to_all", axis_name, x, lambda: jax.lax.all_to_all(
        x, axis_name, split_axis=split_axis, concat_axis=concat_axis,
        tiled=tiled))


def reduce_scatter(x, axis_name: str = DEFAULT_AXIS_NAME, scatter_axis: int = 0):
    return _acc("reduce_scatter", axis_name, x, lambda: jax.lax.psum_scatter(
        x, axis_name, scatter_dimension=scatter_axis, tiled=True))


def ppermute(x, perm, axis_name: str = DEFAULT_AXIS_NAME):
    return _acc("ppermute", axis_name, x, lambda: jax.lax.ppermute(
        x, axis_name, perm=perm))


def shift(x, offset: int, axis_name: str = DEFAULT_AXIS_NAME, size: Optional[int] = None):
    """Ring shift by `offset` (the ring-attention / pipeline building block)."""
    if size is None:
        size = _axis_size_compat(axis_name)
    perm = [(i, (i + offset) % size) for i in range(size)]
    return _acc("shift", axis_name, x, lambda: jax.lax.ppermute(
        x, axis_name, perm=perm))


def axis_index(axis_name: str = DEFAULT_AXIS_NAME):
    return jax.lax.axis_index(axis_name)


def axis_size(axis_name: str = DEFAULT_AXIS_NAME) -> int:
    return _axis_size_compat(axis_name)


def bcast(x, root: int = 0, axis_name: str = DEFAULT_AXIS_NAME):
    """Every rank gets rank `root`'s block (in-jit broadcast)."""
    def one(v):
        # the invariant-typed gather: root's block IS replicated, and
        # may leave shard_map through out_specs=P()
        g = _all_gather_invariant(v, axis_name, axis=0, tiled=False)
        return g[root]
    return _acc("bcast", axis_name, x,
                lambda: jax.tree_util.tree_map(one, x))


def quantized_ring_pmean(x, axis_name: str = DEFAULT_AXIS_NAME,
                         wire_dtype="int8",
                         block: int = DEFAULT_QUANT_BLOCK,
                         pipeline: int = 1):
    """Cross-rank mean with **block-scaled int8 wire traffic**: a
    hand-scheduled ring all-reduce where every hop carries ``wire_dtype``
    payloads plus one fp32 scale per ``block`` elements.

    Beyond the reference's fp16 ``allreduce_grad_dtype`` (its best was 2
    bytes/element; this is ~1): the EQuARX recipe (PAPERS.md, arxiv
    2506.17615) —

    * **block scales** — one fp32 scale per ``block`` elements (default
      256, shrunk to the chunk for tiny leaves) instead of one per
      ``N/P`` chunk: quantization error is bounded per BLOCK
      (``blockmax/254``), so one outlier no longer flattens the whole
      chunk's resolution.
    * **requantization per hop** — each reduce-scatter hop dequantizes
      the incoming running sum, accumulates its own chunk in fp32, and
      requantizes before forwarding (``P-1`` hops).
    * **pipelined sub-chunks** — ``pipeline=k`` splits each chunk into
      ``k`` independent sub-chunk rings (layout from
      :func:`_ring_layout`), so the ppermute of sub-chunk ``j+1`` can
      overlap the dequant+accumulate of sub-chunk ``j`` (XLA's async
      scheduler owns the actual overlap; the schedule merely exposes the
      independence).  :func:`choose_pipeline_depth` picks ``k`` from the
      alpha/bandwidth cost model.
    * **gather ring** — the all-gather phase is one tiled int8
      ``all_gather`` of the packed finished chunk (block scales bitcast
      in-band): the minimal ``(P-1)×chunk`` gather ring, in its
      varying → INVARIANT form (``_compat.all_gather_invariant``), so
      the result reaches the optimizer typed replicated (the
      one-hot-psum phase it replaces paid ~2× the minimal wire; its
      only virtue was the invariant typing).
      The ring's start offset makes rank ``r`` finish its OWN chunk
      ``r``, so the gathered rows concatenate in order — no fix-up
      permutation between the collective and the output.

    Use for gradients (noise-tolerant), not activations.  Call inside
    ``shard_map`` with ``axis_name`` bound.  Works per-leaf on a pytree
    (:func:`chainermn_tpu.optimizers.compressed_mean` buckets a whole
    gradient tree into one flat call).
    """
    import jax.numpy as jnp

    p = _axis_size_compat(axis_name)
    if p == 1:
        return x
    wire = jnp.dtype(wire_dtype)
    if not jnp.issubdtype(wire, jnp.integer):
        raise ValueError(f"wire_dtype must be an integer type, got {wire}")
    qmax = float(jnp.iinfo(wire).max)  # symmetric: use [-qmax, qmax]
    idx = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % p) for i in range(p)]

    def quant_rows(vb):
        # vb: (..., nb, B) -> per-block q + scales
        scale = jnp.maximum(jnp.max(jnp.abs(vb), axis=-1), 1e-30) / qmax
        q = jnp.clip(jnp.round(vb / scale[..., None]),
                     -qmax, qmax).astype(wire)
        return q, scale.astype(jnp.float32)

    def one(leaf):
        flat = leaf.ravel().astype(jnp.float32)
        n = flat.shape[0]
        chunk_len, eff_block, nb_sub, k = _ring_layout(n, p, block, pipeline)
        flat = jnp.pad(flat, (0, p * chunk_len - n))
        # (p, k, nb_sub, B): rank-major chunks, each k sub-chunks of
        # nb_sub quantization blocks
        chunks = flat.reshape(p, k, nb_sub, eff_block)

        # Reduce-scatter: rank i STARTS by forwarding chunk (i-1), so at
        # step s it carries the running sum of chunk (i - 1 - s) mod p
        # and after P-1 hops finishes its OWN chunk i — the gathered
        # rows then concatenate in order with no fix-up permutation.
        # Each hop re-quantizes
        # the running sum per block and moves each sub-chunk as its own
        # packed ppermute, so hop s+1's transfers are independent of hop
        # s's dequants.
        # fp32 scales travel IN-BAND, bitcast to the wire dtype behind
        # the payload: ONE wire message per transfer — half the
        # rendezvous/DMA descriptors of a separate scale message, same
        # bytes (quantized_ring_cost's scale_bytes names the in-band
        # scale share)
        ratio = 4 // wire.itemsize  # wire words per fp32 scale

        def pack(q, scale):
            return jnp.concatenate(
                [q.reshape(-1),
                 jax.lax.bitcast_convert_type(scale, wire).reshape(-1)])

        def unpack(msg, nb):
            q = msg[:nb * eff_block].reshape(nb, eff_block)
            raw = msg[nb * eff_block:].reshape(
                (nb, ratio) if ratio > 1 else (nb,))
            return q, jax.lax.bitcast_convert_type(raw, jnp.float32)

        send = jax.lax.dynamic_index_in_dim(chunks, jnp.mod(idx - 1, p),
                                            0, keepdims=False)
        for s in range(p - 1):
            q, scale = quant_rows(send)            # (k, nb_sub, B), (k, nb_sub)
            msgs = [jax.lax.ppermute(pack(q[j], scale[j]), axis_name,
                                     perm=perm)
                    for j in range(k)]
            c = jnp.mod(idx - s - 2, p)
            nxt = jax.lax.dynamic_index_in_dim(chunks, c, 0, keepdims=False)
            parts = []
            for j in range(k):
                qr, sr = unpack(msgs[j], nb_sub)
                parts.append(qr.astype(jnp.float32) * sr[:, None] + nxt[j])
            send = jnp.stack(parts)

        # Gather ring: ONE block quantization of the finished chunk, then
        # a single tiled all_gather of the packed (q + in-band scales)
        # message — (P-1)×(chunk+scales) minimal wire.  The INVARIANT
        # form of the collective is the "replication fix-up": plain
        # ``jax.lax.all_gather`` (like a hand-rolled ppermute gather
        # ring) comes out axis-varying under vma typing and could not
        # leave the step through ``out_specs=P()``.  tiled=True is the
        # layout the reshape below wants directly.
        nb = k * nb_sub
        q, scale = quant_rows(send.reshape(nb, eff_block))
        ga = _all_gather_invariant(pack(q, scale), axis_name, axis=0,
                                   tiled=True).reshape(p, -1)
        gq = ga[:, :nb * eff_block].reshape(p, nb, eff_block)
        raw = ga[:, nb * eff_block:].reshape(
            (p, nb, ratio) if ratio > 1 else (p, nb))
        gs = jax.lax.bitcast_convert_type(raw, jnp.float32)
        # rank r finished chunk r, so the gathered rows ARE the chunks
        # in order — no permutation between gather and output
        full = (gq.astype(jnp.float32) * gs[..., None]).reshape(p, chunk_len)

        flat_out = full.ravel()[:n] / p
        return flat_out.reshape(leaf.shape).astype(leaf.dtype)

    # Accounted at the WIRE dtype: the whole point of this op is that the
    # ring hops carry int8, so the byte ledger reflects ~1 byte/element,
    # not x's fp32 logical payload (block scales are the documented
    # carve-out — quantized_ring_cost's scale_bytes).
    return _acc("quantized_ring_pmean", axis_name, x,
                lambda: jax.tree_util.tree_map(one, x), wire_dtype=wire)


def hierarchical_pmean(x, chip_axis: str = "chip", slice_axis: str = "slice",
                       dcn_dtype=None):
    """Two-tier mean over a ``('slice', 'chip')`` multislice mesh.

    Reference analog: ``HierarchicalCommunicator`` [uv] (SURVEY.md §2.1) —
    reduce on the fast fabric first (intra-node NCCL), cross the slow one
    once (inter-node MPI).  TPU: mean over ``chip_axis`` rides ICI inside
    each slice; the already-reduced value then crosses DCN exactly once via
    the ``slice_axis`` mean.  The decomposition mean = mean_slice(mean_chip)
    is exact (equal slice sizes by mesh construction).

    ``dcn_dtype`` (e.g. ``'bfloat16'``) compresses ONLY the DCN leg — the
    two-tier version of the reference's fp16 allreduce: ICI is fast enough
    for fp32, the cross-slice hop is the bottleneck worth halving.

    Mesh recipe: ``topology.make_multislice_mesh()``; call this under
    ``shard_map`` with both axes bound (in place of the flat gradient
    pmean).  :func:`chainermn_tpu.optimizers.hierarchical_gradient_average`
    packages it as an optax transform.
    """
    import jax.numpy as jnp

    def one(v):
        local = jax.lax.pmean(v, chip_axis)           # ICI, within slice
        if dcn_dtype is not None:
            wire = jnp.dtype(dcn_dtype)
            return jax.lax.pmean(local.astype(wire), slice_axis).astype(v.dtype)
        return jax.lax.pmean(local, slice_axis)       # DCN, once
    return _acc("hierarchical_pmean", (chip_axis, slice_axis), x,
                lambda: jax.tree_util.tree_map(one, x),
                wire_dtype=dcn_dtype)
