"""TPU slice topology discovery and runtime bootstrap.

Reference parity: ``chainermn/communicators/_communication_utility.py ::
init_ranks / init_intra_mpi_comm / init_inter_mpi_comm / init_nccl_comm`` [uv]
(see SURVEY.md §2.1).  The reference discovers cluster topology by
all-gathering hostnames over MPI and derives ``intra_rank`` (GPU index within
the node) and ``inter_rank`` (node index).  On TPU none of that is needed:
the slice topology is a property of the runtime — ``jax.devices()`` already
knows which process (host) owns which chip and how the chips are wired over
ICI.  This module maps that information onto ChainerMN's rank vocabulary:

=================  ============================================
ChainerMN concept  TPU-native meaning
=================  ============================================
``rank``           index of a chip along the communicator mesh axis
``size``           number of chips in the communicator mesh
``intra_rank``     chip index within its host (``device.local_hardware_id``)
``intra_size``     chips per host (``jax.local_device_count()``)
``inter_rank``     host index (``jax.process_index()``)
``inter_size``     host count (``jax.process_count()``)
=================  ============================================

The reference's ``mpiexec`` bootstrap (one process per GPU) becomes
``jax.distributed.initialize`` (one process per host, multi-controller SPMD);
``init_distributed`` below wraps it and is a no-op for single-process runs.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh

# Canonical mesh-axis name for the data-parallel "multi-node" axis.  The
# reference has no axis names (ranks are implicit in MPI_COMM_WORLD); we pick
# one so in-jit collectives (lax.psum etc.) can refer to it.
DEFAULT_AXIS_NAME = "mn"

_initialized = False

#: The one place the package keeps compiled programs when nobody outside
#: says otherwise: a fixed directory inside the checkout (the path is
#: part of the cache key, so a directory that moves never hits).
_REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache")


def enable_compile_cache() -> str:
    """Place JAX's persistent compilation cache; return the directory.

    Called by every CLI entry point.  If ``JAX_COMPILATION_CACHE_DIR`` is
    set, JAX reads it itself and the code sets NOTHING; otherwise the
    cache lives at ``<checkout>/.jax_cache`` — never a temp name, pid or
    timestamp.
    """
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", _REPO_CACHE_DIR)
    return _REPO_CACHE_DIR


def init_distributed(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> None:
    """Bootstrap the multi-controller runtime (reference: ``mpiexec`` + MPI_Init).

    Safe to call unconditionally: a no-op when running single-process (the
    common case for tests and single-host jobs).  Multi-host TPU pods launched
    through a cluster scheduler auto-detect all three arguments.
    """
    global _initialized
    if _initialized:
        return
    explicit = coordinator_address is not None
    # Auto-detect only on unambiguous signals.  TPU_WORKER_HOSTNAMES is set
    # even on single-host TPU VMs, so it only counts with >1 worker listed.
    auto = any(v in os.environ for v in ("COORDINATOR_ADDRESS", "JAX_COORDINATOR_ADDRESS"))
    workers = os.environ.get("TPU_WORKER_HOSTNAMES", "")
    auto = auto or len([w for w in workers.split(",") if w.strip()]) > 1
    if explicit or auto:
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
        )
        _initialized = True
        # Crash hygiene (reference: auto-installed MPI_Abort hook): once a
        # gang exists, an uncaught exception on one process must abort the
        # whole job instead of wedging the others inside a collective.
        from .global_except_hook import add_hook

        add_hook()
    # No-op branch leaves the flag unset so a later *explicit* call (e.g. a
    # pod launcher passing coordinator_address) still initializes.


@dataclasses.dataclass(frozen=True)
class Topology:
    """Rank bookkeeping derived from the device list (not hostname gossip)."""

    size: int
    intra_size: int
    inter_size: int
    inter_rank: int  # this process's host index

    @classmethod
    def detect(cls, devices: Optional[Sequence[jax.Device]] = None) -> "Topology":
        devices = list(devices) if devices is not None else jax.devices()
        n_local = len([d for d in devices if d.process_index == jax.process_index()])
        n_proc = len({d.process_index for d in devices})
        return cls(
            size=len(devices),
            intra_size=max(n_local, 1),
            inter_size=max(n_proc, 1),
            inter_rank=jax.process_index(),
        )

    def intra_rank_of(self, rank: int) -> int:
        return rank % self.intra_size

    def inter_rank_of(self, rank: int) -> int:
        return rank // self.intra_size


def make_mesh(
    devices: Optional[Sequence[jax.Device]] = None,
    axis_name: str = DEFAULT_AXIS_NAME,
) -> Mesh:
    """A 1-D mesh over all chips — the communicator's world.

    Reference analog: ``MPI_COMM_WORLD`` ordering in ``init_ranks`` [uv].
    Devices are kept in ``jax.devices()`` order, which the runtime guarantees
    to be consistent across processes (so every host agrees on rank→chip).
    """
    devices = list(devices) if devices is not None else jax.devices()
    return Mesh(np.asarray(devices, dtype=object), (axis_name,))


def make_nd_mesh(axis_names: Sequence[str], axis_sizes: Sequence[int],
                 devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """An N-D mesh (e.g. ``('data','model')``) for hybrid DP×MP layouts.

    Reference analog: manual ``CommunicatorBase.split(color, key)`` 2-D
    decompositions (SURVEY.md §2.8 "Hybrid DP×MP").
    """
    devices = list(devices) if devices is not None else jax.devices()
    arr = np.asarray(devices, dtype=object).reshape(tuple(axis_sizes))
    return Mesh(arr, tuple(axis_names))


def slice_index_of(device: jax.Device) -> int:
    """Which slice (ICI island) a device belongs to.

    Real multislice TPU devices carry ``slice_index``; single-slice and CPU
    devices fall back to ``process_index`` (each host = one "slice", the
    closest analog: intra-host is fast, cross-host is DCN).
    """
    idx = getattr(device, "slice_index", None)
    if idx is not None:
        return int(idx)
    return int(device.process_index)


def make_multislice_mesh(
    devices: Optional[Sequence[jax.Device]] = None,
    axis_names: Sequence[str] = ("slice", "chip"),
    num_slices: Optional[int] = None,
) -> Mesh:
    """A 2-D ``('slice', 'chip')`` mesh exposing the two-tier fabric.

    Reference analog: ``HierarchicalCommunicator`` [uv] — intra-node NCCL
    reduce → inter-node MPI allreduce → intra-node bcast, i.e. "use the
    fast fabric first, cross the slow one once".  On TPU the two tiers are
    ICI (within a slice) and DCN (across slices); collectives over the
    ``chip`` axis ride ICI, collectives over ``slice`` cross DCN.  See
    :func:`chainermn_tpu.ops.collective.hierarchical_pmean` for the
    gradient-mean recipe built on this mesh.

    Slice membership comes from each device's ``slice_index`` (multislice
    runtime) with a ``process_index`` fallback; ``num_slices`` overrides
    detection (e.g. to carve a virtual CPU mesh into fake slices for tests).
    """
    devices = list(devices) if devices is not None else jax.devices()
    if num_slices is None:
        groups: dict = {}
        for d in devices:
            groups.setdefault(slice_index_of(d), []).append(d)
        sizes = {len(v) for v in groups.values()}
        if len(sizes) != 1:
            raise ValueError(
                f"uneven slices: {{idx: len}} = "
                f"{ {k: len(v) for k, v in groups.items()} }")
        ordered = [d for _, grp in sorted(groups.items()) for d in grp]
        num_slices = len(groups)
    else:
        if len(devices) % num_slices:
            raise ValueError(
                f"{len(devices)} devices not divisible into {num_slices} slices")
        ordered = devices
    arr = np.asarray(ordered, dtype=object).reshape(
        (num_slices, len(ordered) // num_slices))
    return Mesh(arr, tuple(axis_names))
