"""Derived metrics: step-time breakdown, throughput, MFU gauges.

The trainer/updater stamp raw phase times (``updaters.StandardUpdater``
→ ``phase_times``; ``Trainer`` → ``last_extension_time``/``last_phase``)
and the comm accountant produces per-step byte/call reports; this module
turns them into observation entries that ride the normal reporting path —
:class:`~chainermn_tpu.extensions.ObservationAggregator` rank-means them,
``LogReport`` folds them into epoch means, and the ``Watchdog`` heartbeat
can name the last completed phase when a rank stalls.
"""

from __future__ import annotations

from typing import Optional

from . import trace
from .comm import get_accountant

# Peak dense bf16 FLOP/s per chip by TPU generation (public spec sheets).
# Matched by substring against jax.devices()[0].device_kind (lowercased).
# Read by StepBreakdownReport below (the ``perf/mfu`` gauge); the benchmark
# keeps its own copy in benchmark/harness/device.py by design (PERF.md §3).
PEAK_BF16_FLOPS = [
    ("v6e", 918e12),
    ("trillium", 918e12),
    ("v5p", 459e12),
    ("v5e", 197e12),
    ("v5 lite", 197e12),
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 46e12),
]


def peak_flops_for(device_kind: str) -> Optional[float]:
    kind = device_kind.lower()
    for key, value in PEAK_BF16_FLOPS:
        if key in kind:
            return value
    if "tpu" in kind:
        # a device that is not in the table is an error, not a default:
        # a utilization computed against a guessed peak is worse than none
        raise ValueError(
            f"no peak bf16 FLOP/s on record for TPU device_kind "
            f"{device_kind!r}; add its generation to "
            f"chainermn_tpu/observability/metrics.py")
    return None  # not a TPU (the CPU test mesh): utilization not meaningful


class StepBreakdownReport:
    """Trainer extension publishing the step-time breakdown.

    Observation keys written every iteration (when the sources exist):

    * ``time/data``, ``time/compute`` — the updater's phase stamps
      (batch fetch+convert+upload vs. jitted-step call).  JAX dispatch
      is asynchronous, so host-side "compute" is dispatch time; the
      on-device tail of the step surfaces wherever the first sync
      happens (usually ``time/extensions``).  The per-iteration total
      across all phases is accurate wall clock.
    * ``time/extensions`` — the PREVIOUS iteration's extension pass
      (this extension runs inside the current pass, which has not
      finished yet).
    * ``time/comm``, ``comm/bytes``, ``comm/calls`` — the accountant's
      per-step report: host latency of eager collectives plus the byte/
      call profile of the collectives compiled into the step program.
    * ``throughput/items_per_sec`` — from the updater's observed batch
      size (override with ``items_per_step``); also published as a
      tracer gauge.
    * ``perf/mfu`` — when ``flops_per_item`` is given and the device's
      peak is known (or ``peak_flops`` is passed explicitly).

    All keys go through ``trainer.observation``, so with an
    ``ObservationAggregator`` registered ahead of ``LogReport`` the
    logged values are rank means — a straggling rank shows up as an
    inflated mean ``time/compute``, and the per-rank trace tells which.
    """

    trigger = (1, "iteration")
    # Above PRIORITY_EDITOR (300): the keys must land in the observation
    # BEFORE an ObservationAggregator replaces it with rank means —
    # that ordering is what makes the logged breakdown a cross-rank
    # mean.  Below the Watchdog (10k).
    priority = 350

    def __init__(self, items_per_step: Optional[int] = None,
                 flops_per_item: Optional[float] = None,
                 peak_flops: Optional[float] = None):
        self.items_per_step = items_per_step
        self.flops_per_item = flops_per_item
        self._peak = peak_flops
        self._peak_resolved = peak_flops is not None

    def _peak_flops(self) -> Optional[float]:
        if not self._peak_resolved:
            self._peak_resolved = True
            try:
                import jax
                self._peak = peak_flops_for(jax.devices()[0].device_kind)
            except Exception:
                self._peak = None
        return self._peak

    def observe(self, trainer) -> None:
        obs = trainer.observation
        updater = trainer.updater
        phases = getattr(updater, "phase_times", None)
        total = 0.0
        if phases:
            for phase, dt in phases.items():
                obs[f"time/{phase}"] = dt
                total += dt
        ext_t = getattr(trainer, "last_extension_time", None)
        if ext_t is not None:
            obs["time/extensions"] = ext_t
            total += ext_t
        rep = get_accountant().last_step_report
        if rep is not None:
            obs["comm/bytes"] = rep["bytes"]
            obs["comm/calls"] = rep["calls"]
            obs["time/comm"] = rep["host_time_s"]
        items = self.items_per_step or getattr(updater, "last_batch_size",
                                               None)
        tr = trace.get_tracer()
        if items and total > 0:
            ips = items / total
            obs["throughput/items_per_sec"] = ips
            tr.set_gauge("throughput/items_per_sec", ips)
            if self.flops_per_item:
                peak = self._peak_flops()
                if peak:
                    mfu = self.flops_per_item * ips / peak
                    obs["perf/mfu"] = mfu
                    tr.set_gauge("perf/mfu", mfu)

    def __call__(self, trainer) -> None:
        pass

    def state_dict(self) -> dict:
        return {}

    def load_state_dict(self, state: dict) -> None:
        pass
