"""Peaks of the chips the benchmark may run on, and the device stamp.

A copy of the program's table (``chainermn_tpu/observability/metrics.py``)
on purpose: a later PR may change the program's and may not change the
yardstick.  A device that is not in the table is an error, not a default.
"""

import sys

#: keyed by ``jax.Device.device_kind``.  Source: Google Cloud documentation,
#: "TPU v5e" (197 TFLOP/s bf16, 16 GB HBM at 819 GB/s, 1,600 Gbit/s ICI).
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9, "ici_bits_per_s": 1600e9},
}


class NoChip(RuntimeError):
    """JAX found no accelerator, too few, or one the table does not know."""


def find(chips: int, allow_cpu: bool = False):
    """The ``chips`` devices a cell runs on, or :class:`NoChip`."""
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" and not allow_cpu:
        raise NoChip(f"JAX found no TPU (platform {dev.platform!r}, "
                     f"{len(devices)} device(s)); there is no CPU path")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chip(s), JAX sees "
                     f"{len(devices)}")
    if dev.platform == "tpu" and dev.device_kind not in PEAKS:
        raise NoChip(f"device_kind {dev.device_kind!r} is not in "
                     f"benchmark/harness/device.py's table")
    return devices[:chips]


def stamp(devices) -> dict:
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


def memory_peak_bytes(devices):
    """Peak bytes in use on the fullest chip (None where not reported)."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def say(devices, text: str, file=sys.stdout) -> None:
    """One free line of output, stamped with the device it ran on."""
    s = stamp(devices)
    print(f"[{s['platform']} {s['kind']} x{s['count']}] {text}", file=file,
          flush=True)
