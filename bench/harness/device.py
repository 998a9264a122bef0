"""What JAX sees, and the published peaks of the chips the benchmark knows.

A run that finds no TPU, or fewer chips than its cell asks for, stops
before it prints a result.  A device kind missing from ``PEAKS`` is an
error, never a default.
"""

from __future__ import annotations

from dataclasses import dataclass

__all__ = ["Peak", "PEAKS", "peak_for", "check_device", "memory_peak_bytes"]


@dataclass(frozen=True)
class Peak:
    flops_per_s: float       # dense bf16 matrix units
    hbm_bytes_per_s: float
    hbm_bytes: float
    source: str


PEAKS = {
    "TPU v5 lite": Peak(
        flops_per_s=197e12, hbm_bytes_per_s=819e9, hbm_bytes=16e9,
        source="Google Cloud TPU documentation, 'TPU v5e': 197 TFLOP/s "
               "bf16, 16 GB HBM2 at 819 GB/s per chip"),
}


def peak_for(device_kind: str) -> Peak:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peak for device kind "
                       f"{device_kind!r}; add it to PEAKS with its source")


class NoChip(SystemExit):
    """The run is on the wrong machine: no result is printed."""


def check_device(chips: int, require_tpu: bool = True) -> dict:
    import jax

    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    if require_tpu and dev["platform"] != "tpu":
        raise NoChip(f"bench: needs a TPU; JAX found platform "
                     f"{dev['platform']!r} ({dev['kind']})")
    if dev["count"] < chips:
        raise NoChip(f"bench: the cell needs {chips} chips, JAX sees "
                     f"{dev['count']}")
    if require_tpu:
        peak_for(dev["kind"])
    return dev


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest of ``devices`` (0 where the backend
    keeps no count, as the CPU does)."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks, default=0)
