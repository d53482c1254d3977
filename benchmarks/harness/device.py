"""The look for the chip, and the table of peaks.

A device that is not a TPU, is not in ``peaks.json`` or is one of too few
is an error: no share of a peak is ever computed against an assumed chip.
"""

from __future__ import annotations

import json
from pathlib import Path


class DeviceError(Exception):
    """The machine does not hold the chips the cell asks for."""


def load_peaks() -> dict:
    with open(Path(__file__).with_name("peaks.json")) as f:
        table = json.load(f)
    return {k: v for k, v in table.items() if not k.startswith("_")}


def peaks_for(device_kind: str) -> dict:
    table = load_peaks()
    if device_kind not in table:
        raise DeviceError(
            f"device_kind {device_kind!r} is not in peaks.json "
            f"(known: {sorted(table)}); add its published peaks, with "
            f"their source, before measuring on it")
    return table[device_kind]


def require_chips(devices, chips: int) -> dict:
    """``devices`` is ``jax.devices()``. Returns the ``device`` object of
    the result line (without the memory peak) or raises."""
    if not devices:
        raise DeviceError("JAX found no device")
    platform = devices[0].platform
    if platform != "tpu":
        raise DeviceError(f"platform is {platform!r}, not 'tpu': a "
                          f"benchmark run needs the accelerator")
    kind = devices[0].device_kind
    peaks_for(kind)
    if len(devices) < chips:
        raise DeviceError(f"the cell needs {chips} chips, JAX found "
                          f"{len(devices)}")
    return {"platform": platform, "kind": kind, "count": len(devices)}


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest of ``devices``."""
    peak = 0
    for dev in devices:
        stats = dev.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak
