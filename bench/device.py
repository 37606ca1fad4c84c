"""Which chip a run is on, its published peaks, and its peak memory.

A run names its device in every line it prints.  Anything but a TPU
listed in ``peaks.json``, with as many chips as the cell asks for, is
refused: the benchmark never falls back to the CPU.
"""

from __future__ import annotations

import json
import os
from typing import Dict, List

from .spec import BENCH_DIR


class DeviceError(RuntimeError):
    """The machine cannot run this cell: no TPU, too few chips, or a
    chip whose peaks the table does not hold."""


def load_peaks(path: str = os.path.join(BENCH_DIR, "peaks.json")) -> dict:
    with open(path) as f:
        return json.load(f)


def peaks_for(kind: str, table: dict) -> Dict[str, float]:
    """Published peaks of ``kind`` (JAX's ``device_kind``); an unknown
    kind is an error, never a default."""
    try:
        return dict(table["devices"][kind])
    except KeyError:
        raise DeviceError(
            f"no peaks for device kind {kind!r} in peaks.json "
            f"(have {sorted(table['devices'])})") from None


def check(devices: List, chips: int, table: dict) -> Dict[str, float]:
    """Refuse anything but ``chips`` or more TPU chips of a known kind;
    returns the chip's peaks."""
    if not devices:
        raise DeviceError("JAX found no device")
    platform = devices[0].platform
    if platform != "tpu":
        raise DeviceError(f"needs a TPU; JAX found platform {platform!r}")
    if len(devices) < chips:
        raise DeviceError(f"the cell asks for {chips} chips; JAX found "
                          f"{len(devices)}")
    return peaks_for(devices[0].device_kind, table)


def describe(devices: List, chips: int) -> dict:
    """The ``device`` object of the result line: platform, kind, the
    chips used, and the peak bytes on the fullest of them."""
    used = devices[:chips]
    peak = 0
    for d in used:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": used[0].platform, "kind": used[0].device_kind,
            "count": len(used), "memory_peak_bytes": peak}
