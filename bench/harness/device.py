"""The device a run measures, and its published peaks.

A run measures a TPU or nothing: :func:`require_tpu` raises where JAX finds
another platform or fewer chips than the cell asks for, so no CPU number is
ever printed under a device metric's name.
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parents[1] / "peaks.json"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell needs."""


@dataclass(frozen=True)
class Peaks:
    device_kind: str
    bf16_flops_per_s: float
    int8_ops_per_s: float
    hbm_bytes_per_s: float
    hbm_bytes: float
    source: str


def peaks_for(device_kind: str) -> Peaks:
    """The published peaks of one chip of ``device_kind``; a kind that is
    not in the table is an error, never a default."""
    with open(PEAKS_FILE) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(
            f"no peaks for device kind {device_kind!r} in {PEAKS_FILE.name}; "
            f"known: {sorted(table)}"
        )
    row = table[device_kind]
    return Peaks(
        device_kind=device_kind,
        bf16_flops_per_s=float(row["bf16_flops_per_s"]),
        int8_ops_per_s=float(row["int8_ops_per_s"]),
        hbm_bytes_per_s=float(row["hbm_bytes_per_s"]),
        hbm_bytes=float(row["hbm_bytes"]),
        source=row["source"],
    )


def require_tpu(devices, chips: int):
    """Return the first device when ``devices`` holds ``chips`` TPUs or more."""
    dev = devices[0]
    if dev.platform != "tpu":
        raise NoChip(f"no TPU found: JAX runs on {dev.platform!r}")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX finds {len(devices)}")
    return dev


def memory_in_use(devices, chips: int) -> int:
    """Bytes in use now on the fullest chip."""
    return max(int((d.memory_stats() or {}).get("bytes_in_use", 0))
               for d in devices[:chips])


def device_info(devices, chips: int) -> dict:
    """``platform``, ``kind`` and ``count`` as JAX reports them (the count of
    devices the run uses) and ``memory_peak_bytes`` on the fullest chip."""
    used = devices[:chips]
    peak = 0
    for d in used:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {
        "platform": used[0].platform,
        "kind": used[0].device_kind,
        "count": len(used),
        "memory_peak_bytes": peak,
    }
