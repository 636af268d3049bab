"""What the benchmark may run on: a TPU in ``peaks.json``, nothing else."""

from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"


class DeviceError(RuntimeError):
    """No chip the benchmark can measure on."""


def peaks_for(kind: str, path: Path = PEAKS) -> dict:
    """The published peaks of one chip of ``kind``; an unknown kind is an
    error, never a default."""
    table = json.loads(path.read_text())["devices"]
    if kind not in table:
        raise DeviceError(f"no published peaks for device kind {kind!r} "
                          f"in {path.name} (known: {sorted(table)})")
    return table[kind]


def matmul_peak(peaks: dict, precision: str, path: Path = PEAKS) -> float:
    """FLOP/s of one chip for float32 matmuls at ``precision``: the bf16
    peak over the bf16 passes that precision takes."""
    passes = json.loads(path.read_text())["float32_matmul_passes"]
    if precision not in passes:
        raise DeviceError(f"no pass count for matmul precision "
                          f"{precision!r} in {path.name}")
    return peaks["bf16_flops_per_s"] / passes[precision]


def describe(devices) -> dict:
    return {"platform": devices[0].platform if devices else "none",
            "kind": devices[0].device_kind if devices else "none",
            "count": len(devices)}


def guard(devices, chips: int) -> dict:
    """The first ``chips`` devices' peaks, after checking that they are TPUs
    with published peaks; raises ``DeviceError`` otherwise."""
    found = describe(devices)
    if found["platform"] != "tpu":
        raise DeviceError(f"no TPU: JAX found platform {found['platform']!r}")
    if found["count"] < chips:
        raise DeviceError(f"the cell needs {chips} chips, JAX found "
                          f"{found['count']}")
    return peaks_for(found["kind"])
