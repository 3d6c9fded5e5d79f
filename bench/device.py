"""The accelerator a run measures: found or refused, never a CPU fallback."""

from __future__ import annotations

import json
import os
import pathlib
from typing import Dict, List

PEAKS_FILE = pathlib.Path(__file__).resolve().parent / "peaks.json"


class NoAccelerator(SystemExit):
    """Raised (as a non-zero exit) when the cell's chips are not there."""


def require(chips: int) -> List:
    """The first ``chips`` TPU devices, or a non-zero exit."""
    if os.environ.get("REPRO_KERNEL_BACKEND"):
        raise NoAccelerator(
            "REPRO_KERNEL_BACKEND is set; the benchmark measures only the "
            "platform's own kernel route"
        )
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoAccelerator(
            f"no TPU: JAX found {len(devs)} {devs[0].platform} device(s)"
        )
    if len(devs) < chips:
        raise NoAccelerator(f"the cell needs {chips} chip(s), JAX found {len(devs)}")
    from repro.kernels.ops import kernel_backend

    if kernel_backend() != "tpu":
        raise NoAccelerator(f"kernel backend is {kernel_backend()!r}, not 'tpu'")
    return devs[:chips]


def describe(devs, memory_peak_bytes: int) -> Dict[str, object]:
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
        "memory_peak_bytes": int(memory_peak_bytes),
    }


def peak_bytes(devs) -> int:
    """Peak bytes in use on the fullest device (0 where it is not reported)."""
    peaks = []
    for d in devs:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def peaks(kind: str) -> Dict[str, float]:
    """Published peaks of one chip of ``kind``; a kind not in the table is
    an error, never a default."""
    table = json.loads(PEAKS_FILE.read_text())
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in {PEAKS_FILE.name}")
    return table[kind]
