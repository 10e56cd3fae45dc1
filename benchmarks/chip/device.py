"""The chip a run is on, and its peaks.

A run on anything but a TPU, or on fewer chips than its cell asks for,
is refused: a number from another backend is never written under a
device metric's name.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict

PEAKS = Path(__file__).resolve().parent / "peaks.json"


class NoChip(RuntimeError):
    pass


def peaks(device_kind: str, table: Path = PEAKS) -> Dict:
    """The published peaks of ``device_kind``; an unknown kind is an error."""
    known = json.loads(table.read_text())
    if device_kind not in known:
        raise KeyError(
            f"no peaks for device kind {device_kind!r}; known: {sorted(known)}"
        )
    return known[device_kind]


def require_chips(chips: int) -> Dict:
    """Describe the devices, refusing a host without ``chips`` TPU chips."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devs[0].platform!r} devices")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips; JAX found {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def describe(chips: int) -> Dict:
    """The device record without the TPU requirement (CPU tests only)."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes(chips: int) -> int:
    """Peak bytes in use on the fullest of the first ``chips`` devices."""
    import jax

    peak = 0
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak
