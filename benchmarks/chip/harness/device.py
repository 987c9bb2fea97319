"""The accelerator a run measures: presence, description, peaks, memory."""

from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parents[1] / "peaks.json"


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def require_tpu(chips: int) -> list:
    """The devices of this run; raises :class:`NoAccelerator` unless JAX
    sees at least ``chips`` TPU devices."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoAccelerator(f"JAX found {devices[0].platform!r} devices, "
                            "not a TPU")
    if len(devices) < chips:
        raise NoAccelerator(f"the cell needs {chips} chips, JAX found "
                            f"{len(devices)}")
    return devices


def describe(devices: list) -> dict:
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def peaks(kind: str, path: Path = PEAKS_FILE) -> dict:
    """Published peaks of one chip of ``kind`` (``device_kind`` as JAX
    reports it).  A kind the table does not hold is an error."""
    with Path(path).open() as fh:
        table = json.load(fh)["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in {path}; add "
                       "its published numbers and their source")
    return table[kind]


def memory_peak_bytes(devices: list) -> int:
    """Peak bytes in use on the fullest device (0 where the backend keeps
    no memory statistics)."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak
