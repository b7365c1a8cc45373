"""The device gate, the device line of the result, and the peaks table."""
from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


class NoChip(RuntimeError):
    """JAX runs on no accelerator, or on fewer chips than the cell asks."""


def require_chips(chips: int):
    """The first ``chips`` TPU devices, or NoChip.  Never falls back."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX runs on {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX sees {len(devs)}")
    return devs[:chips]


def peaks(device_kind: str) -> dict:
    """Published peaks of ``device_kind``; a device not in the table is an
    error, never a default."""
    with open(PEAKS_FILE) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE.name}")
    return table[device_kind]


def peak_bytes(devs) -> int:
    """``peak_bytes_in_use`` of the fullest of ``devs`` (0 where the
    backend reports none, as the CPU does)."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)


def line(devs) -> dict:
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak_bytes(devs)}
