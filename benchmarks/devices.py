"""The device the run is on: the table of peaks, and the refusal to run elsewhere."""

from __future__ import annotations

import json
import os

_PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks_for(device_kind: str) -> dict:
    """Published peaks of one chip of ``device_kind``; an unknown kind is an error."""
    with open(_PEAKS_FILE) as f:
        table = json.load(f)
    entry = table.get(device_kind)
    if device_kind.startswith("_") or not isinstance(entry, dict):
        raise KeyError(
            f"device kind {device_kind!r} is not in {_PEAKS_FILE}: add its published "
            "peaks with their source; there is no default")
    return entry


def require_chips(chips: int):
    """The ``chips`` accelerators this cell runs on, or SystemExit: the benchmark
    never falls back to the CPU."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"no accelerator: JAX runs on {devices[0].platform!r}")
    if len(devices) < chips:
        raise SystemExit(f"the cell needs {chips} chips, JAX sees {len(devices)}")
    peaks_for(devices[0].device_kind)
    return devices[:chips]
