"""The device a run is on: JAX's record of it, and its peaks.

``require_tpu`` is the first thing a run does: without as many TPU
chips as the cell asks for there is nothing to measure, and the run
stops before any set-up.  ``peaks`` reads ``peaks.json``, keyed by
``device_kind``; a device that is not in the table is an error.
"""
from __future__ import annotations

import json
import os

import jax

HERE = os.path.dirname(os.path.abspath(__file__))


class NoDevice(RuntimeError):
    pass


def require_tpu(chips: int) -> list:
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoDevice(f"no TPU: JAX found {devices[0].platform!r} devices "
                       f"only; this benchmark measures the chip")
    if len(devices) < chips:
        raise NoDevice(f"the cell asks for {chips} chips, JAX found "
                       f"{len(devices)}")
    return devices[:chips]


def record(devices) -> dict:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
            "memory_peak_bytes": peak}


def peaks(kind: str, path: str = os.path.join(HERE, "peaks.json")) -> dict:
    with open(path) as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in {path}; "
                       f"known: {sorted(table)}")
    return table[kind]
