"""The accelerator a run measures, and its published peaks.

A run needs a TPU: with no accelerator, or fewer chips than its cell asks
for, it fails before it prints a result. There is no CPU fallback.
"""
from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


class NoAccelerator(RuntimeError):
    pass


def check(chips: int) -> dict:
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise NoAccelerator(
            f"this benchmark measures a TPU; JAX's default device platform "
            f"is {dev.platform!r} ({len(devices)} device(s))")
    if len(devices) < chips:
        raise NoAccelerator(f"the cell needs {chips} TPU chips, JAX sees "
                            f"{len(devices)}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


def peaks(kind: str) -> dict:
    """The peaks of a ``device_kind`` from ``peaks.json``; a device that is
    not in the table is an error."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise KeyError(f"no published peaks for device kind {kind!r} in "
                       f"peaks.json (known: {sorted(table)})")
    return table[kind]


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest of ``devices``."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)
