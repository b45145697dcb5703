"""The device a run is on, the benchmark's own table of peaks, and the
refusal to measure on anything else than the cell asks for."""
from __future__ import annotations

import json
import os

from .cells import BENCH_DIR


class DeviceError(RuntimeError):
    """The cell cannot be measured on the devices JAX came up on."""


def load_peaks() -> dict:
    with open(os.path.join(BENCH_DIR, "peaks.json")) as f:
        table = json.load(f)
    return {k: v for k, v in table.items() if not k.startswith("_")}


def require_devices(cell: dict) -> dict:
    """{"platform", "kind", "count"} of the devices this run uses, or a
    DeviceError. There is no CPU fallback: a cell that asks for a TPU and
    finds none fails here, before anything is built."""
    import jax
    devs = jax.devices()
    platform, kind = devs[0].platform, devs[0].device_kind
    if platform != cell["platform"]:
        raise DeviceError(
            f"cell {cell['name']!r} needs platform {cell['platform']!r}, "
            f"JAX came up on {platform!r} ({kind!r} x {len(devs)}); "
            "refusing to measure")
    if len(devs) < cell["chips"]:
        raise DeviceError(
            f"cell {cell['name']!r} needs {cell['chips']} device(s), JAX "
            f"found {len(devs)}")
    return {"platform": platform, "kind": kind, "count": cell["chips"]}


def peaks_for(device: dict) -> dict | None:
    """The peak row for this device kind. None only on the CPU, where the
    test cells report counts and no utilisation; an unknown accelerator is
    an error."""
    if device["platform"] == "cpu":
        return None
    table = load_peaks()
    if device["kind"] not in table:
        raise DeviceError(
            f"device kind {device['kind']!r} is not in benchmark/peaks.json "
            f"(known: {sorted(table)}); add its published peaks with their "
            "source, there is no default")
    return table[device["kind"]]


def memory_bytes(n_chips: int, key: str) -> int:
    """`memory_stats()[key]` of the fullest of the chips the cell uses:
    `bytes_in_use` now, or `peak_bytes_in_use` since the process began
    (set-up's transients included). 0 where the backend keeps no such
    count (the CPU)."""
    import jax
    return max(int((d.memory_stats() or {}).get(key, 0))
               for d in jax.local_devices()[:n_chips])
