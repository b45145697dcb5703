"""Where a Pallas kernel runs: compiled by Mosaic on a TPU, interpreted on
the CPU.

Every `pallas_call` in ops/ takes its `interpret=` from `interpret()` here
and every wrapper that hands a TPU caller the XLA reference instead of its
kernel says so through `note_reference()`, so there is one place that
decides and one counter (`KERNEL_TRACES`) that shows what a trace actually
took — chip_smoke.py and the tests read it.
"""
from __future__ import annotations

import collections
import logging
import threading

import jax

_log = logging.getLogger("paddle_tpu.ops")

# (kernel, path) -> times traced that way. path: "mosaic" | "interpret" for a
# pallas_call, "reference" / "scan" for the XLA code that stood in for one
KERNEL_TRACES: collections.Counter = collections.Counter()
# (kernel, ((name, value), ...)) -> times traced with that tiling: what a
# kernel whose grid and tile follow its input chose for each traced call
KERNEL_TILINGS: collections.Counter = collections.Counter()
_lock = threading.Lock()
_reference_seen = set()


def platform() -> str:
    """Platform of the devices jitted code runs on (the default backend)."""
    return jax.devices()[0].platform


def count(kernel: str, path: str):
    with _lock:
        KERNEL_TRACES[(kernel, path)] += 1


def note_tiling(kernel: str, **tiling):
    """Record, while tracing, the grid and tile `kernel` chose from its
    shapes (ints and tuples of ints; nothing happens at run time)."""
    with _lock:
        KERNEL_TILINGS[(kernel, tuple(sorted(tiling.items())))] += 1


def interpret(kernel: str) -> bool:
    """`interpret=` for the pallas_call of `kernel` being traced now."""
    interp = platform() == "cpu"
    count(kernel, "interpret" if interp else "mosaic")
    return interp


def note_reference(kernel: str, reason: str, *shape_key):
    """A wrapper is returning the XLA reference where `kernel` was asked
    for. Counted always; on an accelerator it is also logged, once per
    (kernel, reason, shape), because there it is a silent slow path."""
    count(kernel, "reference")
    with _lock:
        key = (kernel, reason, shape_key)
        first = key not in _reference_seen
        _reference_seen.add(key)
    if first and platform() != "cpu":
        _log.warning("%s: XLA reference path instead of the Pallas kernel "
                     "(%s) for %s", kernel, reason, shape_key)
