"""Shared by the device metrics of every job kind."""
from ..trace import reduce as R


def idle_pct(trace):
    if trace is None:
        return None
    return 100.0 * (1.0 - R.busy_s(trace) / R.window_s(trace))


def hbm_peak_gb(counters):
    held = counters.get("memory_window_bytes")
    return held / 1e9 if held else None
