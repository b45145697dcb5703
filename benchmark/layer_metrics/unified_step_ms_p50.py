"""Median time on the chip of one execution of the unified step."""
from ..trace import reduce as R

LAYER = "Unified step"
UNIT = "ms"
MOVES = "tpot_p50_ms"
SOURCE = "device_trace"


def read(trace, counters, ctx):
    runs = trace and R.module_runs(trace, counters["main_module"])
    if not runs:
        return None
    return R.median(runs["durations_ns"]) / 1e6
