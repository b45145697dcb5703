"""Median idle gap on the chip between one unified-step execution and the
next: the engine's host loop (commit, admission, row building, dispatch).
With 8 of 32 layers the step is a quarter of a deployment's, so this gap is
four times the share of a step that a deployment sees."""
from ..trace import reduce as R

LAYER = "Serve host loop"
UNIT = "ms"
MOVES = "tpot_p50_ms"
SOURCE = "device_trace"


def read(trace, counters, ctx):
    runs = trace and R.module_runs(trace, counters["main_module"])
    if not runs or not runs["gaps_ns"]:
        return None
    return R.median(runs["gaps_ns"]) / 1e6
