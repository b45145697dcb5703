"""Share of the window the expert layers' grouped matmuls take on the
chip (the `moe_gmm` kernel; the dispatch's sort, gathers and the rest of
the layer show under their own names in `breakdown.device_ops`)."""
from ..trace import reduce as R
from ._moe import kernel_time

LAYER = "Expert layer"
UNIT = "%"
MOVES = "tpot_p50_ms"
SOURCE = "device_trace"


def read(trace, counters, ctx):
    if trace is None:
        return None
    seconds, calls = kernel_time(trace)
    return 100.0 * seconds / R.window_s(trace) if calls else None
