"""Share of the window the Mamba-1 layers' recurrence takes on the chip
(the `selective_scan` kernel; the conv, the three norms, the gate and the
projections show under their own names in `breakdown.device_ops`)."""
from ..trace import reduce as R
from ._selective_scan import kernel_time

LAYER = "State-space layer"
UNIT = "%"
MOVES = "tpot_p50_ms"
SOURCE = "device_trace"


def read(trace, counters, ctx):
    if trace is None:
        return None
    seconds, calls = kernel_time(trace)
    return 100.0 * seconds / R.window_s(trace) if calls else None
