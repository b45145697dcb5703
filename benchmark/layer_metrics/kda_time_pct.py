"""Share of the window the linear-attention layers' recurrence takes on the
chip (the `kda_update` kernel; the conv, the norms, the gates and the
projections show under their own names in `breakdown.device_ops`)."""
from ..trace import reduce as R
from ._kda import kernel_time

LAYER = "Linear-attention layer"
UNIT = "%"
MOVES = "tpot_p50_ms"
SOURCE = "device_trace"


def read(trace, counters, ctx):
    if trace is None:
        return None
    seconds, calls = kernel_time(trace)
    return 100.0 * seconds / R.window_s(trace) if calls else None
