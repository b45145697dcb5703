"""Share of the window the residual path's two kernels take on the chip
(`hc_pre` + `hc_post`: every hyper-connection of every layer; the few small
XLA operations between them show under `hyper_connection` in the trace and
in `breakdown.device_ops` under their own names)."""
from ..trace import reduce as R
from ._hyper import kernel_time

LAYER = "Residual path"
UNIT = "%"
MOVES = "tpot_p50_ms"
SOURCE = "device_trace"


def read(trace, counters, ctx):
    if trace is None:
        return None
    seconds, calls = kernel_time(trace)
    return 100.0 * seconds / R.window_s(trace) if calls else None
