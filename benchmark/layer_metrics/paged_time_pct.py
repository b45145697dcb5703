"""Share of the window the paged-attention kernel takes on the chip."""
from ..trace import reduce as R

LAYER = "Paged kernel"
UNIT = "%"
MOVES = "tpot_p50_ms"
SOURCE = "device_trace"


def read(trace, counters, ctx):
    if trace is None:
        return None
    seconds, calls = R.op_time_s(trace, "paged_attention",
                                 opcode="custom-call")
    return 100.0 * seconds / R.window_s(trace) if calls else None
