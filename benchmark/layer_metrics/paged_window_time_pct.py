"""Share of the window the windowed paged-attention kernel (`paged_window`:
the window layers' calls) takes on the chip."""
from ..trace import reduce as R
from ._window import kernel_time

LAYER = "Window layers"
UNIT = "%"
MOVES = "tpot_p50_ms"
SOURCE = "device_trace"


def read(trace, counters, ctx):
    if trace is None:
        return None
    seconds, calls = kernel_time(trace)
    return 100.0 * seconds / R.window_s(trace) if calls else None
