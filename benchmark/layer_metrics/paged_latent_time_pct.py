"""Share of the window the latent paged-attention kernel (`paged_latent`:
the multi-head-latent-attention layers' calls) takes on the chip."""
from ..trace import reduce as R
from ._latent import kernel_time

LAYER = "Latent layers"
UNIT = "%"
MOVES = "tpot_p50_ms"
SOURCE = "device_trace"


def read(trace, counters, ctx):
    if trace is None:
        return None
    seconds, calls = kernel_time(trace)
    return 100.0 * seconds / R.window_s(trace) if calls else None
