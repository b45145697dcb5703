"""Of the chip's idle time between consecutive runs of `jit_step`, the
share that some `pdtpu/serve/` span other than `pump` and `fetch` covers, on
the trace's clock: how much of `step_gap_ms_p50` the program's spans
explain. The rest is the end of `fetch` (the result's way back to the host),
launch latency inside the device runtime and the scheduler thread's loop.
Needs a device plane: left out on the CPU."""
from ..trace import host_spans as H

LAYER = "Serve host loop"
UNIT = "%"
MOVES = "tpot_p50_ms"
SOURCE = "program_span"


def read(trace, counters, ctx):
    return H.gap_attributed_pct(trace, counters)
