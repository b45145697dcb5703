"""Mean host time a unified step spent admitting: the program's
`pdtpu/serve/admit` spans (dropping expired queued requests, `_admit`, and
inside it every `pdtpu/serve/evict`, one `PrefixCache.evict_for_pressure`)
inside the window, over the runs of `jit_step` in the window. A mean, so
that the three `host_*_ms_per_step` add up to the host's part of the gap.
Left out where the trace holds no such span (a program without them)."""
from ..trace import host_spans as H

LAYER = "Serve host loop"
UNIT = "ms"
MOVES = "tpot_p50_ms"
SOURCE = "program_span"


def read(trace, counters, ctx):
    return H.ms_per_step(trace, counters, H.ADMIT)
