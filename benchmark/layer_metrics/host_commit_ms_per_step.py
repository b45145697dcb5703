"""Mean host time a unified step spent after its result arrived: the
program's `pdtpu/serve/commit` (draft acceptance, emission, retire, finish)
and `publish` (the gauges a pump pass refreshes) spans inside the window,
over the runs of `jit_step`."""
from ..trace import host_spans as H

LAYER = "Serve host loop"
UNIT = "ms"
MOVES = "tpot_p50_ms"
SOURCE = "program_span"


def read(trace, counters, ctx):
    return H.ms_per_step(trace, counters, H.COMMIT, H.PUBLISH)
