"""Mean host time a unified step spent preparing and launching it: the
program's `pdtpu/serve/draft` (only with a draft model), `build_rows` (row
set, fault-injection kinds, sampling and adapter operands) and `dispatch`
(operand upload, block table, the jitted call until it returns: the launch,
not the run) spans inside the window, over the runs of `jit_step`."""
from ..trace import host_spans as H

LAYER = "Serve host loop"
UNIT = "ms"
MOVES = "tpot_p50_ms"
SOURCE = "program_span"


def read(trace, counters, ctx):
    return H.ms_per_step(trace, counters, H.DRAFT, H.BUILD_ROWS,
                         H.DISPATCH)
