"""Mean number of unified steps committed between a request's admission and
its first token (`steps_to_first_token` of the program's
`pdtpu/serve/request/first_token` events in the window): its chunks, plus
the step that was in flight when it was admitted, plus deferred passes. A
TTFT in the engine's own unit, which does not depend on how long a step
is."""
from ..trace import request_spans as Q

LAYER = "Request path"
UNIT = "steps"
MOVES = "ttft_p50_ms"
SOURCE = "program_span"


def read(trace, counters, ctx):
    return Q.mean_of(trace, Q.STEPS)
