"""Mean time a request had a slot bound and rode no launched step yet,
`[admitted, first_launch)`: admission's own work (the prefix probe, page
attaches and copies) and every pass in which its first chunk was deferred
by the step's token budget. The `bound_ms` stat of the program's
`pdtpu/serve/request/first_token` events in the window; a mean."""
from ..trace import request_spans as Q

LAYER = "Request path"
UNIT = "ms"
MOVES = "ttft_p50_ms"
SOURCE = "program_span"


def read(trace, counters, ctx):
    return Q.mean_of(trace, "bound_ms")
