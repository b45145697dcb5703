"""Mean time between the launch that first carried a chunk of a request and
the launch that carried its last, `[first_launch, final_launch)`: a period
for each chunk but the last, plus deferred passes; 0 for a prompt of one
chunk. The `prefill_ms` stat of the program's
`pdtpu/serve/request/first_token` events in the window; a mean."""
from ..trace import request_spans as Q

LAYER = "Request path"
UNIT = "ms"
MOVES = "ttft_p50_ms"
SOURCE = "program_span"


def read(trace, counters, ctx):
    return Q.mean_of(trace, "prefill_ms")
