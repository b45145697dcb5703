"""Mean time a request whose first token fell in the window spent in a class
queue, `[arrival, admitted)`: no free slot, or the pump sat in `fetch` and
had not come round to `admit`. The `queued_ms` stat of the program's
`pdtpu/serve/request/first_token` events; a mean, so that the four
`ttft_*_ms` add up to the mean of the engine's own TTFT. Left out where the
trace holds no such event (a program without them)."""
from ..trace import request_spans as Q

LAYER = "Request path"
UNIT = "ms"
MOVES = "ttft_p50_ms"
SOURCE = "program_span"


def read(trace, counters, ctx):
    return Q.mean_of(trace, "queued_ms")
