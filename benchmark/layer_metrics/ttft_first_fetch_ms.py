"""Mean time from the launch of a request's last chunk to the commit of its
first token, `[final_launch, first_token]`: that step queued behind its
predecessor, run, fetched and committed. The `first_fetch_ms` stat of the
program's `pdtpu/serve/request/first_token` events in the window; a mean."""
from ..trace import request_spans as Q

LAYER = "Request path"
UNIT = "ms"
MOVES = "ttft_p50_ms"
SOURCE = "program_span"


def read(trace, counters, ctx):
    return Q.mean_of(trace, "first_fetch_ms")
