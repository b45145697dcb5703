"""Share of the step's rows lost to slot turnover: the slots that carried no
row in a launched step while as many requests were queued
(`slots_vacant_queued` of the program's `pdtpu/serve/dispatch` events in
the window), over launches x slots. A slot freed when step k is retired,
after step k+1 was launched, counts one. 0 wherever nobody waits."""
from ..trace import request_spans as Q

LAYER = "Request path"
UNIT = "%"
MOVES = "ttft_p50_ms"
SOURCE = "program_span"


def read(trace, counters, ctx):
    return Q.vacant_queued_pct(trace, counters.get("slots"))
