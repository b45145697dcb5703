"""Share of the window the exact top-k of the index scores (`index_topk`:
one call a "full" layer and step, 48 counting passes over a row's `[chunk,
context]` block in VMEM) takes on the chip. The decode rows' `lax.top_k` of
one column is XLA's and is not in it."""
from ..trace import reduce as R
from ._sparse import TOPK, kernel_time

LAYER = "Sparse attention"
UNIT = "%"
MOVES = "serve_out_tokens_per_s"
SOURCE = "device_trace"


def read(trace, counters, ctx):
    if trace is None:
        return None
    seconds, calls = kernel_time(trace, TOPK)
    return 100.0 * seconds / R.window_s(trace) if calls else None
