"""Share of the window the indexer's scoring (`index_score`: one call a
"full" layer and step, every resident index key of every active row against
the step's index queries) takes on the chip."""
from ..trace import reduce as R
from ._sparse import SCORE, kernel_time

LAYER = "Sparse attention"
UNIT = "%"
MOVES = "serve_out_tokens_per_s"
SOURCE = "device_trace"


def read(trace, counters, ctx):
    if trace is None:
        return None
    seconds, calls = kernel_time(trace, SCORE)
    return 100.0 * seconds / R.window_s(trace) if calls else None
