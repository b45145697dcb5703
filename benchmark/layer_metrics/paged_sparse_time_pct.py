"""Share of the window the sparse layers' latent attention (`paged_sparse`:
every call of a layer under an indexer, the gathered walk and the walk
under a mask alike) takes on the chip."""
from ..trace import reduce as R
from ._sparse import SPARSE, kernel_time

LAYER = "Sparse attention"
UNIT = "%"
MOVES = "serve_out_tokens_per_s"
SOURCE = "device_trace"


def read(trace, counters, ctx):
    if trace is None:
        return None
    seconds, calls = kernel_time(trace, SPARSE)
    return 100.0 * seconds / R.window_s(trace) if calls else None
