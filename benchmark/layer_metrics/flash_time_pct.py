"""Share of the window the three flash-attention kernels take on a chip."""
from ..trace import reduce as R
from ._flash import kernel_times

LAYER = "Flash kernels"
UNIT = "%"
MOVES = "train_tokens_per_s_per_chip"
SOURCE = "device_trace"


def read(trace, counters, ctx):
    if trace is None:
        return None
    total = sum(s for s, _ in kernel_times(trace).values())
    return 100.0 * total / R.window_s(trace) if total else None
