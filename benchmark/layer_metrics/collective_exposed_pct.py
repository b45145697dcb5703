"""Share of the window in which a chip's core sat in a collective
instruction (a synchronous collective, or the `-done` of an asynchronous
one) and so ran no compute; mean over the chips."""
from ..trace import reduce as R

LAYER = "SPMD step"
UNIT = "%"
MOVES = "train_tokens_per_s_per_chip"
SOURCE = "device_trace"


def read(trace, counters, ctx):
    if trace is None:
        return None
    devs = trace["devices"]
    exposed = sum(d["collective_exposed_ns"] for d in devs) / len(devs) / 1e9
    return 100.0 * exposed / R.window_s(trace)
