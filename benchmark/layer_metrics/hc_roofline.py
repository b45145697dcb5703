"""The hyper-connection kernels' share of their roofline: the least time
the chip could take for what the connections of the traced steps must do
(`_hyper.py`: the streams read twice and written once, the sublayer's input
out and its result in, `phi` once, over the step's computed positions;
memory-bound) over the time the `hc_pre` and `hc_post` calls took. A
connection is one call of each, so the connections-steps in the window are
half the calls."""
from .. import kernel_costs
from . import _hyper as H

LAYER = "Residual path"
UNIT = "%"
MOVES = "tpot_p50_ms"
SOURCE = "device_trace"


def read(trace, counters, ctx):
    shape = H.streams(ctx.config)
    positions = H.computed_positions(counters)
    if trace is None or ctx.peaks is None or shape is None \
            or not positions:
        return None
    seconds, calls = H.kernel_time(trace)
    if not calls:
        return None
    flops, bytes_ = H.connection_cost(positions, *shape)
    least = calls / len(H.KERNELS) \
        * kernel_costs.min_seconds(flops, bytes_, ctx.peaks)
    return 100.0 * least / seconds
