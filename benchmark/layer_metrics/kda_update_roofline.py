"""The delta-rule recurrence kernel's share of its roofline: the least time
the chip could take for what the KDA layers of the traced steps must do
(`_kda.py`: the state of the rows active in a mean step read and written
once in the type the pool holds it in, the live positions' q, k, g, v, beta
and o, 7 operations a state element a live position; memory-bound at a
decode step) over the time the `kda_update` calls took. Active rows and
live positions of a mean step come from the job's counts
(`active_rows_per_step`, `output_tokens`, `prefill_tokens`, `steps`), the
state's width from the program's gauge of its recurrent state over the
job's `slots`."""
from .. import kernel_costs
from . import _kda as K

LAYER = "Linear-attention layer"
UNIT = "%"
MOVES = "tpot_p50_ms"
SOURCE = "device_trace"


def read(trace, counters, ctx):
    shape = K.kda_shape(ctx.config)
    if trace is None or ctx.peaks is None or shape is None \
            or not counters.get("steps") \
            or not counters.get("active_rows_per_step"):
        return None
    seconds, calls = K.kernel_time(trace)
    width = K.state_itemsize(ctx.config, counters.get("slots"))
    if not calls or width is None:
        return None
    live = (counters["prefill_tokens"] + counters["output_tokens"]) \
        / counters["steps"]
    flops, bytes_ = K.layer_cost(counters["active_rows_per_step"], live,
                                 shape[0], shape[1], state_itemsize=width)
    least = calls / K.CALLS_PER_LAYER \
        * kernel_costs.min_seconds(flops, bytes_, ctx.peaks)
    return 100.0 * least / seconds
