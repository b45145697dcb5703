"""The recurrence kernel's share of its roofline: the least time the chip
could take for what the Mamba-2 layers of the traced steps must do
(`_ssm.py`: the state of the rows active in a mean step read and written
once, the live positions' inputs and outputs, 6*H*P*N operations a live
position; memory-bound at a decode step) over the time the `ssm_update`
calls took. Active rows and live positions of a mean step come from the
job's counts (`active_rows_per_step`, `output_tokens`, `prefill_tokens`,
`steps`)."""
from .. import kernel_costs
from . import _ssm

LAYER = "State-space layer"
UNIT = "%"
MOVES = "tpot_p50_ms"
SOURCE = "device_trace"


def read(trace, counters, ctx):
    shape = _ssm.mamba_shape(ctx.config)
    if trace is None or ctx.peaks is None or shape is None \
            or not counters.get("steps") \
            or not counters.get("active_rows_per_step"):
        return None
    seconds, calls = _ssm.kernel_time(trace)
    if not calls:
        return None
    live = (counters["prefill_tokens"] + counters["output_tokens"]) \
        / counters["steps"]
    flops, bytes_ = _ssm.layer_cost(counters["active_rows_per_step"], live,
                                    *shape)
    least = calls / _ssm.CALLS_PER_LAYER \
        * kernel_costs.min_seconds(flops, bytes_, ctx.peaks)
    return 100.0 * least / seconds
