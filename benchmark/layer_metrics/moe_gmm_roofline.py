"""The grouped matmuls' share of their roofline: the least time the chip
could take for what the expert layers of the traced steps must do (`_moe.py`:
the weights of the experts that have a row read once, 6*h*f operations an
assignment; memory-bound at a decode step) over the time the `moe_gmm`
calls took. The live assignments of a mean step come from the job's counts
(`output_tokens`, `prefill_tokens`, `steps`)."""
from .. import kernel_costs
from . import _moe

LAYER = "Expert layer"
UNIT = "%"
MOVES = "tpot_p50_ms"
SOURCE = "device_trace"


def read(trace, counters, ctx):
    if trace is None or ctx.peaks is None \
            or "num_experts" not in ctx.config:
        return None
    seconds, calls = _moe.kernel_time(trace)
    assignments = _moe.assignments_per_step(counters, ctx.config)
    if not calls or not assignments:
        return None
    flops, bytes_ = _moe.layer_cost(
        assignments, ctx.config["num_experts"], ctx.config["hidden_size"],
        ctx.config["intermediate_size"])
    layer_steps = calls / _moe.CALLS_PER_LAYER
    least = layer_steps * kernel_costs.min_seconds(flops, bytes_, ctx.peaks)
    return 100.0 * least / seconds
