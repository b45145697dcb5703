"""The flash kernels' share of their roofline: the least time the chip
could take for the calls the trace holds (operations and bytes from the
shapes, `benchmark/kernel_costs.py`; compute-bound at these shapes) over
the time they took."""
from .. import cells, kernel_costs
from ._flash import kernel_times

LAYER = "Flash kernels"
UNIT = "%"
MOVES = "train_tokens_per_s_per_chip"
SOURCE = "device_trace"


def read(trace, counters, ctx):
    if trace is None or ctx.peaks is None:
        return None
    shape = cells.family_module(ctx.config).attention_shape(ctx.config)
    bh = counters["batch_per_chip"] * shape["heads"]
    least = took = 0.0
    for kernel, (seconds, calls) in kernel_times(trace).items():
        flops, bytes_ = kernel_costs.flash_cost(
            kernel, bh, counters["sequence_length"], shape["head_dim"])
        least += calls * kernel_costs.min_seconds(flops, bytes_, ctx.peaks)
        took += seconds
    return 100.0 * least / took if took else None
