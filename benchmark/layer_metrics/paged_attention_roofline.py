"""The paged kernel's share of its roofline: the least time the chip could
take to read the pages a step must read, once per KV head (memory-bound;
`benchmark/kernel_costs.py`), over the time the calls took. The resident
tokens per step are sampled from the engine's pool by the load thread."""
from .. import cells, kernel_costs
from ..trace import reduce as R

LAYER = "Paged kernel"
UNIT = "%"
MOVES = "tpot_p50_ms"
SOURCE = "device_trace"


def read(trace, counters, ctx):
    if trace is None or ctx.peaks is None \
            or not counters.get("kv_tokens_per_step"):
        return None
    seconds, calls = R.op_time_s(trace, "paged_attention",
                                 opcode="custom-call")
    if not calls:
        return None
    shape = cells.family_module(ctx.config).attention_shape(ctx.config)
    useful = (counters["prefill_tokens"] + counters["output_tokens"]) \
        / counters["steps"]
    flops, bytes_ = kernel_costs.paged_cost(
        counters["kv_tokens_per_step"], counters["active_rows_per_step"],
        useful, counters["block_len"], shape["heads"], shape["kv_heads"],
        shape["head_dim"])
    least = calls * kernel_costs.min_seconds(flops, bytes_, ctx.peaks)
    return 100.0 * least / seconds
