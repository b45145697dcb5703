"""Shared by the window-layer metrics: the windowed walk's events in the
trace and what one such call must read at the least.

The kernel is found by the name its `pallas_call` carries into the
instruction (`paged_window`, `paddle_tpu/ops/paged_attention.py`; the name
does not hold `paged_attention`, which the full walk's readers find by
substring). One window layer calls it once a step.

The least one call needs is `kernel_costs.paged_cost`'s, with the keys
inside the rows' windows where the full walk has the keys resident: every
page that cuts a row's window, of K and of V, read once per KV head, the
queries read and the outputs written once (memory-bound). The keys inside
the windows are the program's own count, `LLMMetrics.counters
["window_kv_tokens"]`: per committed step the sum over its active rows of
min(length after the step, window), which the job reads at the window's
two ends (`jobs/serve_closed_loop_long.py`) and divides by the steps
between. A row's window cuts a page at either edge and `paged_cost` rounds
up by half a page a row, so the bytes are counted low, never high."""
from .. import cells, kernel_costs
from ..trace import reduce as R

KERNEL = "paged_window"


def kernel_time(trace) -> tuple:
    """(seconds, calls) of the windowed walks, per chip."""
    return R.op_time_s(trace, KERNEL, opcode="custom-call")


def call_cost(counters: dict, config: dict):
    """(operations, bytes) of one windowed call of a mean step of the
    window, or None where the program or the job left no count."""
    kv = counters.get("window_kv_tokens_per_step")
    rows = counters.get("active_rows_per_step")
    if not kv or not rows or not counters.get("steps"):
        return None
    shape = cells.family_module(config).attention_shape(config)
    useful = (counters["prefill_tokens"] + counters["output_tokens"]) \
        / counters["steps"]
    return kernel_costs.paged_cost(
        kv, rows, useful, counters["block_len"], shape["heads"],
        shape["kv_heads"], shape["head_dim"])
