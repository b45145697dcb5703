"""Shared by the expert-layer metrics: the grouped-matmul kernel's events in
the trace, what one expert layer must do at the least in one step, and the
program's table of live assignments per (layer, expert).

The kernel is found by the name its `pallas_call` carries into the
instruction (`moe_gmm`, `paddle_tpu/ops/grouped_matmul.py`); one expert
layer calls it three times (gate, up, down).

The least one expert layer needs in one step, from the step's live
assignments `A` (live positions x experts per token), hidden size `h` and
expert width `f`:
  operations  6 * h * f * A     three matmuls of h x f per assignment
  bytes       the three bf16 matrices (3 * h * f * 2 B) of every expert
              that has a row, min(E, A) of them (at A well above E every
              expert has one), read once; plus each routed row read once
              and its result written once (2 * A * h * 2 B).
At a decode step A is a few assignments an expert and the weights are
nearly all of the bytes: the layer is bound by reading them.
"""
from ..trace import reduce as R

KERNEL = "moe_gmm"
CALLS_PER_LAYER = 3


def kernel_time(trace) -> tuple:
    """(seconds, calls) of the grouped matmuls, per chip."""
    return R.op_time_s(trace, KERNEL, opcode="custom-call")


def layer_cost(assignments: float, experts: int, hidden: int, width: int,
               itemsize: int = 2) -> tuple:
    """(operations, bytes) of one expert layer in one step."""
    flops = 6.0 * hidden * width * assignments
    bytes_ = min(experts, assignments) * 3 * hidden * width * itemsize \
        + 2 * assignments * hidden * itemsize
    return flops, bytes_


def assignments_per_step(counters: dict, config: dict):
    """Live positions of a mean step of the window (prompt tokens
    prefilled + output tokens, over the steps) x experts per token."""
    steps = counters.get("steps")
    if not steps:
        return None
    live = (counters["prefill_tokens"] + counters["output_tokens"]) / steps
    return live * config["num_experts_per_tok"]


def expert_tokens(config: dict):
    """The program's process-wide totals as `[layers][experts]`, or None
    where the program keeps no such table or it is empty (a program
    without the dropless layer; a dense model)."""
    from paddle_tpu.nn.layer import moe
    table = getattr(moe, "EXPERT_TOKENS", None)
    if not table:
        return None
    return [[int(table.get((layer, e), 0))
             for e in range(config["num_experts"])]
            for layer in range(config["num_hidden_layers"])]
