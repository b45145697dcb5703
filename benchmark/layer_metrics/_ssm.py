"""Shared by the state-space layer's metrics: the recurrence kernel's events
in the trace and what one Mamba-2 layer's recurrence must do at the least
in one step.

The kernel is found by the name its `pallas_call` carries into the
instruction (`ssm_update`, `paddle_tpu/ops/ssm.py`); one Mamba-2 layer
calls it once a step.

The least one layer's recurrence needs in one step, from the rows whose
state the step advances (`rows`: the pool's active rows), the step's live
positions (`live`), H heads of width P and N state channels:
  operations  6 * H * P * N a live position: per state element the decay's
              multiply, the outer product's multiply and its add, then the
              multiply and the add of the read-out (5), and dt * x once a
              row of N (counted as 1 more: an upper bound on what the
              recurrence needs, so the share is not flattered)
  bytes       each active row's state [H, P, N] read once and written once
              in its storage type; for each live position x read and y
              written (2 * H * P), B and C read (2 * N), in the activation
              type, and dt and its decay read (2 * H float32).
A slot that is free, or whose row waits, has no state to move: the share
falls if the kernel moves it anyway. At a decode step the state is nearly
all of the bytes: the layer is bound by moving it.
"""
from ..trace import reduce as R

KERNEL = "ssm_update"
CALLS_PER_LAYER = 1


def kernel_time(trace) -> tuple:
    """(seconds, calls) of the recurrence kernel, per chip."""
    return R.op_time_s(trace, KERNEL, opcode="custom-call")


def layer_cost(rows: float, live: float, heads: int, head_dim: int,
               state: int, state_itemsize: int = 2,
               itemsize: int = 2) -> tuple:
    """(operations, bytes) of one Mamba-2 layer's recurrence in one step."""
    flops = 6.0 * heads * head_dim * state * live
    bytes_ = 2 * rows * heads * head_dim * state * state_itemsize \
        + live * ((2 * heads * head_dim + 2 * state) * itemsize
                  + 2 * heads * 4)
    return flops, bytes_


def mamba_shape(config: dict):
    """(heads, head_dim, state channels), or None for a configuration
    without state-space layers."""
    try:
        return (config["mamba_n_heads"], config["mamba_d_head"],
                config["mamba_d_state"])
    except KeyError:
        return None
