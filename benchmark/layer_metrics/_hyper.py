"""Shared by the residual path's metrics: the two hyper-connection kernels'
events in the trace and what one connection must do at the least in one
step.

The kernels are found by the names their `pallas_call`s carry into the
instruction (`hc_pre`, `hc_post`, `paddle_tpu/ops/hyper_connection.py`); a
connection calls each once a step, and a layer has two connections (round
its attention and round its FFN). A program without them (one residual
stream a token) leaves no such event and the readers return nothing.

The least one connection needs in one step, over the step's computed
positions `P` (the packed step computes `step_tokens` positions whatever
the mix of rows), with n = `hc_mult` streams of C = `hidden_size` columns
in the activation type:
  bytes       the streams `X [P, n C]` read twice (once for the coefficients
              and the sublayer's input, once, after the sublayer, for the
              mixing: the sublayer's result stands between the two) and
              written once; the sublayer's input `u [P, C]` written once and
              its result `y [P, C]` read once; `phi [n C, n^2 + 2 n]` read
              once a call, in the type the weights are held in. The
              coefficients themselves (n^2 + 2 n numbers a position) are
              left out: bytes counted low, never high.
  operations  `x' phi` (2 n C (n^2 + 2 n) a position), `u` (2 n C) and the
              mixing (2 (n^2 + n) C); the Sinkhorn passes are a few hundred
              operations a position beside these and are left out.
At every shape here the bytes decide: the connection is memory-bound.
"""
from ..trace import reduce as R

KERNELS = ("hc_pre", "hc_post")
# the program's `serving.llm.llm_engine.MIN_STEP_TOKENS`
MIN_STEP_TOKENS = 512


def kernel_time(trace) -> tuple:
    """(seconds, calls) of both kernels together, per chip."""
    return R.op_time_s(trace, *KERNELS, opcode="custom-call")


def streams(config: dict):
    """(n, C), or None for a configuration with one residual stream."""
    n = config.get("hc_mult", 1)
    return (n, config["hidden_size"]) if n > 1 else None


def computed_positions(counters: dict):
    """Positions one step computes: slots x chunk, packed to
    `MIN_STEP_TOKENS` where the engine is wider (no draft window)."""
    slots, chunk = counters.get("slots"), counters.get("prefill_chunk")
    if not slots or not chunk:
        return None
    return min(slots * chunk, max(slots + chunk, MIN_STEP_TOKENS))


def connection_cost(positions: float, n: int, C: int,
                    itemsize: int = 2) -> tuple:
    """(operations, bytes) of one connection in one step."""
    k = n * n + 2 * n
    flops = positions * (2.0 * n * C * k + 2.0 * n * C
                         + 2.0 * (n * n + n) * C)
    bytes_ = positions * (3 * n * C + 2 * C) * itemsize \
        + n * C * k * itemsize
    return flops, bytes_
