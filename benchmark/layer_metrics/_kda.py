"""Shared by the delta-rule recurrence's metrics: the kernel's events in the
trace and what one Kimi Delta Attention layer's recurrence must do at the
least in one step.

The kernel is found by the name its `pallas_call` carries into the
instruction (`kda_update`, `paddle_tpu/ops/kda.py`); one KDA layer calls it
once a step. (`_ssm.py` and `_selective_scan.py` are the state-space
layers': other kernels, whose recurrences never read the state before they
write it.)

The least one layer's recurrence needs in one step, from the rows whose
state the step advances (`rows`: the pool's active rows), the step's live
positions (`live`), H heads and a state of d x d a head:
  operations  7 * H * d * d a live position: per state element the decay's
              multiply (1), `S^T k`'s multiply and add (2), the update's
              multiply and add (2: k (x) delta, the sum), `S^T q`'s
              multiply and add (2). The exp of the log-decay, beta's
              product and the difference v - S^T k are once a channel, 1/d
              of one more each, and are left out.
  bytes       each active row's state [d, H * d] read once and written once
              in its storage type; for each live position q, k, g and v read
              and o written (5 * H * d) and beta read (H), in the
              activation type.
A slot that is free, or whose row waits, has no state to move: the share
falls if the kernel moves it anyway, and it falls by whatever an
implementation moves beyond this (q, k and g in float32, columns nobody
reads). At a decode step the state is nearly all of the bytes: the layer is
bound by moving it.
"""
from ..trace import reduce as R

KERNEL = "kda_update"
CALLS_PER_LAYER = 1


def kernel_time(trace) -> tuple:
    """(seconds, calls) of the recurrence kernel, per chip."""
    return R.op_time_s(trace, KERNEL, opcode="custom-call")


def layer_cost(rows: float, live: float, heads: int, head_dim: int,
               state_itemsize: int = 4, itemsize: int = 2) -> tuple:
    """(operations, bytes) of one KDA layer's recurrence in one step."""
    state = heads * head_dim * head_dim
    flops = 7.0 * state * live
    bytes_ = 2 * rows * state * state_itemsize \
        + live * (5 * heads * head_dim + heads) * itemsize
    return flops, bytes_


def kda_shape(config: dict):
    """(heads, head width, the conv's channels, its carried columns), or
    None for a configuration without KDA layers."""
    lin = config.get("linear_attn_config")
    if not lin or "kda_allow_neg_eigval" not in config:
        return None
    heads, d = lin["num_heads"], lin["head_dim"]
    return heads, d, 3 * heads * d, lin["short_conv_kernel_size"] - 1


def kda_layers(config: dict) -> int:
    from ..reference.solar_open2 import layer_types
    return layer_types(config).count("kda")


def state_itemsize(config: dict, slots: int, itemsize: int = 2):
    """Bytes of one state element as the program's pool holds it, from the
    program's own gauge of its recurrent state (every slot and KDA layer:
    the conv's carried columns in the activation type and the state), or
    None where the program keeps no such gauge."""
    from paddle_tpu.serving import metrics
    held = getattr(metrics, "RECURRENT_STATE_BYTES", 0)
    layers = kda_layers(config)
    if not held or not slots or not layers:
        return None
    heads, d, channels, carried = kda_shape(config)
    a_slot = held / (slots * layers)
    return (a_slot - carried * channels * itemsize) / (heads * d * d)
