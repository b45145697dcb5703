"""Shared by the Mamba-1 recurrence's metrics: the kernel's events in the
trace and what one Mamba-1 layer's recurrence must do at the least in one
step.

The kernel is found by the name its `pallas_call` carries into the
instruction (`selective_scan`, `paddle_tpu/ops/ssm.py`); one Mamba-1 layer
calls it once a step. (`_ssm.py` is Mamba-2's: another kernel, `ssm_update`,
whose decays are a scalar a head and arrive precomputed.)

The least one layer's recurrence needs in one step, from the rows whose
state the step advances (`rows`: the pool's active rows), the step's live
positions (`live`), `channels` = d_inner channels and N state elements a
channel:
  operations  7 * channels * N a live position: per state element the
              decay's product dt * A and its exp (2), the update's two
              multiplies and its add (3: decay * h, (dt c) * B, the sum),
              the read-out's multiply and add (2). dt * c is once a
              channel, 1/N of one more, and is left out.
  bytes       each active row's state [channels, N] read once and written
              once in its storage type; A [channels, N] float32 once a call;
              for each live position c and dt read and y written
              (3 * channels) and B and C read (2 * N), in the activation
              type.
A slot that is free, or whose row waits, has no state to move: the share
falls if the kernel moves it anyway, and it falls by whatever an
implementation moves beyond this (dt in float32, columns nobody reads). At
a decode step the state is nearly all of the bytes: the layer is bound by
moving it.
"""
from ..trace import reduce as R

KERNEL = "selective_scan"
CALLS_PER_LAYER = 1


def kernel_time(trace) -> tuple:
    """(seconds, calls) of the recurrence kernel, per chip."""
    return R.op_time_s(trace, KERNEL, opcode="custom-call")


def layer_cost(rows: float, live: float, channels: int, state: int,
               state_itemsize: int = 4, itemsize: int = 2) -> tuple:
    """(operations, bytes) of one Mamba-1 layer's recurrence in one step."""
    flops = 7.0 * channels * state * live
    bytes_ = 2 * rows * channels * state * state_itemsize \
        + channels * state * 4 \
        + live * (3 * channels + 2 * state) * itemsize
    return flops, bytes_


def mamba_shape(config: dict):
    """(channels, state elements a channel, the conv's carried columns), or
    None for a configuration without Mamba-1 layers (Mamba-2's has no
    `mamba_dt_rank`)."""
    try:
        config["mamba_dt_rank"]
        return (config["mamba_expand"] * config["hidden_size"],
                config["mamba_d_state"], config["mamba_d_conv"] - 1)
    except KeyError:
        return None


def mamba_layers(config: dict) -> int:
    from ..reference.jamba import layer_types
    return layer_types(config).count("mamba")


def state_itemsize(config: dict, slots: int, itemsize: int = 2):
    """Bytes of one state element as the program's pool holds it, from the
    program's own gauge of its recurrent state (every slot and Mamba layer:
    the conv's carried columns in the activation type and the state), or
    None where the program keeps no such gauge."""
    from paddle_tpu.serving import metrics
    held = getattr(metrics, "RECURRENT_STATE_BYTES", 0)
    if not held or not slots:
        return None
    channels, state, carried = mamba_shape(config)
    a_slot = held / (slots * mamba_layers(config))
    return (a_slot - carried * channels * itemsize) / (state * channels)
