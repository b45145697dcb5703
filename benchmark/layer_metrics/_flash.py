"""Shared by the flash metrics: the three kernels' events in the trace."""
from ..trace import reduce as R

KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")


def kernel_times(trace) -> dict:
    """{kernel: (seconds, calls)} per chip. `flash_fwd` also matches the
    forward kernel under jvp and under recompute (`jvp_flash_fwd_`)."""
    return {k: R.op_time_s(trace, k, opcode="custom-call") for k in KERNELS}
