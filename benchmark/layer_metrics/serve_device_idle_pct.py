"""Share of the window in which no operation ran on the chip (1 - union of
the device-op intervals over the window), mean over the chips; serve cells."""
from ._device import idle_pct

LAYER = "Device"
UNIT = "%"
MOVES = "serve_out_tokens_per_s"
SOURCE = "device_trace"


def read(trace, counters, ctx):
    return idle_pct(trace)
