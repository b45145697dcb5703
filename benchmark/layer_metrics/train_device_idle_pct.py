"""Share of the window in which no operation ran on the chip (1 - union of
the device-op intervals over the window), mean over the chips; train cells."""
from ._device import idle_pct

LAYER = "Device"
UNIT = "%"
MOVES = "train_tokens_per_s_per_chip"
SOURCE = "device_trace"


def read(trace, counters, ctx):
    return idle_pct(trace)
