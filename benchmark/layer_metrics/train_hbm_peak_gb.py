"""The most the fullest chip held inside the window: the largest sample of
`memory_stats()["bytes_in_use"]` (window open and close, and while a step
runs), so set-up's transients do not count; train cells."""
from ._device import hbm_peak_gb

LAYER = "Device"
UNIT = "GB"
MOVES = "train_tokens_per_s_per_chip"
SOURCE = "program_counter"


def read(trace, counters, ctx):
    return hbm_peak_gb(counters)
