"""The most the fullest chip held inside the window: the largest sample of
`memory_stats()["bytes_in_use"]` (window open and close, and while a step
runs), so set-up's transients do not count; serve cells."""
from ._device import hbm_peak_gb

LAYER = "Device"
UNIT = "GB"
MOVES = "serve_out_tokens_per_s"
SOURCE = "program_counter"


def read(trace, counters, ctx):
    return hbm_peak_gb(counters)
