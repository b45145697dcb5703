"""Seconds set-up spent in the backend: self time of JAX's
`backend_compile_duration` events before the window, which wrap the XLA and
Mosaic compile of a program the persistent cache lacks and the load of one
it holds alike. With `setup_cache_misses` at 0 it is the time to load."""
from ._setup import LAYER, MOVES, SOURCE, at_warm  # noqa: F401

UNIT = "s"


def read(trace, counters, ctx):
    frozen = at_warm()
    return None if frozen is None else frozen["totals"]["backend_s"]
