"""The windowed paged kernel's share of its roofline: the least time the
chip could take to read the pages that cut the rows' windows, once per KV
head (memory-bound; `_window.py`, from the program's `window_kv_tokens`),
over the time the `paged_window` calls took. It does not grow with a row's
length past the window; what the walk spends on steps that fetch nothing
and on its per-step overhead is what keeps it from 100."""
from .. import kernel_costs
from . import _window

LAYER = "Window layers"
UNIT = "%"
MOVES = "tpot_p50_ms"
SOURCE = "device_trace"


def read(trace, counters, ctx):
    if trace is None or ctx.peaks is None:
        return None
    seconds, calls = _window.kernel_time(trace)
    cost = _window.call_cost(counters, ctx.config)
    if not calls or cost is None:
        return None
    least = calls * kernel_costs.min_seconds(*cost, ctx.peaks)
    return 100.0 * least / seconds
