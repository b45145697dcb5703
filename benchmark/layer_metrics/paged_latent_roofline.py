"""The latent paged kernel's share of its roofline: the least time the chip
could take for the calls of the traced steps (`_latent.py`: every resident
latent page read once for scores and values alike, 2 x (2 x latent + rope)
operations a head, query and key; the larger of the memory and the compute
bound, from the program's `full_kv_tokens`) over the time the
`paged_latent` calls took. What the walk pays for dead query columns of a
decode row (16 computed for one live), for masked keys of a row's last
group and for its per-group overhead is what keeps it from 100."""
from .. import kernel_costs
from . import _latent

LAYER = "Latent layers"
UNIT = "%"
MOVES = "tpot_p50_ms"
SOURCE = "device_trace"


def read(trace, counters, ctx):
    if trace is None or ctx.peaks is None:
        return None
    seconds, calls = _latent.kernel_time(trace)
    cost = _latent.call_cost(counters, ctx.config)
    if not calls or cost is None:
        return None
    least = calls * kernel_costs.min_seconds(*cost, ctx.peaks)
    return 100.0 * least / seconds
