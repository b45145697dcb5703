"""`moe_gmm_held_roofline` for a family that states its expert layer through
its module (`families/<family>.py::expert_shape`: hidden size, expert width,
experts held and published, experts per token, layers that have experts)
and not through granite's key spellings: the least time the chip could take
for what the held experts of the traced steps must do (`_moe.layer_cost`:
the held experts' weights read once, 6 h f operations and a row in and out
for each assignment that falls on a held expert) over the time the
`moe_gmm` calls took. The assignments that fall here are the job's live
positions of a mean step x experts per token x the program's own count of
that part (`moe_share_here_pct`'s)."""
from .. import cells, kernel_costs
from . import _moe

LAYER = "Expert layer"
UNIT = "%"
MOVES = "tpot_p50_ms"
SOURCE = "device_trace"


def expert_shape(config: dict):
    """The family's statement of its expert layer, or None."""
    family = cells.family_module(config)
    return family.expert_shape(config) \
        if hasattr(family, "expert_shape") else None


def share_here(shape: dict):
    """Assignments that fell on experts this program holds over the
    assignments its routers made, all sparse layers, the whole run (set-up's
    check requests included): `nn.layer.moe.EXPERT_TOKENS` over
    `ROUTED_TOKENS` x experts per token. None where the program keeps no
    such tables or they are empty."""
    from paddle_tpu.nn.layer import moe
    held = getattr(moe, "EXPERT_TOKENS", None)
    routed = getattr(moe, "ROUTED_TOKENS", None)
    if not held or not routed:
        return None
    return sum(held.values()) / (sum(routed.values()) * shape["per_token"])


def read(trace, counters, ctx):
    shape = expert_shape(ctx.config)
    if trace is None or ctx.peaks is None or shape is None:
        return None
    seconds, calls = _moe.kernel_time(trace)
    steps = counters.get("steps")
    share = share_here(shape)
    if not calls or not steps or not share:
        return None
    live = (counters["prefill_tokens"] + counters["output_tokens"]) / steps
    flops, bytes_ = _moe.layer_cost(
        live * shape["per_token"] * share, shape["held"], shape["hidden"],
        shape["width"])
    layer_steps = calls / _moe.CALLS_PER_LAYER
    least = layer_steps * kernel_costs.min_seconds(flops, bytes_, ctx.peaks)
    return 100.0 * least / seconds
