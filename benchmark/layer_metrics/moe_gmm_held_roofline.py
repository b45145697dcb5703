"""The grouped matmuls' share of their roofline where the expert layers hold
a share of the router's experts (`num_local_experts` of
`num_local_experts_published`; `DroplessMoE(held=...)`): the least time the
chip could take for what the held experts of the traced steps must do
(`_moe.py`: the held experts' weights read once, 6*h*f operations and a row
in and out for each assignment that falls on a held expert; memory-bound at
a decode step) over the time the `moe_gmm` calls took. `moe_gmm_roofline`
counts every live position's `num_experts_per_tok` assignments over
`num_experts`, which is what a whole layer sees; a share sees the part of
them the router sends its way: the job's live positions of a mean step x
experts per token x the program's own count of that part
(`moe_held_share_pct`'s)."""
from .. import kernel_costs
from . import _moe

LAYER = "Expert layer"
UNIT = "%"
MOVES = "tpot_p50_ms"
SOURCE = "device_trace"


def held_share(config: dict):
    """Assignments that fell on experts this program holds over the
    assignments its routers made, all layers, the whole run (set-up's check
    requests included): `nn.layer.moe.EXPERT_TOKENS` over `ROUTED_TOKENS`
    x experts per token. None where the program keeps no such tables or
    they are empty (the parent; a dense model)."""
    from paddle_tpu.nn.layer import moe
    held = getattr(moe, "EXPERT_TOKENS", None)
    routed = getattr(moe, "ROUTED_TOKENS", None)
    if not held or not routed:
        return None
    return sum(held.values()) \
        / (sum(routed.values()) * config["num_experts_per_tok"])


def read(trace, counters, ctx):
    if trace is None or ctx.peaks is None \
            or "num_local_experts" not in ctx.config:
        return None
    seconds, calls = _moe.kernel_time(trace)
    routed = _moe.assignments_per_step(counters, ctx.config)
    share = held_share(ctx.config)
    if not calls or not routed or not share:
        return None
    flops, bytes_ = _moe.layer_cost(
        routed * share, ctx.config["num_local_experts"],
        ctx.config["hidden_size"], ctx.config["intermediate_size"])
    layer_steps = calls / _moe.CALLS_PER_LAYER
    least = layer_steps * kernel_costs.min_seconds(flops, bytes_, ctx.peaks)
    return 100.0 * least / seconds
