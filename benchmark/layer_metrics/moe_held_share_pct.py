"""Where the expert layers hold a share of the router's experts: the part
of the routers' assignments that fell on experts held here, over the whole
run, from the program's always-on totals (`nn.layer.moe.EXPERT_TOKENS`
over `ROUTED_TOKENS` x experts per token). `num_local_experts /
num_local_experts_published` (25% for 18 of 72) when the router spreads
evenly; what is above that is this chip's surplus under expert
parallelism, and the rows its grouped matmuls and its dispatch carry."""
from .moe_gmm_held_roofline import held_share

LAYER = "Expert layer"
UNIT = "%"
MOVES = "tpot_p50_ms"
SOURCE = "program_counter"


def read(trace, counters, ctx):
    if "num_local_experts" not in ctx.config:
        return None
    share = held_share(ctx.config)
    return None if share is None else 100.0 * share
