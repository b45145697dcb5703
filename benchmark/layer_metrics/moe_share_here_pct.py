"""`moe_held_share_pct` for a family that states its expert layer through
its module (`expert_shape`): the part of the routers' assignments that fell
on experts held here, over the whole run, from the program's always-on
totals. `held / published` (6.25% for 12 of 192) when the router spreads
evenly; a group-limited router sends a token's assignments to a few groups,
so a share that is part of one group sees more of some tokens and none of
others, and what is above the even share is this chip's surplus under
expert parallelism."""
from .moe_gmm_share_roofline import expert_shape, share_here

LAYER = "Expert layer"
UNIT = "%"
MOVES = "tpot_p50_ms"
SOURCE = "program_counter"


def read(trace, counters, ctx):
    shape = expert_shape(ctx.config)
    share = None if shape is None else share_here(shape)
    return None if share is None else 100.0 * share
