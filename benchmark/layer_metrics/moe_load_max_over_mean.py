"""How unevenly the router loads the experts: the busiest expert's live
assignments over the mean expert's, per layer, averaged over the layers
(1.0 = even; `num_experts / num_experts_per_tok` = every position chose the
same experts, a collapsed router). From the program's always-on totals of
live assignments per (layer, expert), which it leaves in a process-wide
table when the engine stops (`paddle_tpu.nn.layer.moe.EXPERT_TOKENS`): the
whole run, set-up's check requests included."""
from ._moe import expert_tokens

LAYER = "Expert layer"
UNIT = "ratio"
MOVES = "tpot_p50_ms"
SOURCE = "program_counter"


def read(trace, counters, ctx):
    if "num_experts" not in ctx.config:
        return None
    totals = expert_tokens(ctx.config)
    if totals is None or not all(sum(row) for row in totals):
        return None
    return sum(max(row) * len(row) / sum(row) for row in totals) \
        / len(totals)
