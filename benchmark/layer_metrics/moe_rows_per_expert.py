"""Rows an expert's group holds in a mean step of the window: the step's
live assignments (prompt tokens prefilled + output tokens, over the steps,
x experts per token) over the experts a sparse layer holds. The grouped
matmul walks row tiles of 128 (`ops/grouped_matmul.py::TILE_M`): at a few
rows an expert a tile straddles many groups and is visited once for each,
and every expert's weights are read for a handful of rows, so this is the
number that says whether a group fills a tile (OLMoE's decode cell: 16-32;
256 experts at 128-512 live positions: 4-16). Nothing to read for a family
that states no expert layer (`families/<family>.py::expert_shape`)."""
from .moe_gmm_share_roofline import expert_shape

LAYER = "Expert layer"
UNIT = "rows"
MOVES = "tpot_p50_ms"
SOURCE = "program_counter"


def read(trace, counters, ctx):
    shape = expert_shape(ctx.config)
    steps = counters.get("steps")
    if shape is None or not steps:
        return None
    live = (counters["prefill_tokens"] + counters["output_tokens"]) / steps
    return live * shape["per_token"] / shape["held"]
