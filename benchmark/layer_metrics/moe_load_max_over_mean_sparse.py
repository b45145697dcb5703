"""`moe_load_max_over_mean` over the layers that have experts: the busiest
expert's live assignments over the mean expert's, per sparse layer,
averaged over those layers (1.0 = even; experts / experts per token = every
position chose the same experts). `moe_load_max_over_mean` asks for a row
of every layer of the model and so returns nothing where a leading dense
layer has none; here the rows are the family's own count of sparse layers
(`families/<family>.py::expert_shape`), which is how the program numbers
them: row i of `paddle_tpu.nn.layer.moe.EXPERT_TOKENS` is the i-th layer
that has experts, not the i-th layer. The whole run, set-up's check
requests included. With many small experts the mean is a few rows a step,
so the ratio also says how far the largest group is from the smallest."""
from .moe_gmm_share_roofline import expert_shape

LAYER = "Expert layer"
UNIT = "ratio"
MOVES = "tpot_p50_ms"
SOURCE = "program_counter"


def sparse_rows(config: dict):
    """The program's totals as `[sparse layers][experts held]`, or None
    where the family states no expert layer, the program keeps no table or
    a sparse layer's row is empty."""
    shape = expert_shape(config)
    if shape is None:
        return None
    from paddle_tpu.nn.layer import moe
    table = getattr(moe, "EXPERT_TOKENS", None)
    if not table:
        return None
    rows = [[int(table.get((layer, e), 0)) for e in range(shape["held"])]
            for layer in range(shape["layers"])]
    return rows if rows and all(sum(row) for row in rows) else None


def read(trace, counters, ctx):
    rows = sparse_rows(ctx.config)
    if rows is None:
        return None
    return sum(max(row) * len(row) / sum(row) for row in rows) / len(rows)
