"""The indexer's scoring's share of its roofline: the least time the chip
could take for the traced steps' index scores (`_sparse.index_cost`: a live
query position's resident keys x 32 heads x 128 x 2 operations, from the
program's `sparse_keys_resident`; the active rows' index keys read once a
row, from `full_kv_tokens`, and a float32 score out a query and key; the
larger of the two bounds) over the time the `index_score` calls took, every
"full" layer. A decode row's fifteen dead query columns, computed with the
live one, are what keeps it from 100 first."""
from .. import kernel_costs
from . import _sparse

LAYER = "Sparse attention"
UNIT = "%"
MOVES = "serve_out_tokens_per_s"
SOURCE = "device_trace"


def read(trace, counters, ctx):
    if trace is None or ctx.peaks is None:
        return None
    shape = _sparse.shape(ctx.config)
    resident = _sparse.per_step(counters, "sparse_keys_resident")
    row_keys = _sparse.per_step(counters, "full_kv_tokens")
    seconds, calls = _sparse.kernel_time(trace, _sparse.SCORE)
    steps = _sparse.steps_in(trace, counters)
    if not calls or not steps or shape is None or not resident \
            or not row_keys:
        return None
    cost = _sparse.index_cost(resident, row_keys, shape["index_heads"],
                              shape["index_dim"])
    least = steps * shape["index_layers"] \
        * kernel_costs.min_seconds(*cost, ctx.peaks)
    return 100.0 * least / seconds
