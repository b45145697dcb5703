"""The sparse layers' latent attention's share of its roofline: the least
time the chip could take for the traced steps' attention over the selected
keys (`_sparse.sparse_cost`: a query's min(position + 1, index_topk) keys x
64 heads x 2 x (2 x 512 + 64) operations, those tokens' 1,280 B read once a
query, queries in, results out; the larger of the two bounds; from the
program's `sparse_keys_selected`) over the time the `paged_sparse` calls
took, every layer of the model. What the implementation pays beyond the
selected keys (a prefill row's walk streams the row's every page and
computes every key under a mask; a decode row's gather is XLA's, outside the
kernel's events) is what keeps it from 100 or, for the gather, is not in the
denominator: `PERF.md` says which."""
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
    selected = _sparse.per_step(counters, "sparse_keys_selected")
    live = _sparse.live_positions(counters)
    seconds, calls = _sparse.kernel_time(trace, _sparse.SPARSE)
    steps = _sparse.steps_in(trace, counters)
    if not calls or not steps or shape is None or not selected or not live:
        return None
    cost = _sparse.sparse_cost(selected, live, shape["heads"],
                               shape["latent"], shape["rope"])
    layers = ctx.config["num_hidden_layers"]
    least = steps * layers * kernel_costs.min_seconds(*cost, ctx.peaks)
    return 100.0 * least / seconds
