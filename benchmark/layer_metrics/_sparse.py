"""Shared by the sparse-attention metrics: the three kernels' events in the
trace, the program's counters over the window, and what one call must do at
the least.

The kernels are found by the names their `pallas_call`s carry into the
instructions (`paddle_tpu/ops/paged_attention.py`, `ops/index_select.py`):
`paged_sparse` (the latent attention of a layer under an indexer: a decode
row's walk over its gathered keys and a prefill row's walk under its
columns' masks are both that name), `index_score` and `index_topk` (one
call each a "full" layer and step). No name holds another kernel's.

The work is counted from what the model must do, whatever implements it,
with the program's own counters (`LLMMetrics.counters`, always on; the job
leaves their change over the window in `counters` as `<name>_window`):
`sparse_keys_selected` and `sparse_keys_resident` are, summed over a step's
live query positions p, min(p + 1, index_topk) and p + 1.

  `sparse_cost`   one sparse layer, one step:
    operations  selected keys x heads x 2 x (2 x latent + rope)
                (q . [c | r] and p . c, for every head)
    bytes       the selected tokens' [c | r] as stored (latent + the rotary
                key in whole lane tiles: 1,280 B in bf16) each read once a
                query; the queries in (heads x (latent + rope stored)) and
                the results out (heads x latent) a live position. A walk
                over the union reads fewer bytes than this where columns
                share keys: counted high is a share counted low, never over
                100 for that.
  `index_cost`    one "full" layer, one step:
    operations  resident keys x index heads x index width x 2
    bytes       the active rows' index keys read once a row
                (`full_kv_tokens` x index width x 2 B) and a float32 score
                out a query and resident key.
"""
from .. import cells
from ..trace import reduce as R

SPARSE, SCORE, TOPK = "paged_sparse", "index_score", "index_topk"
LANES = 128


def kernel_time(trace, name: str) -> tuple:
    """(seconds, calls) of one kernel, per chip."""
    return R.op_time_s(trace, name, opcode="custom-call")


def steps_in(trace, counters) -> int:
    runs = R.module_runs(trace, counters.get("main_module", ""))
    return runs["count"] if runs else 0


def per_step(counters: dict, name: str):
    """A counter's mean a step over the window, or None."""
    steps = counters.get("unified_steps_window")
    value = counters.get(f"{name}_window")
    return None if not steps or value is None else value / steps


def shape(config: dict):
    """The family's `attention_shape` where it states an indexer."""
    family = cells.family_module(config)
    if not hasattr(family, "attention_shape"):
        return None
    out = family.attention_shape(config)
    return out if "index_topk" in out else None


def live_positions(counters: dict):
    steps = counters.get("steps")
    if not steps:
        return None
    return (counters["prefill_tokens"] + counters["output_tokens"]) / steps


def sparse_cost(selected: float, live: float, heads: int, latent: int,
                rope: int, itemsize: int = 2) -> tuple:
    stored = latent + -(-rope // LANES) * LANES
    flops = 2.0 * selected * heads * (2 * latent + rope)
    bytes_ = selected * stored * itemsize \
        + live * heads * (stored + latent) * itemsize
    return flops, bytes_


def index_cost(resident: float, row_keys: float, heads: int, width: int,
               itemsize: int = 2) -> tuple:
    flops = 2.0 * resident * heads * width
    return flops, row_keys * width * itemsize + resident * 4
