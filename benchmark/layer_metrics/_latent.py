"""Shared by the latent-layer metrics: the latent walk's events in the trace
and what one such call must do at the least.

The kernel is found by the name its `pallas_call` carries into the
instruction (`paged_latent`, `paddle_tpu/ops/paged_attention.py`; the name
holds neither `paged_attention` nor `paged_window`, which the other walks'
readers find by substring). One latent layer calls it once a step.

The least one call needs (`latent_cost`), from the keys resident over the
step's active rows, the query positions it really has, and the model's
shape (`heads` query heads over one latent of `latent` columns and one
rotary key of `rope` columns a token):
  bytes       every resident page read ONCE: the scores and the values
              come from the same latent page, so (resident keys + half a
              page a row) x (latent + rope) x 2 B, where a K/V cache is
              read twice; plus the queries in (heads x (latent + rope)) and
              the results out (heads x latent) for each query position.
              The rotary key is stored in whole lane tiles (128 columns for
              64); the pad is not counted: bytes counted low, never high.
  operations  every query position against its row's mean resident length,
              for every head: q . [c | r] and p . c, 2 x (latent + rope)
              + 2 x latent a key.
The resident keys are the program's own count, `LLMMetrics.counters
["full_kv_tokens"]` (per committed step the sum over its active rows of the
length after the step), which the job reads at the window's two ends
(`jobs/serve_closed_loop_long.py`) and divides by the steps between. A
chunk of 16 queries x 64 heads against a key is compute-bound; a decode
row's one query is not: the roofline takes the larger of the two bounds
over the step's mix."""
from .. import cells, kernel_costs
from ..trace import reduce as R

KERNEL = "paged_latent"


def kernel_time(trace) -> tuple:
    """(seconds, calls) of the latent walks, per chip."""
    return R.op_time_s(trace, KERNEL, opcode="custom-call")


def latent_cost(kv_tokens: float, active_rows: float, query_tokens: float,
                block_len: int, heads: int, latent: int, rope: int,
                itemsize: int = 2) -> tuple:
    """(operations, bytes) of one latent walk over a batch of rows."""
    pages_tokens = kv_tokens + active_rows * (block_len - 1) / 2.0
    bytes_ = pages_tokens * (latent + rope) * itemsize \
        + query_tokens * heads * (2 * latent + rope) * itemsize
    mean_len = kv_tokens / active_rows if active_rows else 0.0
    flops = 2.0 * query_tokens * mean_len * heads * (2 * latent + rope)
    return flops, bytes_


def call_cost(counters: dict, config: dict):
    """(operations, bytes) of one latent call of a mean step of the
    window, or None where the program or the job left no count, or the
    family has no latent cache."""
    kv = counters.get("full_kv_tokens_per_step")
    rows = counters.get("active_rows_per_step")
    if not kv or not rows or not counters.get("steps"):
        return None
    shape = cells.family_module(config).attention_shape(config)
    if "latent" not in shape:
        return None
    useful = (counters["prefill_tokens"] + counters["output_tokens"]) \
        / counters["steps"]
    return latent_cost(kv, rows, useful, counters["block_len"],
                       shape["heads"], shape["latent"], shape["rope"])
