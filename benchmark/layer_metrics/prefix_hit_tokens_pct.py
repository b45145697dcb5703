"""Of the prompt tokens looked up in the prefix cache at admission over the
window, the share served from cached pages (attached or copied): the
program's `prefix_hit_tokens` over `prefix_lookup_tokens`
(`LLMMetrics.counters`, always on). A session's turn hits its whole context
but its last answer and its new tokens."""
LAYER = "Serve host loop"
UNIT = "%"
MOVES = "serve_out_tokens_per_s"
SOURCE = "program_counter"


def read(trace, counters, ctx):
    hit = counters.get("prefix_hit_tokens_window")
    looked = counters.get("prefix_lookup_tokens_window")
    return None if hit is None or not looked else 100.0 * hit / looked
