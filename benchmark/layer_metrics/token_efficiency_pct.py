"""Useful positions of the unified step: prompt tokens prefilled plus
output tokens emitted in the window, over steps x slots x prefill_chunk
(every step computes the whole [slots, prefill_chunk] block). A count, by
the benchmark's own arithmetic over the engine's always-on counters."""

LAYER = "Unified step"
UNIT = "%"
MOVES = "serve_out_tokens_per_s"
SOURCE = "program_counter"


def read(trace, counters, ctx):
    steps = counters.get("steps")
    if not steps:
        return None
    useful = counters["prefill_tokens"] + counters["output_tokens"]
    return 100.0 * useful / (steps * counters["slots"]
                             * counters["prefill_chunk"])
