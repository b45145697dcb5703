"""What the state-space layers keep per slot beside the paged K/V: the bytes
of recurrent state (conv inputs and the recurrence's state, every slot,
every such layer) the engine's pool holds, from the program's own gauge
(`pdtpu_llm_recurrent_state_bytes`), which it also leaves in a process-wide
value for a reader that comes after the engine is gone
(`paddle_tpu.serving.metrics.RECURRENT_STATE_BYTES`). Fixed at construction: it
does not follow the traffic; it falls when the state's type or the slots
shrink. Nothing to read on a program without such a gauge, or for a model
without recurrent layers."""
LAYER = "State-space layer"
UNIT = "GB"
MOVES = "serve_out_tokens_per_s"
SOURCE = "program_counter"


def read(trace, counters, ctx):
    from paddle_tpu.serving import metrics
    nbytes = getattr(metrics, "RECURRENT_STATE_BYTES", 0)
    return None if not nbytes else nbytes / 1e9
