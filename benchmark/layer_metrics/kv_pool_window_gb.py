"""The window layers' part of the KV pool: the bytes of the rings (every
slot, every window layer, K and V, the write pad included) the engine's
pool holds, from the program's own gauge (`pdtpu_llm_kv_pool_bytes{kind=
"window"}`), which it also leaves in a process-wide value for a reader that
comes after the engine is gone (`paddle_tpu.serving.metrics.KV_POOL_BYTES`).
Fixed at construction: slots x window layers x (window + two chunks) x 2 KB
here, whatever the requests' lengths; with one geometry for every layer it
would be slots x layers x `context_tokens`. Nothing to read on a program
without such a gauge, or for a model without window layers."""
LAYER = "Window layers"
UNIT = "GB"
MOVES = "serve_out_tokens_per_s"
SOURCE = "program_counter"


def read(trace, counters, ctx):
    from paddle_tpu.serving import metrics
    nbytes = getattr(metrics, "KV_POOL_BYTES", {}).get("window")
    return None if not nbytes else nbytes / 1e9
