"""The latent layers' part of the KV pool: the bytes of the latent pages
(every slot, every latent layer, the latent and the rotary key as stored,
the write pad included) the engine's pool holds, from the program's own
gauge (`pdtpu_llm_kv_pool_bytes{kind="latent"}`), which it also leaves in a
process-wide value for a reader that comes after the engine is gone
(`paddle_tpu.serving.metrics.KV_POOL_BYTES`). Fixed at construction: slots x
layers x (`context_tokens` + a chunk) x (latent + rotary key in whole lane
tiles) x 2 B; the same slots as K and V of every head would hold 32 times
that. Nothing to read on a program without such a gauge, or for a model
without latent layers."""
LAYER = "Latent layers"
UNIT = "GB"
MOVES = "serve_out_tokens_per_s"
SOURCE = "program_counter"


def read(trace, counters, ctx):
    from paddle_tpu.serving import metrics
    nbytes = getattr(metrics, "KV_POOL_BYTES", {}).get("latent")
    return None if not nbytes else nbytes / 1e9
