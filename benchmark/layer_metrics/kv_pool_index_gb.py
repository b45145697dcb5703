"""The index-key pages' part of the KV pool: the bytes of the third slab of
every layer that carries an indexer (every slot, one index key of
`index_head_dim` a token, the write pad included), from the program's own
gauge (`pdtpu_llm_kv_pool_bytes{kind="index"}`, left in
`paddle_tpu.serving.metrics.KV_POOL_BYTES`). Fixed at construction. Nothing
to read on a program without such a gauge, or for a model without an
indexer."""
LAYER = "Sparse attention"
UNIT = "GB"
MOVES = "serve_out_tokens_per_s"
SOURCE = "program_counter"


def read(trace, counters, ctx):
    from paddle_tpu.serving import metrics
    nbytes = getattr(metrics, "KV_POOL_BYTES", {}).get("index")
    return None if not nbytes else nbytes / 1e9
