"""Of the keys a sparse layer's queries could see, the share they attend
to: the program's `sparse_keys_selected` over `sparse_keys_resident`, over
the window (summed over live query positions p: min(p + 1, index_topk) over
p + 1). `index_topk` over the mean context where contexts are long (2,048
over 16k-36k: 5-13); 100 while every context is under `index_topk`."""
LAYER = "Sparse attention"
UNIT = "%"
MOVES = "serve_out_tokens_per_s"
SOURCE = "program_counter"


def read(trace, counters, ctx):
    selected = counters.get("sparse_keys_selected_window")
    resident = counters.get("sparse_keys_resident_window")
    return None if not selected or not resident \
        else 100.0 * selected / resident
