"""kv_window_pages_freed_pct — window-pool pages freed by the window's passing over the pages its tokens filled.

Counter ``arkflow_gen_window_pages_freed_total`` (``tpu/serving.py::
_slide_window``: a page of the sliding layers' pool handed back because the
513-token window passed it, while its request still runs) times the page
size, over the tokens that went through the cache in the window: every
token of a chunk or a decode step is routed ``num_experts_per_tok`` times in
each expert layer, so ``arkflow_gen_moe_assignments_total`` over those two is
their number. A request of L tokens frees (L - 513) / 16 of its L / 16 pages
on the way and the rest when it ends: about 90 % at the mix's mean 4.9k; 0
would say pages are held to a request's end, as the full layers' are.
"""


def read(view):
    freed = view.counter("arkflow_gen_window_pages_freed_total")
    pairs = view.counter("arkflow_gen_moe_assignments_total")
    s = view.sizes
    if pairs <= 0 or "sliding_window_size" not in s:
        return None
    expert_layers = int(s["num_hidden_layers"]) - int(s["first_k_dense_replace"])
    tokens = pairs / (int(s["num_experts_per_tok"]) * expert_layers)
    return 100.0 * freed * view.proc_cfg["page_size"] / tokens
