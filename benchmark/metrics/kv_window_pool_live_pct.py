"""kv_window_pool_live_pct — bytes live in the per-head window pool over what keeping every token would hold.

The four sliding layers' K and V (16,384 B a token over them) live in a pool
of their own whose pages are freed as the 128-token window passes. The
harness samples the gauge ``arkflow_gen_kv_live_bytes`` summed over its
pools (``kv`` + ``kv_window``, ``tpu/serving.py::_update_gauges``); the kept
pool's part is the tokens the kept pages hold (gauge
``arkflow_gen_page_pool_occupancy`` x the pool's pages x the page size)
times 4,096 B, the rest is the window pool's. That, over what the same
tokens would occupy in the sliding layers were their rows kept for the
request's life, as the full layer's are: what freeing pages saves, and what
lets 48 slots of 8,448 tokens fit.
"""

from benchmark.lib.costs_window_gqa_moe import kv_row_bytes, sizes_of


def read(view):
    live = view.gauge("arkflow_gen_kv_live_bytes")
    occupancy = view.gauge("arkflow_gen_page_pool_occupancy")
    s, p = sizes_of(view), view.proc_cfg
    if not live or not occupancy or s is None or not s["sliding_layers"]:
        return None
    row = kv_row_bytes(kv_heads=s["kv_heads"], head_dim=s["head_dim"])
    page = p["page_size"]
    pages = p["slots"] * -(-(p["max_input"] + p["max_new_tokens"]) // page)
    kept_tokens = sum(occupancy) / len(occupancy) * pages * page
    if kept_tokens <= 0:
        return None
    window_live = sum(live) / len(live) - kept_tokens * row * s["full_layers"]
    return 100.0 * window_live / (kept_tokens * row * s["sliding_layers"])
