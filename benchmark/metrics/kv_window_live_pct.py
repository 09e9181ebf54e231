"""kv_window_live_pct — bytes live in the window pool over what keeping every token would hold.

The sliding layers' rows (6,528 B a token over the three layers) live in a
pool of their own whose pages are freed as the 513-token window passes. The
harness samples the gauge ``arkflow_gen_kv_live_bytes`` summed over its
pools (latent + index + window, ``tpu/serving.py::_update_gauges``); the
kept pools' part is the tokens the kept pages hold (gauge
``arkflow_gen_page_pool_occupancy`` x the pool's pages x the page size)
times their bytes a token, the rest is the window pool's. That, over what
the same tokens would occupy in the sliding layers were their rows kept for
the request's life, as the full layers' are. What freeing pages saves.
"""

from benchmark.lib.costs_sparse_window import layer_counts


def read(view):
    live = view.gauge("arkflow_gen_kv_live_bytes")
    occupancy = view.gauge("arkflow_gen_page_pool_occupancy")
    s, p = view.sizes, view.proc_cfg
    if not live or not occupancy or "sliding_window_size" not in s:
        return None
    full, sliding = layer_counts(s)
    kept_row = 2 * full * (s["kv_lora_rank"] + s["qk_rope_head_dim"]
                           + s["index_head_dim"])
    window_row = 2 * sliding * (s["swa_kv_lora_rank"] + s["swa_qk_rope_head_dim"])
    page = p["page_size"]
    pages = p["slots"] * -(-(p["max_input"] + p["max_new_tokens"]) // page)
    kept_tokens = sum(occupancy) / len(occupancy) * pages * page
    if kept_tokens <= 0 or window_row <= 0:
        return None
    window_live = sum(live) / len(live) - kept_tokens * kept_row
    return 100.0 * window_live / (kept_tokens * window_row)
