"""kv_window_share_of_cache_pct — the window pool's share of the bytes live in the cache.

The harness samples the gauge ``arkflow_gen_kv_live_bytes`` summed over its
pools (``kv`` + ``kv_window``, ``tpu/serving.py::_update_gauges``). The kept
pool's part is the tokens the kept pages hold (gauge
``arkflow_gen_page_pool_occupancy`` x the pool's pages x the page size)
times the full layers' row AS HELD (``lib/costs_hetero_gqa_moe.
held_row_bytes``: 3,072 B a token a layer, two layers); the rest is the
window pool's. Its row is twice as wide (8 K/V heads against 4) over five
layers against two, and lives 128 tokens: at the mix's contexts (mean 5.4k)
the five sliding layers hold about a tenth of the cache; kept for a
request's life they would hold 5 / 6 of it.
"""

from benchmark.lib.costs_hetero_gqa_moe import held_row_bytes, sizes_of


def read(view):
    live = view.gauge("arkflow_gen_kv_live_bytes")
    occupancy = view.gauge("arkflow_gen_page_pool_occupancy")
    s, p = sizes_of(view), view.proc_cfg
    if not live or not occupancy or s is None or not s["sliding_layers"]:
        return None
    page = p["page_size"]
    pages = p["slots"] * -(-(p["max_input"] + p["max_new_tokens"]) // page)
    kept = (sum(occupancy) / len(occupancy) * pages * page * s["full_layers"]
            * held_row_bytes(kv_heads=s["kv_heads"], key_dim=s["key_dim"],
                             value_dim=s["value_dim"]))
    total = sum(live) / len(live)
    return None if total <= 0 else 100.0 * (total - kept) / total
