"""dsa_index_hbm_pct — share of the chip's HBM bandwidth the index-score kernel reaches.

Needed bytes of the indexer's scores of one decode step
(``lib/costs_sparse_window.index_scores_bytes``: the 256 B index key of
every token in context — the program's counter — read once for all 64 index
heads, the index queries in, one float32 score a key out) over 819 GB/s
(``peaks.json``) and over the kernel's device time in a ``_decode``
execution (``dsa_index_ms_per_step``).
"""

from benchmark.lib.costs_mla_moe import kernel_ms_per_decode
from benchmark.lib.costs_sparse_window import (dsa_decode_step, index_scores_bytes,
                                               layer_counts)


def read(view):
    ms = kernel_ms_per_decode(view, r"dsa_index_topk")
    step = dsa_decode_step(view)
    if ms is None or step is None:
        return None
    s = view.sizes
    nbytes = index_scores_bytes(
        index_heads=s["index_n_heads"], index_dim=s["index_head_dim"],
        context=step[2], queries=step[0], layers=layer_counts(s)[0])
    return 100.0 * nbytes / view.peaks["hbm_bytes_per_s"] / (ms * 1e-3)
