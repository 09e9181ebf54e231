"""gqa_window_attn_hbm_pct — share of the chip's HBM bandwidth the window attention kernel reaches.

Needed bytes of the sliding layers' attention of one decode step
(``lib/costs_window_gqa_moe.attention_bytes``: the last 128 tokens' K and V
of every busy lane, 4,096 B a token a layer, four layers, plus the queries
in and the outputs back) over 819 GB/s (``peaks.json``) and over the
kernel's device time in a ``_decode`` execution
(``gqa_window_attn_ms_per_step``). Lanes and context as ``decode_hbm_pct``
takes them.
"""

from benchmark.lib.costs_window_gqa_moe import attention_share


def read(view):
    return attention_share(view, r"paged_window_attention", sliding=True)
