"""gqa_full_attn_hbm_pct — share of the chip's HBM bandwidth the full layers' attention kernel reaches.

Needed bytes of the full layer's attention of one decode step
(``lib/costs_window_gqa_moe.attention_bytes``: the K and V of every token
in every busy lane's context, 4,096 B a token, plus the queries in and the
outputs back) over 819 GB/s (``peaks.json``) and over the kernel's device
time in a ``_decode`` execution (``gqa_full_attn_ms_per_step``). Lanes and
context as ``decode_hbm_pct`` takes them.
"""

from benchmark.lib.costs_window_gqa_moe import attention_share


def read(view):
    return attention_share(view, r"paged_flash_attention", sliding=False)
