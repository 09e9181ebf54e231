"""hetero_window_attn_hbm_pct — share of the chip's HBM bandwidth the window attention kernel reaches.

Needed bytes of the sliding layers' attention of one decode step
(``lib/costs_hetero_gqa_moe.attention_bytes``: the last 128 tokens' K and V
of every busy lane at their published widths, 8 x (192 + 128) x 2 B = 5,120
B a token a layer, five layers, plus the queries in and the outputs back)
over 819 GB/s (``peaks.json``) and over the kernel's device time in a
``_decode`` execution (``hetero_window_attn_ms_per_step``). The pool holds a
key in 256 lanes (6,144 B a token): the padding is read and not needed, so
it lowers this share. Lanes and context as ``decode_hbm_pct`` takes them.
"""

from benchmark.lib.costs_hetero_gqa_moe import attention_share


def read(view):
    return attention_share(view, r"paged_window_attention", sliding=True)
