"""hetero_full_attn_hbm_pct — share of the chip's HBM bandwidth the full layers' attention kernel reaches.

Needed bytes of the full layers' attention of one decode step
(``lib/costs_hetero_gqa_moe.attention_bytes``: the K and V of every token in
every busy lane's context at their published widths, 4 x (192 + 128) x 2 B =
2,560 B a token a layer, two layers, plus the queries in and the outputs
back) over 819 GB/s (``peaks.json``) and over the kernel's device time in a
``_decode`` execution (``hetero_full_attn_ms_per_step``). The pool holds a
key in 256 lanes (3,072 B a token): the padding is read and not needed, so
this share cannot pass 83 %. Lanes and context as ``decode_hbm_pct`` takes
them.
"""

from benchmark.lib.costs_hetero_gqa_moe import attention_share


def read(view):
    return attention_share(view, r"paged_flash_attention", sliding=False)
