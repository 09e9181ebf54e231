"""narrow_attn_hbm_pct — share of the chip's HBM bandwidth the narrow-head attention kernel reaches.

Needed bytes of the three attention layers of one decode step
(``lib/costs_conv_gqa_moe.attention_bytes``: the K and V of every token in
every busy lane's context at their published width, 8 x 64 x 2 x 2 B =
2,048 B a token a layer — what the row-major pools hold: no padding — plus
the queries in and the outputs back) over 819 GB/s (``peaks.json``) and over
the kernel's device time in a ``_decode`` execution
(``narrow_attn_ms_per_step``). Lanes and context as ``decode_hbm_pct``
takes them.
"""

from benchmark.lib.costs_conv_gqa_moe import attention_share


def read(view):
    return attention_share(view)
