"""gdn_full_attn_hbm_pct — share of the chip's HBM bandwidth the full layers' paged attention reaches (Qwen3-Next's keys).

Needed bytes of the two full layers' attention in one decode step — the K
and V rows the lanes' queries may attend, 2 K/V heads x 256 x K and V x 2 B =
2,048 B a token a layer (4,096 B over both: what ``cache_spec`` states), plus
the queries in and the outputs back — over 819 GB/s (``peaks.json``) and over
the ``paged_flash_attention`` kernel's device time in a ``_decode`` execution
(``gdn_full_attn_ms_per_step``): ``lib/costs_gdn_gqa_moe.
full_attention_share``. The accepted readers read other files' keys.
"""

from benchmark.lib.costs_gdn_gqa_moe import full_attention_share


def read(view):
    return full_attention_share(view)
