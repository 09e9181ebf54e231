"""swa_attn_hbm_pct — share of the chip's HBM bandwidth the window attention kernel reaches.

Needed bytes of the sliding layers' attention of one decode step
(``lib/costs_sparse_window.window_attention_bytes``: the last 513 tokens'
latent rows and rope keys of every busy lane, 2,176 B each, read once for
all heads, plus the queries in and the per-head latent outputs back) over
819 GB/s (``peaks.json``) and over the kernel's device time in a ``_decode``
execution (``swa_attn_ms_per_step``). Lanes and context as
``mla_attn_hbm_pct`` takes them.
"""

from benchmark.lib.costs_mla_moe import decode_context, kernel_ms_per_decode
from benchmark.lib.costs_sparse_window import layer_counts, window_attention_bytes


def read(view):
    ms = kernel_ms_per_decode(view, r"swa_latent_attention")
    ctx = decode_context(view)
    s = view.sizes
    if ms is None or ctx is None or "sliding_window_size" not in s:
        return None
    nbytes = window_attention_bytes(
        heads=s["swa_num_attention_heads"], kv_lora=s["swa_kv_lora_rank"],
        rope=s["swa_qk_rope_head_dim"], window=s["sliding_window_size"],
        context=ctx[1], queries=ctx[0], layers=layer_counts(s)[1])
    return 100.0 * nbytes / view.peaks["hbm_bytes_per_s"] / (ms * 1e-3)
