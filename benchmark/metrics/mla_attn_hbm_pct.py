"""mla_attn_hbm_pct — share of the chip's HBM bandwidth the latent attention kernel reaches.

Needed bytes of the latent paged attention of one decode step
(``lib/costs_mla_moe.latent_attention_bytes``: the latent row and rope key
of every attended token, 576 values x 2 B, read ONCE for all heads, plus the
queries in and the per-head latent outputs back, over all layers) over
819 GB/s (``peaks.json``) and over the kernel's device time in a ``_decode``
execution (``mla_attn_ms_per_step``). Lanes and context as ``decode_hbm_pct``
takes them: mean busy slots x (mean prompt + half of ``max_new_tokens``).
"""

from benchmark.lib.costs_mla_moe import (decode_context, kernel_ms_per_decode,
                                         latent_attention_bytes)


def read(view):
    ms = kernel_ms_per_decode(view, r"mla_paged_attention")
    ctx = decode_context(view)
    if ms is None or ctx is None:
        return None
    s = view.sizes
    nbytes = latent_attention_bytes(
        heads=s["num_attention_heads"], kv_lora=s["kv_lora_rank"],
        rope=s["qk_rope_head_dim"], kv_tokens=ctx[1], queries=ctx[0],
        layers=s["num_hidden_layers"])
    return 100.0 * nbytes / view.peaks["hbm_bytes_per_s"] / (ms * 1e-3)
