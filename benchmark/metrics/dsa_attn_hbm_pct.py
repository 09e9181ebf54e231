"""dsa_attn_hbm_pct — share of the chip's HBM bandwidth the selected-keys attention kernel reaches.

Needed bytes of the indexed layers' attention of one decode step
(``lib/costs_sparse_window.selected_attention_bytes``: the latent row and
rope key of every SELECTED token — the program's counter, at most 2,048 a
lane a layer, 1,152 B each — read once for all heads, plus the queries in and
the per-head latent outputs back) over 819 GB/s (``peaks.json``) and over the
kernel's device time in a ``_decode`` execution (``dsa_attn_ms_per_step``).
"""

from benchmark.lib.costs_mla_moe import kernel_ms_per_decode
from benchmark.lib.costs_sparse_window import (dsa_decode_step, layer_counts,
                                               selected_attention_bytes)


def read(view):
    ms = kernel_ms_per_decode(view, r"dsa_sparse_attention")
    step = dsa_decode_step(view)
    if ms is None or step is None:
        return None
    s = view.sizes
    nbytes = selected_attention_bytes(
        heads=s["num_attention_heads"], kv_lora=s["kv_lora_rank"],
        rope=s["qk_rope_head_dim"], selected=step[1], queries=step[0],
        layers=layer_counts(s)[0])
    return 100.0 * nbytes / view.peaks["hbm_bytes_per_s"] / (ms * 1e-3)
