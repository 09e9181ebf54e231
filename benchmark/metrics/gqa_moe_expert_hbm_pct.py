"""gqa_moe_expert_hbm_pct — share of the chip's HBM bandwidth the expert kernel reaches (K-EXAONE's keys).

Needed bytes of the expert products of one decode step
(``lib/costs_window_gqa_moe.expert_product_bytes``: per expert layer the
HELD routed experts the step hit — the program's counter — and the shared
expert, three bf16 6,144 x 2,048 matrices each) over 819 GB/s
(``peaks.json``) and over the ``moe_expert_swiglu`` kernel's device time in
a ``_decode`` execution. ``moe_expert_hbm_pct``'s count under this source's
keys (``num_shared_experts``).
"""

from benchmark.lib.costs_mla_moe import decode_routing, kernel_ms_per_decode
from benchmark.lib.costs_window_gqa_moe import expert_product_bytes, sizes_of


def read(view):
    ms = kernel_ms_per_decode(view, r"moe_expert_swiglu")
    routing, s = decode_routing(view), sizes_of(view)
    if ms is None or routing is None or s is None:
        return None
    nbytes = expert_product_bytes(
        hidden=s["hidden"], moe_width=s["moe_width"], shared=s["shared"],
        experts_hit=routing[0], expert_layers=s["layers"] - s["dense_layers"])
    return 100.0 * nbytes / view.peaks["hbm_bytes_per_s"] / (ms * 1e-3)
